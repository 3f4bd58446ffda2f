"""Record the small trace that ``bench/tests/test_trace.py`` reads.

    python3 bench/tests/fixtures/record.py <out.xplane.pb>

Runs on one TPU chip: two jitted programs (a matmul chain and a
reduction) for a few steps inside a host span ``bench:window``, with host
spans ``bench:step`` around each call and ``bench:idle`` around a short
sleep that leaves the device idle, and copies the profiler's
``.xplane.pb`` to the given path.
"""
import pathlib
import shutil
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from bench import trace
    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 2
    mm = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    red = jax.jit(lambda x: jnp.sum(x * x, axis=0))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((mm(x), red(x)))
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        with TraceAnnotation("bench:window"):
            for _ in range(3):
                with TraceAnnotation("bench:step"):
                    jax.block_until_ready(red(mm(x)))
                with TraceAnnotation("bench:idle"):
                    time.sleep(0.005)
    src = trace.find_xplane(d)
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(d)
    print(f"{out}: {pathlib.Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
