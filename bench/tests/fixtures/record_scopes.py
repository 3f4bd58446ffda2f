"""Record the scoped trace that ``bench/tests/test_scopes.py`` reads.

    python3 bench/tests/fixtures/record_scopes.py <out prefix>

Runs on one TPU chip: the gradient of a ``lax.scan`` over four remat'd
layers, each a matmul under ``jax.named_scope("attn_core")`` and one of
the same size under ``jax.named_scope("mlp")``, for three steps inside a
host span ``bench:window``.  Writes the profiler's trace to
``<out prefix>.xplane.pb`` and the compiled program's HLO text to
``<out prefix>.hlo.txt``.  The rows outnumber the weights' columns
four to one, as a model's tokens outnumber its widths, so the scan's own
slicing of each layer's weights out of the stack (and of their gradients
back into it), which no scope names, is a small part of the time.
"""
import pathlib
import shutil
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))

LAYERS, ROWS, WIDTH = 4, 16384, 4096


def layer(x, w):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("attn_core"):
        h = jnp.tanh(x @ w[0])
    with jax.named_scope("mlp"):
        return jax.nn.relu(h @ w[1]), None


def loss(ws, x):
    import jax
    import jax.numpy as jnp
    y, _ = jax.lax.scan(jax.checkpoint(layer), x, ws)
    return jnp.mean(jnp.square(y.astype(jnp.float32)))


def record(out: str, layers=LAYERS, rows=ROWS, width=WIDTH) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from bench import trace
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    ws = (jax.random.normal(k1, (layers, 2, width, width), jnp.float32)
          / width ** 0.5).astype(jnp.bfloat16)
    x = jax.random.normal(k2, (rows, width), jnp.bfloat16)
    step = jax.jit(jax.grad(loss)).lower(ws, x).compile()
    jax.block_until_ready(step(ws, x))
    d = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(d):
            with TraceAnnotation("bench:window"):
                for _ in range(3):
                    g = step(ws, x)
                jax.block_until_ready(g)
        out = pathlib.Path(out)
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace.find_xplane(d), f"{out}.xplane.pb")
        pathlib.Path(f"{out}.hlo.txt").write_text(
            without_frames(step.as_text()))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def without_frames(hlo: str) -> str:
    """The HLO text less its stack-frame tables (``FileNames`` to
    ``StackFrames``), which hold the recording machine's source paths and
    nothing the reduction reads."""
    lines = hlo.splitlines(keepends=True)
    body = next(i for i, ln in enumerate(lines)
                if ln.startswith(("%", "ENTRY")))
    return lines[0] + "\n" + "".join(lines[body:])


def main(out: str) -> int:
    import jax
    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 2
    # an executable read from the persistent cache keeps the metadata of
    # the program that wrote it: compile afresh
    jax.config.update("jax_enable_compilation_cache", False)
    record(out)
    for suffix in (".xplane.pb", ".hlo.txt"):
        p = pathlib.Path(out + suffix)
        print(f"{p}: {p.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
