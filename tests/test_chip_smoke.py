"""chip_smoke.py rehearsed on the CPU: its phases at reduced widths, its
four-chip comparison on four virtual CPU devices, and its refusal to run
anywhere but on a TPU."""
import importlib.util
import math
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN = ("--arch", "qwen2-0.5b", "--reduced", "--steps", "3", "--batch",
         "2", "--seq", "64", "--dtype", "bfloat16", "--log-every", "1")
SERVE = ("--arch", "qwen2-0.5b", "--reduced", "--mode", "paged",
         "--requests", "4", "--prompt-len", "40", "--min-prompt-len", "8",
         "--max-new", "4", "--max-seq", "64")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_full_width_arguments(smoke):
    from repro.launch import serve, train
    t = train.parse_args(list(smoke.TRAIN_ARGS))
    assert (t.arch, t.reduced, t.preset) == ("qwen2-0.5b", False, "")
    assert t.steps >= 3 and t.dtype == "bfloat16"
    s = serve.parse_args(list(smoke.SERVE_ARGS))
    assert (s.arch, s.reduced, s.mode) == ("qwen2-0.5b", False, "paged")
    assert len(set(smoke.SERVE_SLOTS)) == 2


def test_train_phase_reduced(smoke):
    res = smoke.train_phase(TRAIN)
    assert len(res["losses"]) == 3 == len(res["step_s"])
    assert all(math.isfinite(x) for x in res["losses"])
    assert res["compile_s"] > 0


def test_serve_phase_reduced(smoke):
    res = smoke.serve_phase(SERVE)
    assert res["served"] == [4, 4] and res["identical"]


@pytest.mark.parametrize("bad", [("--prompt-len", "100"),
                                 ("--min-prompt-len", "41")])
def test_serve_phase_fails_on_rejected_arguments(smoke, bad):
    with pytest.raises(smoke.PhaseFailed, match="exit 2"):
        smoke.serve_phase((*SERVE, *bad))


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_a_host_without_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    assert capsys.readouterr().out == ""


def test_four_chip_phase_on_virtual_devices():
    src = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        out = chip_smoke.four_chip_phase((
            "--arch", "qwen2-0.5b", "--reduced", "--steps", "2", "--batch",
            "4", "--seq", "32", "--dtype", "bfloat16", "--warmup", "1"))
        assert set(out) == {{"one_chip", "4x1", "2x2", "1x4"}}, out
        print("FOUR_CHIP_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", src], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert "FOUR_CHIP_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
