"""mfu.train: model FLOPs of the steps run in the traced window (from
shapes, ``bench/flops.py``) over the window, the chips and the chip's
bf16 peak (``bench/peaks.json``), in percent."""
from bench.harness import share


def read(m):
    if m.get("kind") != "train" or not m.get("steps"):
        return None
    work = m["steps"] * m["step_flops"]
    return share(work, m["window_s"] * m["chips"]
                 * m["peak"]["bf16_flops_per_s"], "mfu.train")
