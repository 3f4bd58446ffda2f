"""mfu.serve: useful model FLOPs of the traced window (prompt tokens not
served from the prefix cache and decoded tokens, each at its context,
``bench/flops.py``) over the device time of the serve-step programs in
the window and the chip's bf16 peak, in percent."""
from bench.harness import share


def read(m):
    if m.get("kind") != "serve" or "trace" not in m:
        return None
    busy = m["trace"].get("program_s", 0.0)
    if busy <= 0 or m.get("useful_flops", 0) <= 0:
        return None
    return share(m["useful_flops"], busy * m["peak"]["bf16_flops_per_s"],
                 "mfu.serve")
