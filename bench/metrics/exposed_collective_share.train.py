"""exposed_collective_share.train: time in all-gather, reduce-scatter,
all-reduce, collective-permute and all-to-all operations during which no
other operation ran on that chip, over the traced window, on the worst
chip, in percent (profiler trace).  Nothing to read on one chip."""


def read(m):
    if m.get("kind") != "train" or m.get("chips", 1) < 2 \
            or "trace" not in m:
        return None
    t = m["trace"]
    return 100.0 * t["exposed_collective_s_worst"] / t["window_s"]
