"""idle_share.serve: share of the traced window in which no operation
ran on the device, on the idlest chip, in percent (profiler trace)."""


def read(m):
    if m.get("kind") != "serve" or "trace" not in m:
        return None
    return 100.0 * m["trace"]["idle_share_worst"]
