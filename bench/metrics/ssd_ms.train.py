"""ssd_ms.train: device time per training step of the Mamba-2 SSD core
(the chunk scan: forward, remat recompute and backward), in milliseconds
on each chip: the window's leaf operations under the program's ``ssd``
scope (``bench/scopes.py``, a mean over chips) over the steps run
(profiler trace)."""


def read(m):
    if m.get("kind") != "train" or not m.get("steps"):
        return None
    sec = m.get("trace", {}).get("scope_s", {}).get("ssd")
    return None if not sec else 1e3 * sec / m["steps"]
