"""head_ms.train: device time per training step of the output head and its
cross entropy (forward and backward), in milliseconds: the window's leaf
operations under the program's ``head`` scope (``bench/scopes.py``) over
the steps run (profiler trace)."""


def read(m):
    if m.get("kind") != "train" or not m.get("steps"):
        return None
    sec = m.get("trace", {}).get("scope_s", {}).get("head")
    return None if not sec else 1e3 * sec / m["steps"]
