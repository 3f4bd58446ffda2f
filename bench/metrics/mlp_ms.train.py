"""mlp_ms.train: device time per training step of the MLP (forward, remat
recompute and backward), in milliseconds: the window's leaf operations
under the program's ``mlp`` scope (``bench/scopes.py``) over the steps
run (profiler trace)."""


def read(m):
    if m.get("kind") != "train" or not m.get("steps"):
        return None
    sec = m.get("trace", {}).get("scope_s", {}).get("mlp")
    return None if not sec else 1e3 * sec / m["steps"]
