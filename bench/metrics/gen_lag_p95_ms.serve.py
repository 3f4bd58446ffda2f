"""gen_lag_p95_ms.serve: 95th percentile of how late the load generator
submitted each request of the window after it was due, in milliseconds
(host clock)."""
import numpy as np


def read(m):
    if m.get("kind") != "serve" or not m.get("gen_lag_s"):
        return None
    return 1e3 * float(np.percentile(m["gen_lag_s"], 95))
