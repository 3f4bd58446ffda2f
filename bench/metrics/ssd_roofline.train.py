"""ssd_roofline.train: the Mamba-2 SSD core's share of its roofline, in
percent: the least time one chip could take for its share of the core's
work (``bench/ssd_work.py``: the larger of FLOPs over the bf16 peak and
least bytes over the HBM bandwidth, ``bench/peaks.json``), times the
steps run, over the device time under the ``ssd`` scope on each chip (a
mean over chips; profiler trace)."""
from bench.harness import share


def read(m):
    if m.get("kind") != "train" or not m.get("steps") \
            or "ssd_core_flops" not in m or "ssd_core_bytes" not in m:
        return None
    sec = m.get("trace", {}).get("scope_s", {}).get("ssd")
    if not sec:
        return None
    peak, chips = m["peak"], m.get("chips", 1)
    least = max(m["ssd_core_flops"] / peak["bf16_flops_per_s"],
                m["ssd_core_bytes"] / peak["hbm_bytes_per_s"]) / chips
    return share(least * m["steps"], sec, "ssd_roofline.train")
