"""attn_core_roofline.train: the attention core's share of its roofline,
in percent: its FLOPs per step (causal Q.K^T and P.V, three times the
forward pass, ``bench/scopes.attention_core_train``) times the steps run,
over the device time under the ``attn_core`` scope and the chip's bf16
peak (``bench/peaks.json``).  The core moves a few GB per step against
tens of TFLOPs, so the FLOP bound is the roofline (profiler trace)."""
from bench.harness import share


def read(m):
    if m.get("kind") != "train" or not m.get("steps") \
            or "attn_core_flops" not in m:
        return None
    sec = m.get("trace", {}).get("scope_s", {}).get("attn_core")
    if not sec:
        return None
    return share(m["attn_core_flops"] * m["steps"],
                 sec * m["peak"]["bf16_flops_per_s"],
                 "attn_core_roofline.train")
