"""Cells of ``BENCHMARK.json`` cut to a size a CPU test can hold: every
width divided down, the same code paths."""
import copy
import pathlib

import jax

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]

# Cells whose files are ready under bench/ but which are not in
# BENCHMARK.json yet (not measured on the chip): the CPU tests drive their
# paths, the four-chip one on four virtual devices.
SERVE = "qwen2-0.5b.serve.chat"
PREPARED = {
    "configs": [{"name": "mamba2-780m",
                 "file": "bench/configs/mamba2-780m.json"}],
    "workloads": [{"name": "mamba2-780m.train.zero3-4x1",
                   "config": "mamba2-780m", "traffic": "train.zero3-4x1",
                   "chips": 4},
                  {"name": SERVE, "config": "qwen2-0.5b",
                   "traffic": "serve.chat", "chips": 1}],
    "end_to_end": [{"name": n, "unit": u, "workloads": [SERVE]} for n, u in
                   (("serve_output_tokens_per_s", "tokens/s"),
                    ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"))],
    "per_layer": [{"name": "exposed_collective_share.train", "unit": "%",
                   "layer": "collectives", "moves": "train_tokens_per_s",
                   "workloads": ["mamba2-780m.train.zero3-4x1"]}]
    + [{"name": n, "unit": u, "moves": mv, "workloads": [SERVE]}
       for n, u, mv in (("mfu.serve", "%", "itl_p95_ms"),
                        ("idle_share.serve", "%", "itl_p95_ms"),
                        ("host_gap_ms.serve", "ms", "itl_p95_ms"),
                        ("prefix_hit_rate.serve", "%", "ttft_p95_ms"),
                        ("gen_lag_p95_ms.serve", "ms", "ttft_p95_ms"))],
}


def benchmark():
    """BENCHMARK.json with the prepared cells added."""
    bench = copy.deepcopy(harness.load_json(ROOT / "BENCHMARK.json"))
    names = {m["name"] for k in ("workloads", "end_to_end", "per_layer",
                                 "configs") for m in bench[k]}
    for key, entries in PREPARED.items():
        bench[key] = bench[key] + [e for e in entries
                                   if e["name"] not in names]
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s" and \
                "mamba2-780m.train.zero3-4x1" not in m["workloads"]:
            m["workloads"] = m["workloads"] + ["mamba2-780m.train.zero3-4x1"]
    return bench


# Limits at this size.  grad_gap is the chip's own limit; delta_gap is
# wider, because a leaf of a few thousand weights changes by round-off
# more unevenly than a full-width one (sound runs read up to 5e-3 here,
# 1.1e-3 on the chip).  Readings at this size on the CPU: sound runs
# grad 0.003-0.007, the float8 control 0.014-0.043, half the batch left
# out 0.34 and 0.12 (grad, delta), a state left unchanged 1.0 (delta).
TEST_LIMITS = {
    "train": {"leaf_rule": 1e-3,
              "limits": {"grad_gap": {"limit": 0.012},
                         "delta_gap": {"limit": 0.03}}},
}


def tiny(name: str) -> harness.Cell:
    cell = harness.find_cell(name, benchmark())
    c = copy.deepcopy(cell.config)
    t = copy.deepcopy(cell.traffic)
    if "hidden_size" in c:
        c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
    else:
        c.update(d_model=64, n_layer=2, vocab_size=500)
        c["mamba2_layer"].update(d_state=16, headdim=16, chunk_size=32)
    if t["kind"] == "train":
        t.update(seq=64)
        if t.get("mesh"):
            t.update(mesh=[4, 1], batch=8)
    else:
        t["engine"].update(n_slots=4, max_seq=256, prefill_chunk=16)
        t["prompt"]["shared_prefix"]["length"] = 32
        t["prompt"]["user"].update(median=16, min=4, max=64)
        t["output"].update(median=8, min=2, max=32)
        t["arrivals"].update(rate_per_s=4.0, preroll_s=2, tail_s=20)
        t["check"] = {"min_tokens": 40, "max_requests": 4}
    cell.config, cell.traffic = c, t
    cell.limits = TEST_LIMITS.get(t["kind"], cell.limits)
    return cell


def devices(cell: harness.Cell):
    return jax.devices()[:cell.chips]
