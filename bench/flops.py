"""Model FLOPs from shapes, for the MFU metrics.

Counted: every matrix product of the forward pass (projections, MLP, the
tied output head), the causal attention scores and their product with V
(half the square: only the keys at or before each query), and the SSD
core of Mamba-2 (intra-chunk C.B^T and its product with x over the causal
half of each chunk, the state read-out and the state update).  Training is
three times the forward pass.  Not counted: recomputation by remat,
padding, norms, activations, the softmax, the convolutions and the
embedding gather.  Adapted from ``repro.core.costmodel.forward_flops``
(dense and SSM parts), kept here so that no change to the program can
change the yardstick.
"""
from __future__ import annotations

from typing import Any, Dict


def attention_layer(c: Dict[str, Any], ctx: float) -> float:
    """Forward FLOPs of one attention layer for one token that attends
    over ``ctx`` positions (itself included)."""
    d, H, K = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, F = d // H, c["intermediate_size"]
    proj = 2 * d * (H + 2 * K) * hd + 2 * H * hd * d
    scores = 2 * 2 * H * hd * ctx
    mlp = 3 * 2 * d * F
    return proj + scores + mlp


def ssd_layer(c: Dict[str, Any]) -> float:
    """Forward FLOPs of one Mamba-2 layer per token (chunked SSD)."""
    m = c["mamba2_layer"]
    d = c["d_model"]
    d_in = m["expand"] * d
    H, N, P, G, L = d_in // m["headdim"], m["d_state"], m["headdim"], \
        m["ngroups"], m["chunk_size"]
    proj = 2 * d * (2 * d_in + 2 * G * N + H) + 2 * d_in * d
    core = 2 * (L / 2) * G * N + 2 * (L / 2) * H * P + 2 * 2 * H * N * P
    return proj + core


def head(c: Dict[str, Any]) -> float:
    d = c.get("hidden_size", c.get("d_model"))
    V = -(-c["vocab_size"] // 128) * 128
    return 2 * d * V


def train_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of one training step over ``batch`` rows of ``seq``."""
    T = batch * seq
    if "mamba2_layer" in c:
        fwd = T * (c["n_layer"] * ssd_layer(c) + head(c))
    else:
        # mean context of a causal row of seq tokens: (seq + 1) / 2
        fwd = T * (c["num_hidden_layers"]
                   * attention_layer(c, (seq + 1) / 2) + head(c))
    return 3.0 * fwd


def serve_tokens(c: Dict[str, Any], start: int, n: int,
                 with_head: int) -> float:
    """Forward FLOPs of ``n`` consecutive tokens at positions ``start ..
    start + n - 1`` of one sequence (each attends to every earlier
    position and itself), with ``with_head`` of them read out through the
    output head."""
    L = c["num_hidden_layers"]
    # sum over positions p of attention_layer(c, p + 1)
    per = attention_layer(c, 0)
    d, H = c["hidden_size"], c["num_attention_heads"]
    score_per_ctx = 2 * 2 * H * (d // H)
    ctx_sum = n * start + n * (n + 1) / 2
    return L * (n * per + score_per_ctx * ctx_sum) + with_head * head(c)
