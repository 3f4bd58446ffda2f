"""Shared neural-net building blocks (pure functions over param pytrees).

Conventions:
  * params are nested dicts of jnp arrays; init_* functions build them.
  * compute dtype is passed explicitly (bf16 for TPU); norms/softmax
    accumulate in fp32.
  * weights are stored in ``param_dtype`` (fp32 default; ZeRO keeps masters).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def manual_axes() -> frozenset:
    """Mesh axes already manual in the enclosing ``shard_map`` (empty
    outside one): a nested ``shard_map`` takes the context mesh and
    manualizes only the axes it owns beyond these."""
    am = jax.sharding.get_abstract_mesh()
    return frozenset(a for a, t in zip(am.axis_names, am.axis_types)
                     if t == jax.sharding.AxisType.Manual)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype=jnp.float32, scale: Optional[float] = None):
    """Truncated-normal fan-in init (matches common LM practice)."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_norm(kind: str, d: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def apply_norm(params, x, kind: str, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
        return y.astype(dt)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(dt)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# rotary / positional embeddings
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, fraction: float, theta: float):
    rot_dim = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (np.arange(0, rot_dim, 2, dtype=np.float32) / rot_dim))
    return rot_dim, jnp.asarray(inv)


def apply_rope(x, positions, *, fraction: float = 1.0, theta: float = 10000.0):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    rot_dim, inv = rope_frequencies(d, fraction, theta)
    if rot_dim == 0:
        return x
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)          # (..., S, 1, rot/2)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = jnp.split(x_rot, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, x_pass], axis=-1) if rot_dim < d else out


def sinusoidal_positions(positions, d_model: int, dtype=jnp.float32):
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, act: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {
            "wi": dense_init(ks[0], (d_model, d_ff), dtype),
            "wg": dense_init(ks[1], (d_model, d_ff), dtype),
            "wo": dense_init(ks[2], (d_ff, d_model), dtype),
        }
    return {
        "wi": dense_init(ks[0], (d_model, d_ff), dtype),
        "wo": dense_init(ks[2], (d_ff, d_model), dtype),
    }


def apply_mlp(params, x, act: str, compute_dtype=jnp.bfloat16):
    x = x.astype(compute_dtype)
    wi = params["wi"].astype(compute_dtype)
    wo = params["wo"].astype(compute_dtype)
    h = x @ wi
    if act == "swiglu":
        h = jax.nn.silu(h) * (x @ params["wg"].astype(compute_dtype))
    elif act == "geglu":
        h = jax.nn.gelu(h) * (x @ params["wg"].astype(compute_dtype))
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(act)
    return h @ wo


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------
def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": embed_init(key, (vocab, d_model), dtype)}


def embed_tokens(params, tokens, compute_dtype=jnp.bfloat16):
    # gather, then cast: the gradient's scatter-add over the tokens then
    # accumulates in the table's dtype, not in bf16
    return params["table"][tokens].astype(compute_dtype)


def unembed(params_or_table, x, compute_dtype=jnp.bfloat16):
    table = (params_or_table["table"]
             if isinstance(params_or_table, dict) else params_or_table)
    return x.astype(compute_dtype) @ table.astype(compute_dtype).T


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def _gold_logit(logits, labels):
    """logits[..., labels] via masked reduction (partition-friendly: no
    gather over the — possibly vocab-sharded — last dim)."""
    V = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    hit = iota == labels[..., None]
    return jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)


def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross entropy; logits (..., V) fp-any, labels int."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = _gold_logit(logits, labels)
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_softmax_xent(x, embed_table, labels, *, chunk: int,
                         compute_dtype=jnp.bfloat16, mask=None):
    """Cross entropy without materializing the full (T, V) logits.

    x: (B, S, D) final hidden states; embed_table: (V, D).
    Scans over sequence chunks; each chunk computes (B, chunk, V) logits,
    reduces to per-token NLL, and discards them.  Cuts peak logits memory by
    S/chunk — essential for vocab 200k+ at 1M tokens/step.
    """
    B, S, D = x.shape
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)          # (n, B, c, D)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)        # (n, B, c)
    if mask is None:
        ms = jnp.ones((n, B, chunk), jnp.float32)
    else:
        ms = mask.reshape(B, n, chunk).swapaxes(0, 1).astype(jnp.float32)
    table = embed_table.astype(compute_dtype)

    def body(carry, inp):
        xc, lc, mc = inp
        logits = (xc.astype(compute_dtype) @ table.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = _gold_logit(logits, lc)
        nll = (lse - gold) * mc
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(mc)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (xs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)
