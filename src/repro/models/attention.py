"""Attention: projections + three execution paths.

Paths:
  * ``full``       — materializes (S, T) scores; oracle + short sequences.
  * ``flash_xla``  — two-level blocked scan (online softmax), pure JAX. Never
                     materializes more than one (q_block, kv_block) score
                     tile; lowers/compiles on any backend; the path of every
                     call the kernels below do not take.
  * ``decode``     — single-token attention over a (possibly ring-buffered)
                     KV cache.
  * the Pallas kernels (``kernels.flash_attention_bwd``) — on the TPU, for
                     unsharded, unwindowed, unmasked calls whose lengths
                     the kernels' tiles divide (``kernel_takes``): forward
                     and backward on chip, no score tile saved to HBM.  The
                     one place that chooses the kernel and its tiles.

All paths support GQA (H = K * G query groups), causal masking, and sliding
windows. Shapes: q (B, S, H, D); k/v (B, T, Kh, D).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import flash_attention_bwd as fab
from repro.models import layers, scopes


NEG_INF = -1e30


def _fit_block(block: int, dim: int) -> int:
    """Largest tile <= ``block`` that divides ``dim`` (bounded: at most
    ``block`` decrements)."""
    b = max(1, min(int(block), int(dim)))
    while dim % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype=jnp.float32):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, H, hd), dtype),
        "wk": layers.dense_init(ks[1], (d, K, hd), dtype),
        "wv": layers.dense_init(ks[2], (d, K, hd), dtype),
        "wo": layers.dense_init(ks[3], (H, hd, d), dtype,
                                scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((K, hd), dtype)
        p["bv"] = jnp.zeros((K, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = layers.init_norm("layernorm", hd, dtype)
        p["k_norm"] = layers.init_norm("layernorm", hd, dtype)
    return p


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------
def _mask_value(q_pos, k_pos, causal: bool, window: int):
    """Additive mask for (…, Sq, Tk) given absolute positions."""
    m = jnp.zeros(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]),
                  jnp.float32)
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        m = jnp.where(diff < 0, NEG_INF, m)
    if window > 0:
        m = jnp.where(diff >= window, NEG_INF, m)
    return m


@scopes.scoped(scopes.ATTN_CORE)
def full_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                   kv_mask=None, softcap=0.0):
    """Oracle path. q (B,S,H,D), k/v (B,T,K,D)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(D)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(S) + q_offset
    k_pos = jnp.arange(T)
    s = s + _mask_value(q_pos, k_pos, causal, window)
    if kv_mask is not None:  # (B, T) True = attend
        s = jnp.where(kv_mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return o.reshape(B, S, H, D)


@scopes.scoped(scopes.ATTN_CORE)
def flash_attention_xla(q, k, v, *, causal=True, window=0, q_offset=0,
                        q_block=512, kv_block=512, softcap=0.0,
                        batch_axes=(), head_axis=None):
    """Blocked online-softmax attention (pure JAX, scan over tiles).

    Peak score memory = (B, H, q_block, kv_block) fp32 regardless of S, T.

    GQA is handled by repeating K/V to the full H heads up front: a
    (K, G) reshape would destroy a head sharding whenever tp does not
    divide K (kv=8 heads on a 16-way model axis forced per-tile
    all-gathers — 2.2 TiB/step measured on command-r).  The repeat keeps
    every grid tensor sharded on H (``head_axis`` pins it) and costs only
    the broadcast KV tile in VMEM.
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    # fit, don't assert: the tiles are fixed when the step is built, but
    # a served prompt can be any length <= capacity
    q_block = _fit_block(q_block, S)
    kv_block = _fit_block(kv_block, T)
    nq, nk = S // q_block, T // kv_block
    scale = 1.0 / math.sqrt(D)

    def pin(x, hdim):
        x = _constrain_batch(x, batch_axes, 0)
        if head_axis is not None and x.shape[hdim] % 2 == 0:
            from jax.sharding import PartitionSpec as P
            entries = [None] * x.ndim
            if batch_axes:
                entries[0] = (tuple(batch_axes) if len(batch_axes) > 1
                              else batch_axes[0])
            entries[hdim] = head_axis
            try:
                x = jax.lax.with_sharding_constraint(x, P(*entries))
            except (ValueError, RuntimeError):
                pass
        return x

    kr = jnp.repeat(k, G, axis=2) if G > 1 else k      # (B, T, H, D)
    vr = jnp.repeat(v, G, axis=2) if G > 1 else v
    qg = q.reshape(B, nq, q_block, H, D).transpose(1, 0, 3, 2, 4)
    # qg: (nq, B, H, qb, D)
    kb = kr.reshape(B, nk, kv_block, H, D).transpose(1, 0, 3, 2, 4)
    vb = vr.reshape(B, nk, kv_block, H, D).transpose(1, 0, 3, 2, 4)
    # kb/vb: (nk, B, H, kvb, D)

    def q_step(_, qi_and_block):
        qi, qblk = qi_and_block  # qblk (B,H,qb,D)
        qblk = pin(qblk, 1)
        q_pos = qi * q_block + jnp.arange(q_block) + q_offset

        def kv_step(carry, kj_and_kv):
            m, l, acc = carry
            kj, kblk, vblk = kj_and_kv
            kblk = pin(kblk, 1)
            s = jnp.einsum("bhqd,bhtd->bhqt", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = pin(s, 1)
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            k_pos = kj * kv_block + jnp.arange(kv_block)
            s = s + _mask_value(q_pos, k_pos, causal, window)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = pin(l * corr + jnp.sum(p, axis=-1), 1)
            m_new = pin(m_new, 1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqt,bhtd->bhqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            acc_new = pin(acc_new, 1)
            return (m_new, l_new, acc_new), None

        m0 = pin(jnp.full((B, H, q_block), NEG_INF, jnp.float32), 1)
        l0 = pin(jnp.zeros((B, H, q_block), jnp.float32), 1)
        a0 = pin(jnp.zeros((B, H, q_block, D), jnp.float32), 1)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, ob = jax.lax.scan(q_step, None, (jnp.arange(nq), qg))
    # ob: (nq, B, H, qb, D) -> (B, S, H, D)
    return ob.transpose(1, 0, 3, 2, 4).reshape(B, S, H, D)


def _constrain_batch(x, batch_axes, dim: int):
    """Pin the batch dim's sharding (None = no-op).

    GSPMD's backward propagation through nested scans can drift to a
    batch-replicated layout (measured: full-batch fp32 score tiles
    all-reduced over 'data' 320x/step); constraining the batch dim of the
    scan operands/carries inside the body prevents the drift.
    """
    if not batch_axes:
        return x
    from jax.sharding import PartitionSpec as P
    entries = [None] * x.ndim
    entries[dim] = tuple(batch_axes) if len(batch_axes) > 1 else \
        batch_axes[0]
    try:
        return jax.lax.with_sharding_constraint(x, P(*entries))
    except (ValueError, RuntimeError):
        return x


@scopes.scoped(scopes.ATTN_CORE)
def local_flash_xla(q, k, v, *, window: int, causal=True, softcap=0.0,
                    q_block=512, kv_block=512, batch_axes=(),
                    head_axis=None):
    """O(S·window) sliding-window flash attention.

    Per q block i, only a STATIC-length key span of ``window + q_block``
    (rounded up to kv_block) ending at the block's last key can be in
    range; the span is ``dynamic_slice``d from a front-padded K/V and
    flash-tiled, so peak score memory stays one (q_block, kv_block) tile
    and executed FLOPs are S·(window + q_block) per head instead of the
    full S².  Invalid (padding) keys carry position < 0 and are masked.
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq = min(q_block, S)
    if S % bq:
        return flash_attention_xla(q, k, v, causal=causal, window=window,
                                   softcap=softcap, batch_axes=batch_axes,
                                   head_axis=head_axis)
    span = window + bq
    bk = min(kv_block, span)
    span = -(-span // bk) * bk              # round up to kv tiles
    if span >= T:                           # no savings: plain flash
        return flash_attention_xla(q, k, v, causal=causal, window=window,
                                   q_block=q_block, kv_block=kv_block,
                                   softcap=softcap, batch_axes=batch_axes,
                                   head_axis=head_axis)
    pad = span - bq                         # front padding (invalid keys)
    nq = S // bq
    nk = span // bk
    scale = 1.0 / math.sqrt(D)

    def pin(x, hdim):
        x = _constrain_batch(x, batch_axes, 0)
        if head_axis is not None and x.ndim > hdim:
            from jax.sharding import PartitionSpec as P
            entries = [None] * x.ndim
            if batch_axes:
                entries[0] = (tuple(batch_axes) if len(batch_axes) > 1
                              else batch_axes[0])
            entries[hdim] = head_axis
            try:
                x = jax.lax.with_sharding_constraint(x, P(*entries))
            except (ValueError, RuntimeError):
                pass
        return x

    kr = jnp.repeat(k, G, axis=2) if G > 1 else k      # (B, T, H, D)
    vr = jnp.repeat(v, G, axis=2) if G > 1 else v
    kp = jnp.pad(kr, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(vr, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    qg = q.reshape(B, nq, bq, H, D).transpose(1, 0, 3, 2, 4)
    # qg: (nq, B, H, bq, D); kp/vp: (B, pad+T, H, D)

    def q_step(_, qi_and_block):
        qi, qblk = qi_and_block
        qblk = pin(qblk, 1)
        q_pos = qi * bq + jnp.arange(bq)
        ks = jax.lax.dynamic_slice_in_dim(kp, qi * bq, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, qi * bq, span, axis=1)
        kb = ks.reshape(B, nk, bk, H, D).transpose(1, 0, 3, 2, 4)
        vb = vs.reshape(B, nk, bk, H, D).transpose(1, 0, 3, 2, 4)

        def kv_step(carry, kj_and_kv):
            m, l, acc = carry
            kj, kblk, vblk = kj_and_kv
            kblk = pin(kblk, 1)
            s = jnp.einsum("bhqd,bhtd->bhqt", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = pin(s, 1)
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            k_pos = qi * bq + kj * bk + jnp.arange(bk) - pad
            diff = q_pos[:, None] - k_pos[None, :]
            msk = jnp.where(k_pos < 0, NEG_INF, 0.0)[None, :]
            if causal:
                msk = jnp.where(diff < 0, NEG_INF, msk)
            msk = jnp.where(diff >= window, NEG_INF, msk)
            s = s + msk
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = pin(l * corr + jnp.sum(p, axis=-1), 1)
            m_new = pin(m_new, 1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqt,bhtd->bhqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            acc_new = pin(acc_new, 1)
            return (m_new, l_new, acc_new), None

        m0 = pin(jnp.full((B, H, bq), NEG_INF, jnp.float32), 1)
        l0 = pin(jnp.zeros((B, H, bq), jnp.float32), 1)
        a0 = pin(jnp.zeros((B, H, bq, D), jnp.float32), 1)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, ob = jax.lax.scan(q_step, None, (jnp.arange(nq), qg))
    return ob.transpose(1, 0, 3, 2, 4).reshape(B, S, H, D)


@scopes.scoped(scopes.ATTN_CORE)
def chunk_decode_attention(q, k_cache, v_cache, cache_pos, q_pos, *,
                           window=0, softcap=0.0):
    """Multi-token attention of a prompt *chunk* against a KV cache.

    q (B,S,H,D) is a contiguous chunk of new tokens at absolute positions
    ``q_pos`` (B,S); the caches (B,W,K,D) already contain the chunk's own
    K/V (written by the caller) plus all earlier history, with ``cache_pos``
    (B,W) giving each slot's absolute position (-1 = empty).  Masking is
    purely positional — a query attends to every valid slot at a position
    <= its own (and within ``window``) — so the result is bit-identical to
    one-shot prefill over the same tokens regardless of how the prompt was
    chunked.  This is the chunked-prefill primitive of the serving stack.
    """
    B, S, H, D = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    valid = (cache_pos >= 0)[:, None, :]                  # (B,1,W)
    diff = q_pos[:, :, None] - cache_pos[:, None, :]      # (B,S,W)
    keep = valid & (diff >= 0)
    if window > 0:
        keep = keep & (diff < window)
    s = jnp.where(keep[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v_cache)
    return o.reshape(B, S, H, D)


@scopes.scoped(scopes.ATTN_CORE)
def decode_attention(q, k_cache, v_cache, cache_pos, *, window=0,
                     softcap=0.0):
    """q (B,1,H,D); caches (B,W,K,D); cache_pos (B,W) absolute positions of
    each cache slot (-1 = empty). Works for both full and ring-buffer caches.
    """
    B, _, H, D = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    valid = (cache_pos >= 0)
    if window > 0:
        cur = jnp.max(cache_pos, axis=-1, keepdims=True)
        valid = valid & (cur - cache_pos < window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v_cache)
    return o.reshape(B, 1, H, D)


@scopes.scoped(scopes.ATTN_CORE)
def sharded_decode(q, k_new, v_new, cache, positions, *, mesh, dp_axes,
                   tp_axis, window=0, softcap=0.0):
    """Flash-decode under shard_map: batch over dp, cache LENGTH over tp.

    Each model rank holds a slice of the (B, W, K, D) history; the new
    token is written into whichever rank owns its slot (ring-buffer slot
    for windowed layers); attention computes local partial max/sum-exp
    and combines with one tiny psum triplet over tp — no rank ever
    materializes the full cache (32k x 128 x 40L would blow HBM) and no
    gather/scatter crosses the wire.

    Returns (out (B,1,H,D), new_cache).  Falls back to the dense path
    when the mesh/shapes don't divide.
    """
    B, _, H, D = q.shape
    W = cache["k"].shape[1]
    K = cache["k"].shape[2]
    G = H // K
    tp = mesh.shape.get(tp_axis, 1) if tp_axis else 1
    dp = tuple(a for a in dp_axes if mesh.shape.get(a, 1) > 1)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if (tp > 1 and (W % tp or W < 2 * tp)) or (n_dp > 1 and B % n_dp):
        return None                      # caller uses the dense path

    from jax.sharding import PartitionSpec as Pspec
    dp_e = (dp if len(dp) > 1 else dp[0]) if dp else None
    tp_e = tp_axis if tp > 1 else None
    s_q = Pspec(dp_e, None, None, None)
    s_kv = Pspec(dp_e, tp_e, None, None)
    s_pos = Pspec(dp_e, tp_e)
    s_cur = Pspec(dp_e, None)

    def body(ql, knl, vnl, ck, cv, cp, cur):
        Bl = ql.shape[0]
        Wl = ck.shape[1]
        r = jax.lax.axis_index(tp_axis) if tp > 1 else 0
        slot_g = (cur[:, 0] % W) if window > 0 else cur[:, 0]
        slot_l = slot_g - r * Wl
        ok = (slot_l >= 0) & (slot_l < Wl)
        safe = jnp.clip(slot_l, 0, Wl - 1)
        bidx = jnp.arange(Bl)
        old_k = ck[bidx, safe]
        old_v = cv[bidx, safe]
        old_p = cp[bidx, safe]
        ck = ck.at[bidx, safe].set(
            jnp.where(ok[:, None, None], knl[:, 0].astype(ck.dtype), old_k))
        cv = cv.at[bidx, safe].set(
            jnp.where(ok[:, None, None], vnl[:, 0].astype(cv.dtype), old_v))
        cp = cp.at[bidx, safe].set(
            jnp.where(ok, cur[:, 0].astype(cp.dtype), old_p))

        qg = ql.reshape(Bl, K, G, D)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        valid = cp >= 0
        if window > 0:
            valid = valid & (cur[:, :1] - cp < window)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_l = jnp.max(s, axis=-1)                         # (B,K,G)
        p = jnp.exp(s - m_l[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_l = jnp.sum(p, axis=-1)
        acc_l = jnp.einsum("bkgt,btkd->bkgd", p.astype(cv.dtype), cv,
                           preferred_element_type=jnp.float32)
        if tp > 1:
            m = jax.lax.pmax(m_l, tp_axis)
            f = jnp.exp(m_l - m)
            l = jax.lax.psum(l_l * f, tp_axis)
            acc = jax.lax.psum(acc_l * f[..., None], tp_axis)
        else:
            l, acc = l_l, acc_l
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(ql.dtype)
        return out.reshape(Bl, 1, H, D), ck, cv, cp

    manual = frozenset(dp) | ({tp_axis} if tp > 1 else set())
    if not manual:
        return None
    already = layers.manual_axes()
    out, ck, cv, cp = jax.shard_map(
        body, mesh=None if already else mesh,
        axis_names=manual - already if already else manual,
        in_specs=(s_q, s_q, s_q, s_kv, s_kv, s_pos, s_cur),
        out_specs=(s_q, s_kv, s_kv, s_pos), check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"], cache["pos"], positions)
    return out, {"k": ck, "v": cv, "pos": cp}


@scopes.scoped(scopes.ATTN_CORE)
def sharded_flash(q, k, v, *, mesh, dp_axes, tp_axis, causal=True,
                  window=0, softcap=0.0, q_block=512, kv_block=512):
    """Flash attention under an explicit ``shard_map``: batch over the dp
    axes, heads over the tp axis — every tensor inside the scan is a plain
    local array, so GSPMD cannot drift (pin-based constraints still left
    2560 per-tile all-gathers in the backward of nested scans; manual
    sharding removes them by construction).

    GQA KV heads are repeated to H *before* sharding; if tp does not
    divide H, heads are zero-padded up to the next multiple (the padded
    heads compute garbage that is sliced off — bounded waste, vs. the
    16x redundant compute of batch-only sharding or per-tile gathers).
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    tp = mesh.shape.get(tp_axis, 1) if tp_axis else 1
    dp = tuple(a for a in dp_axes if mesh.shape.get(a, 1) > 1)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if (n_dp > 1 and B % n_dp) or S % q_block:
        # fall back to the pin-based jit path
        fn = local_flash_xla if window > 0 else flash_attention_xla
        kwargs = dict(causal=causal, softcap=softcap,
                      batch_axes=dp, q_block=q_block, kv_block=kv_block)
        if window > 0:
            return fn(q, k, v, window=window, **kwargs)
        return fn(q, k, v, window=window, **kwargs)

    kr = jnp.repeat(k, G, axis=2) if G > 1 else k
    vr = jnp.repeat(v, G, axis=2) if G > 1 else v
    Hp = -(-H // tp) * tp
    if Hp != H:
        padh = ((0, 0), (0, 0), (0, Hp - H), (0, 0))
        q = jnp.pad(q, padh)
        kr = jnp.pad(kr, padh)
        vr = jnp.pad(vr, padh)

    from jax.sharding import PartitionSpec as P
    dp_entry = (dp if len(dp) > 1 else dp[0]) if dp else None
    spec = P(dp_entry, None, tp_axis if tp > 1 else None, None)

    def body(ql, kl, vl):
        if window > 0:
            return local_flash_xla(ql, kl, vl, window=window,
                                   causal=causal, softcap=softcap,
                                   q_block=q_block, kv_block=kv_block)
        return flash_attention_xla(ql, kl, vl, causal=causal,
                                   window=0, softcap=softcap,
                                   q_block=q_block, kv_block=kv_block)

    manual = frozenset(dp) | ({tp_axis} if tp > 1 else set())
    if not manual:                      # degenerate 1x1 mesh: run local
        return body(q, kr, vr)[:, :, :H]
    already = layers.manual_axes()
    out = jax.shard_map(body, mesh=None if already else mesh,
                        axis_names=manual - already if already else manual,
                        in_specs=(spec, spec, spec), out_specs=spec,
                        check_vma=False)(q, kr, vr)
    return out[:, :, :H]


def on_tpu() -> bool:
    """Whether this trace compiles for the TPU, the one backend that
    compiles the Pallas kernels; read at trace time, so the untaken path
    leaves nothing (no residuals) in the program."""
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """Whether the Pallas kernels run in the interpreter: everywhere but
    on the TPU.  Kept apart from ``on_tpu`` so that a test can dispatch
    as the TPU does while the kernels still interpret on the CPU.
    Cached: the backend cannot change within a process, and the answer
    is part of the jit cache key."""
    return jax.default_backend() != "tpu"


def kernel_takes(q, k, window: int, kv_mask) -> bool:
    """Whether an unsharded, uncached call runs the fused Pallas kernels:
    on the TPU, full (not windowed) attention, no key mask, and lengths
    that the kernels' tiles divide.  Every other call keeps the XLA
    paths."""
    if not on_tpu() or window > 0 or kv_mask is not None:
        return False
    bq, bk = fab.kernel_blocks(q.shape[2] // k.shape[2], q.shape[3])
    return q.shape[1] % bq == 0 and k.shape[1] % bk == 0


@scopes.scoped(scopes.ATTN_CORE)
def flash_kernel_attention(q, k, v, *, causal=True, softcap=0.0):
    """The fused Pallas flash attention (``kernels.flash_attention_bwd``):
    forward and backward on chip, recomputing score tiles in the backward
    from the saved row statistics instead of keeping them in HBM."""
    bq, bk = fab.kernel_blocks(q.shape[2] // k.shape[2], q.shape[3])
    return fab.flash_attention_vjp(q, k, v, causal, 0, softcap, bq, bk,
                                   default_interpret())


# ---------------------------------------------------------------------------
# block-level apply (projections + path dispatch + cache management)
# ---------------------------------------------------------------------------
@scopes.scoped(scopes.ATTN_PROJ)
def project_qkv(params, x, cfg: ModelConfig, positions, compute_dtype):
    cd = compute_dtype
    x = x.astype(cd)
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(cd))
    k = jnp.einsum("bsd,dke->bske", x, params["wk"].astype(cd))
    v = jnp.einsum("bsd,dke->bske", x, params["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cd)
        k = k + params["bk"].astype(cd)
        v = v + params["bv"].astype(cd)
    if cfg.qk_norm:
        q = layers.apply_norm(params["q_norm"], q, "layernorm", cfg.norm_eps)
        k = layers.apply_norm(params["k_norm"], k, "layernorm", cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        q = layers.apply_rope(q, positions, fraction=cfg.rope_fraction,
                              theta=cfg.rope_theta)
        k = layers.apply_rope(k, positions, fraction=cfg.rope_fraction,
                              theta=cfg.rope_theta)
    return q, k, v


@scopes.scoped(scopes.ATTN_PROJ)
def project_out(params, o, compute_dtype):
    """The heads' outputs (B, S, H, D) back to (B, S, d_model)."""
    cd = compute_dtype
    return jnp.einsum("bshe,hed->bsd", o.astype(cd), params["wo"].astype(cd))


def apply_attention(params, x, cfg: ModelConfig, *, local: bool,
                    positions, compute_dtype=jnp.bfloat16, impl="xla",
                    cache=None, blocks=(512, 512), kv_mask=None,
                    cache_capacity: int = 0, batch_axes=(),
                    head_axis=None, mesh=None, tp_axis=None):
    """Returns (out (B,S,d_model), new_cache_or_None).

    cache (decode): dict(k=(B,W,K,D), v=(B,W,K,D), pos=(B,W) int32).
    For prefill (cache is the string "init"), returns the filled cache.
    """
    window = cfg.local_window if local else 0
    B = x.shape[0]
    cd = compute_dtype

    if cache is not None and not isinstance(cache, str):
        S = x.shape[1]
        if S > 1:
            # ---- chunked prefill: S new tokens appended to the cache ----
            q, k_new, v_new = project_qkv(params, x, cfg, positions, cd)
            W = cache["k"].shape[1]
            bidx = jnp.arange(B)[:, None]
            if window > 0:
                # attend over [pre-write ring ∥ full chunk] — a ring write
                # first would drop keys that early chunk queries still need
                # whenever S > W; then apply the ring rule (last min(S, W)
                # tokens survive, slot = pos % W), matching
                # build_cache_from_prefill / the single-token decode write
                o = chunk_decode_attention(
                    q,
                    jnp.concatenate([cache["k"],
                                     k_new.astype(cache["k"].dtype)], 1),
                    jnp.concatenate([cache["v"],
                                     v_new.astype(cache["v"].dtype)], 1),
                    jnp.concatenate([cache["pos"], positions], 1),
                    positions, window=window, softcap=cfg.logit_softcap)
                m = min(S, W)
                slots = positions[:, -m:] % W
                k_cache = cache["k"].at[bidx, slots].set(
                    k_new[:, -m:].astype(cache["k"].dtype))
                v_cache = cache["v"].at[bidx, slots].set(
                    v_new[:, -m:].astype(cache["v"].dtype))
                pos_cache = cache["pos"].at[bidx, slots].set(
                    positions[:, -m:])
            else:
                k_cache = cache["k"].at[bidx, positions].set(
                    k_new.astype(cache["k"].dtype))
                v_cache = cache["v"].at[bidx, positions].set(
                    v_new.astype(cache["v"].dtype))
                pos_cache = cache["pos"].at[bidx, positions].set(positions)
                o = chunk_decode_attention(q, k_cache, v_cache, pos_cache,
                                           positions, window=window,
                                           softcap=cfg.logit_softcap)
            out = project_out(params, o, cd)
            return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}
        # ---- decode: single new token at absolute position `positions` ----
        q, k_new, v_new = project_qkv(params, x, cfg, positions, cd)
        if mesh is not None:
            res = sharded_decode(q, k_new, v_new, cache, positions,
                                 mesh=mesh, dp_axes=batch_axes,
                                 tp_axis=tp_axis, window=window,
                                 softcap=cfg.logit_softcap)
            if res is not None:
                o, new_cache = res
                out = project_out(params, o, cd)
                return out, new_cache
        W = cache["k"].shape[1]
        slot = (positions[:, 0] % W) if window > 0 else positions[:, 0]
        bidx = jnp.arange(B)
        k_cache = cache["k"].at[bidx, slot].set(k_new[:, 0])
        v_cache = cache["v"].at[bidx, slot].set(v_new[:, 0])
        pos_cache = cache["pos"].at[bidx, slot].set(positions[:, 0])
        o = decode_attention(q, k_cache, v_cache, pos_cache, window=window,
                             softcap=cfg.logit_softcap)
        out = project_out(params, o, cd)
        return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}

    q, k, v = project_qkv(params, x, cfg, positions, cd)
    if impl == "full":
        o = full_attention(q, k, v, causal=cfg.causal, window=window,
                           kv_mask=kv_mask, softcap=cfg.logit_softcap)
    elif mesh is not None:
        # manual-sharding path: no collectives inside the tile scans
        o = sharded_flash(q, k, v, mesh=mesh, dp_axes=batch_axes,
                          tp_axis=tp_axis, causal=cfg.causal,
                          window=window, softcap=cfg.logit_softcap,
                          q_block=blocks[0], kv_block=blocks[1])
    elif kernel_takes(q, k, window, kv_mask):
        o = flash_kernel_attention(q, k, v, causal=cfg.causal,
                                   softcap=cfg.logit_softcap)
    elif window > 0:
        # sliding-span O(S·w) flash path for windowed blocks
        o = local_flash_xla(q, k, v, window=window, causal=cfg.causal,
                            softcap=cfg.logit_softcap,
                            q_block=blocks[0], kv_block=blocks[1],
                            batch_axes=batch_axes, head_axis=head_axis)
    else:
        o = flash_attention_xla(q, k, v, causal=cfg.causal, window=window,
                                q_block=blocks[0], kv_block=blocks[1],
                                softcap=cfg.logit_softcap,
                                batch_axes=batch_axes, head_axis=head_axis)
    out = project_out(params, o, cd)

    new_cache = None
    if cache == "init":
        new_cache = build_cache_from_prefill(
            k, v, positions, window=window, capacity=cache_capacity,
            kv_mask=kv_mask)
    return out, new_cache


def build_cache_from_prefill(k, v, positions, *, window: int,
                             capacity: int = 0, kv_mask=None):
    """Turn prefill K/V into a decode cache.

    Full attention: cache slot = absolute position (capacity >= S + decode
    budget). Local attention: ring buffer of size ``window``; slot = pos %
    window (matching the decode-side write rule).

    ``kv_mask`` (B, S) bool, True = real token (pow2-bucketed prefill):
    right-padded entries must not enter the cache.  Full caches mark the
    padded slots empty (``pos = -1``); ring caches gather the last
    ``window`` *real* tokens per batch row instead of the array tail —
    the tail itself is padding, and a masked scatter at ``-1 % W`` would
    clobber a live slot.
    """
    B, S = k.shape[0], k.shape[1]
    pos = jnp.broadcast_to(positions, (B, S)).astype(jnp.int32)
    if window > 0:
        W = window
        if kv_mask is not None:
            # slot w holds the newest real index p ≡ w (mod W); per-batch
            # lengths make this a gather, matching the decode write rule
            L = kv_mask.astype(jnp.int32).sum(axis=1)          # (B,)
            w_ids = jnp.arange(W)[None, :]                      # (1, W)
            p = (L[:, None] - 1) - ((L[:, None] - 1 - w_ids) % W)
            valid = p >= 0
            pc = jnp.clip(p, 0)
            gather = lambda a: jnp.take_along_axis(
                a, pc.reshape(B, W, *([1] * (a.ndim - 2))), axis=1)
            cache_k = jnp.where(valid.reshape(B, W, 1, 1), gather(k), 0)
            cache_v = jnp.where(valid.reshape(B, W, 1, 1), gather(v), 0)
            cache_p = jnp.where(valid, jnp.take_along_axis(pos, pc, 1), -1)
            return {"k": cache_k.astype(k.dtype),
                    "v": cache_v.astype(v.dtype), "pos": cache_p}
        m = min(S, W)
        slots = (jnp.arange(S - m, S) % W)
        cache_k = jnp.zeros((B, W) + k.shape[2:], k.dtype).at[:, slots].set(k[:, -m:])
        cache_v = jnp.zeros((B, W) + v.shape[2:], v.dtype).at[:, slots].set(v[:, -m:])
        cache_p = jnp.full((B, W), -1, jnp.int32).at[:, slots].set(pos[:, -m:])
        return {"k": cache_k, "v": cache_v, "pos": cache_p}
    if kv_mask is not None:
        pos = jnp.where(kv_mask, pos, -1)       # padded slots stay empty
    cap = max(capacity, S)
    if cap == S:
        return {"k": k, "v": v, "pos": pos.astype(jnp.int32)}
    cache_k = jnp.zeros((B, cap) + k.shape[2:], k.dtype).at[:, :S].set(k)
    cache_v = jnp.zeros((B, cap) + v.shape[2:], v.dtype).at[:, :S].set(v)
    cache_p = jnp.full((B, cap), -1, jnp.int32).at[:, :S].set(pos)
    return {"k": cache_k, "v": cache_v, "pos": cache_p}


def init_decode_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
                      local: bool, dtype=jnp.bfloat16):
    W = min(cfg.local_window, max_seq) if local else max_seq
    K, D = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, W, K, D), dtype),
        "v": jnp.zeros((batch, W, K, D), dtype),
        "pos": jnp.full((batch, W), -1, jnp.int32),
    }
