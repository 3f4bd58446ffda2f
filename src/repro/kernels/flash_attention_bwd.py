"""Backward pass for the fused GQA flash attention — Pallas TPU kernels.

FlashAttention-2 style two-kernel backward:

  * ``_bwd_dkv_kernel``  — grid (B, K, kv_block, q_block): for a fixed KV
    tile, accumulate dK/dV over the q tiles in VMEM scratch (q innermost,
    sequential).
  * ``_bwd_dq_kernel``   — grid (B, K, q_block, kv_block): for a fixed Q
    tile, accumulate dQ over kv tiles.

Both recompute the tile's softmax from the saved row statistics
(m, l) — the standard memory-optimal recipe: no (S, T) matrix is ever
materialized.  ``delta = rowsum(dO * O)`` is precomputed outside (a
cheap fused elementwise+reduce).

Exposed through ``flash_attention_vjp`` (jax.custom_vjp): the forward
runs the fwd kernel extended to also emit (m, l); gradients are exact
(validated against jax.grad of the oracle in tests/test_kernels_bwd.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward (emits row stats for the backward)
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                m_ref, l_ref, acc_ref, *, causal, window, bq, bk, nk,
                scale, softcap):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(2)
    live = jnp.asarray(True)
    if causal:
        live = live & (j * bk <= i * bq + bq - 1)
    if window > 0:
        live = live & ((i * bq) - (j * bk + bk - 1) < window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0]                  # (G, bq, D)
        k = k_ref[0, 0]                  # (bk, D)
        v = v_ref[0, 0]
        G, _, D = q.shape
        s = jax.lax.dot_general(
            q.reshape(G * bq, D), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        q_row = jax.lax.broadcasted_iota(jnp.int32, (G * bq, bk), 0) % bq
        q_pos = i * bq + q_row
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (G * bq, bk), 1)
        diff = q_pos - k_pos
        mask = jnp.zeros_like(s)
        if causal:
            mask = jnp.where(diff < 0, NEG_INF, mask)
        if window > 0:
            mask = jnp.where(diff >= window, NEG_INF, mask)
        s = s + mask
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _flush():
        G, _, D = q_ref[0, 0].shape
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(
            o_ref.dtype).reshape(G, bq, D)
        m_out_ref[0, 0] = m_ref[...].reshape(G, bq)
        l_out_ref[0, 0] = l[...].reshape(G, bq)


def _recompute_p(q, k, i, j, bq, bk, scale, softcap, causal, window,
                 m_row, l_row):
    """Recompute the (G*bq, bk) probability tile from saved row stats."""
    G, _, D = q.shape
    s = jax.lax.dot_general(
        q.reshape(G * bq, D), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_row = jax.lax.broadcasted_iota(jnp.int32, (G * bq, bk), 0) % bq
    q_pos = i * bq + q_row
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (G * bq, bk), 1)
    diff = q_pos - k_pos
    mask = jnp.zeros_like(s)
    if causal:
        mask = jnp.where(diff < 0, NEG_INF, mask)
    if window > 0:
        mask = jnp.where(diff >= window, NEG_INF, mask)
    s = s + mask
    return jnp.exp(s - m_row[:, None]) / l_row[:, None], s


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal, window,
                    bq, bk, nq, scale, softcap):
    i = pl.program_id(3)                 # q tile (innermost)
    j = pl.program_id(2)                 # kv tile (this kernel's output)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = jnp.asarray(True)
    if causal:
        live = live & (j * bk <= i * bq + bq - 1)
    if window > 0:
        live = live & ((i * bq) - (j * bk + bk - 1) < window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0]                  # (G, bq, D)
        k = k_ref[0, 0]                  # (bk, D)
        v = v_ref[0, 0]
        do = do_ref[0, 0].reshape(-1, v.shape[-1])   # (G*bq, D)
        m_row = m_ref[0, 0].reshape(-1)
        l_row = l_ref[0, 0].reshape(-1)
        delta = delta_ref[0, 0].reshape(-1)
        G = q.shape[0]
        p, s = _recompute_p(q, k, i, j, bq, bk, scale, softcap, causal,
                            window, m_row, l_row)
        # dV += P^T dO
        dv_acc[...] += jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dP = dO V^T ; dS = P * (dP - delta)
        dp = jax.lax.dot_general(
            do.astype(jnp.float32), v.astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        if softcap > 0:
            # d tanh-softcap: ds *= sech^2(s_pre/softcap); recover via s
            t = s / softcap
            ds = ds * (1.0 - jnp.tanh(t) ** 2)
        ds = ds * scale
        # dK += dS^T Q
        dk_acc[...] += jax.lax.dot_general(
            ds, q.reshape(-1, q.shape[-1]).astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                   dq_ref, dq_acc, *, causal, window, bq, bk, nk, scale,
                   softcap):
    j = pl.program_id(3)                 # kv tile (innermost)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = jnp.asarray(True)
    if causal:
        live = live & (j * bk <= i * bq + bq - 1)
    if window > 0:
        live = live & ((i * bq) - (j * bk + bk - 1) < window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].reshape(-1, v.shape[-1])
        m_row = m_ref[0, 0].reshape(-1)
        l_row = l_ref[0, 0].reshape(-1)
        delta = delta_ref[0, 0].reshape(-1)
        p, s = _recompute_p(q, k, i, j, bq, bk, scale, softcap, causal,
                            window, m_row, l_row)
        dp = jax.lax.dot_general(
            do.astype(jnp.float32), v.astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        if softcap > 0:
            t = s / softcap
            ds = ds * (1.0 - jnp.tanh(t) ** 2)
        ds = ds * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _flush():
        G, bq_, D = q_ref[0, 0].shape
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype).reshape(G, bq_, D)


# ---------------------------------------------------------------------------
# host-side wiring
# ---------------------------------------------------------------------------
def _layout(q, k, v, bq, bk):
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, D).transpose(0, 2, 3, 1, 4)   # (B,K,G,S,D)
    kt = k.transpose(0, 2, 1, 3)                             # (B,K,T,D)
    vt = v.transpose(0, 2, 1, 3)
    return qg, kt, vt, B, S, H, D, T, K, G


def _fwd(q, k, v, *, causal, window, softcap, bq, bk, interpret):
    qg, kt, vt, B, S, H, D, T, K, G = _layout(q, k, v, bq, bk)
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(D)
    kern = functools.partial(_fwd_kernel, causal=causal, window=window,
                             bq=bq, bk=bk, nk=nk, scale=scale,
                             softcap=softcap)
    o, m, l = pl.pallas_call(
        kern,
        grid=(B, K, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, G, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, K, G, S), jnp.float32),
            jax.ShapeDtypeStruct((B, K, G, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * bq,), jnp.float32),
            pltpu.VMEM((G * bq,), jnp.float32),
            pltpu.VMEM((G * bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kt, vt)
    out = o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)
    return out, (o, m, l)


def _bwd(q, k, v, o_blk, m, l, dout, *, causal, window, softcap, bq, bk,
         interpret):
    qg, kt, vt, B, S, H, D, T, K, G = _layout(q, k, v, bq, bk)
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(D)
    do_blk = dout.reshape(B, S, K, G, D).transpose(0, 2, 3, 1, 4)
    # delta = rowsum(dO * O) per (b, k, g, s)
    delta = jnp.sum(do_blk.astype(jnp.float32)
                    * o_blk.astype(jnp.float32), axis=-1)

    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, window=window,
                          bq=bq, bk=bk, nq=nq, scale=scale,
                          softcap=softcap),
        grid=(B, K, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, j, i: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, j, i: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, j, i: (b, h, 0, i)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, j, i: (b, h, 0, i)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, j, i: (b, h, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, K, T, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kt, vt, do_blk, m, l, delta)
    dk_b, dv_b = dkv

    dq_b = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, window=window,
                          bq=bq, bk=bk, nk=nk, scale=scale,
                          softcap=softcap),
        grid=(B, K, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((1, 1, G, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bq, D),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((G * bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kt, vt, do_blk, m, l, delta)

    dq = dq_b.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D).astype(q.dtype)
    dk = dk_b.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_b.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_vjp(q, k, v, causal, window, softcap, block_q, block_k,
                        interpret):
    """Differentiable fused flash attention (Pallas fwd + bwd kernels)."""
    out, _ = _fwd(q, k, v, causal=causal, window=window, softcap=softcap,
                  bq=min(block_q, q.shape[1]),
                  bk=min(block_k, k.shape[1]), interpret=interpret)
    return out


def _vjp_fwd(q, k, v, causal, window, softcap, block_q, block_k,
             interpret):
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    out, (o_blk, m, l) = _fwd(q, k, v, causal=causal, window=window,
                              softcap=softcap, bq=bq, bk=bk,
                              interpret=interpret)
    return out, (q, k, v, o_blk, m, l)


def _vjp_bwd(causal, window, softcap, block_q, block_k, interpret,
             res, dout):
    q, k, v, o_blk, m, l = res
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    dq, dk, dv = _bwd(q, k, v, o_blk, m, l, dout, causal=causal,
                      window=window, softcap=softcap, bq=bq, bk=bk,
                      interpret=interpret)
    return dq, dk, dv


flash_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
