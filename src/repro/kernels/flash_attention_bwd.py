"""Differentiable fused GQA flash attention — Pallas TPU kernels.

FlashAttention-2 style: one forward kernel and two backward kernels.

  * ``_fwd_kernel``      — grid (B, K, q_block, kv_block): online softmax
    over the kv tiles (kv innermost, sequential); emits O and the row
    log-sum-exp ``lse = m + log(l)``.
  * ``_bwd_dkv_kernel``  — grid (B, K, kv_block, q_block): for a fixed KV
    tile, accumulate dK/dV over the q tiles in VMEM scratch (q innermost,
    sequential).
  * ``_bwd_dq_kernel``   — grid (B, K, q_block, kv_block): for a fixed Q
    tile, accumulate dQ over kv tiles.

The backward kernels recompute each tile's probabilities from the saved
``lse`` — no (S, T) matrix and no score tile ever reaches HBM.
``delta = rowsum(dO * O)`` is precomputed outside (a cheap fused
elementwise+reduce).  All G query heads of a GQA group ride in one
(G*bq, bk) tile, so each K/V tile is read once per group.

Layout: the row statistics (``lse``, ``delta``) are kept lane-broadcast,
``(..., rows, 128)`` f32, as in ``jax.experimental.pallas.ops.tpu.
flash_attention``: a per-row vector then needs no relayout between the
sublane-major column the softmax reduces to and the HBM array.  MXU
operands are cast to the inputs' dtype (P, dO, V, dS, Q, K) and
accumulate in f32; the statistics and dS's elementwise math stay f32.
Tiles entirely above the causal diagonal (or older than the window) are
skipped with ``pl.when``, only tiles that straddle the band are masked,
and under causal masking the index maps clamp to the last live tile so a
skipped tile is never fetched.

Exposed through ``flash_attention_vjp`` (jax.custom_vjp); gradients are
validated against jax.grad of the oracle in tests/test_kernels_bwd.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
LANES = 128                     # lane width of the row statistics
TILE_ROWS = 4096                # score rows (G * block_q) of a tile, D <= 64
TILE_COLS = 512                 # keys (block_k) of a tile; most block_q
VMEM_LIMIT = 64 * 2**20


def kernel_blocks(G: int, D: int) -> tuple:
    """(block_q, block_k) of the kernels for G query heads per kv head of
    width D.  A tile holds ``G * block_q`` score rows, so block_q shrinks
    as G grows, and the tile's (rows, D) operands shrink it further for
    heads wider than 64.  block_q is a power of two from 8 (sublanes) to
    block_k, block_k a multiple of 128 (lanes).  The budget comes from a
    block sweep on one TPU v5e at qwen2-0.5b's G = 7, D = 64 (PERF.md)."""
    rows = TILE_ROWS * 64 // max(D, 64)
    bq = 8
    while bq * 2 * G <= rows and bq < TILE_COLS:
        bq *= 2
    return bq, TILE_COLS


def _cols(x, n):
    """A lane-broadcast (R, 128) statistic as (R, n) columns."""
    reps, rem = divmod(n, LANES)
    if rem == 0:
        return x if reps == 1 else jnp.tile(x, (1, reps))
    if reps == 0:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _bands(i, j, bq, bk, causal, window):
    """(live, straddles): the tile (i, j) holds a key some query attends
    to; it also holds a key some query must not."""
    live = jnp.asarray(True)
    edge = jnp.asarray(False)
    if causal:
        live = live & (j * bk <= i * bq + bq - 1)
        edge = edge | (j * bk + bk - 1 > i * bq)
    if window > 0:
        live = live & (i * bq - (j * bk + bk - 1) < window)
        edge = edge | (i * bq + bq - 1 - j * bk >= window)
    return live, edge


def _tiles(body, i, j, bq, bk, causal, window):
    """Run ``body(masked)`` on a live tile, masking only where the tile
    straddles the causal / window band."""
    live, edge = _bands(i, j, bq, bk, causal, window)
    if not (causal or window > 0):
        body(False)
        return

    @pl.when(live & edge)
    def _edge():
        body(True)

    @pl.when(live & jnp.logical_not(edge))
    def _inner():
        body(False)


def _scores(q, k, i, j, *, bq, bk, scale, softcap, causal, window,
            masked):
    """The (G*bq, bk) f32 scores of q tile i against kv tile j, softcapped
    and, where ``masked``, NEG_INF outside the band; with the softcap's
    derivative (None without softcap)."""
    G, _, D = q.shape
    s = jax.lax.dot_general(
        q.reshape(G * bq, D), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    dcap = None
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
        dcap = 1.0 - (s / softcap) ** 2
    if masked:
        shape = (G, bq, bk)
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        diff = (q_pos - k_pos).reshape(G * bq, bk)
        keep = jnp.asarray(True)
        if causal:
            keep = keep & (diff >= 0)
        if window > 0:
            keep = keep & (diff < window)
        s = jnp.where(keep, s, NEG_INF)
    return s, dcap


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, causal, window, bq, bk, nk, scale, softcap):
    i = pl.program_id(2)
    j = pl.program_id(3)                 # kv tile (innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        v = v_ref[0, 0]
        s, _ = _scores(q_ref[0, 0], k_ref[0, 0], i, j, bq=bq, bk=bk,
                       scale=scale, softcap=softcap, causal=causal,
                       window=window, masked=masked)
        m_prev = m_ref[...]                                   # (R, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _cols(m_new, bk))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _cols(corr, v.shape[-1]) + (
            jax.lax.dot_general(p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))

    _tiles(body, i, j, bq, bk, causal, window)

    @pl.when(j == nk - 1)
    def _flush():
        G, _, D = o_ref.shape[2:]
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / _cols(l, D)).astype(
            o_ref.dtype).reshape(G, bq, D)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).reshape(G, bq, LANES)


def _probs(q_ref, k_ref, lse_ref, i, j, masked, *, bq, bk, scale, softcap,
           causal, window):
    """Recompute the (G*bq, bk) probability tile from the saved lse."""
    s, dcap = _scores(q_ref[0, 0], k_ref[0, 0], i, j, bq=bq, bk=bk,
                      scale=scale, softcap=softcap, causal=causal,
                      window=window, masked=masked)
    lse = lse_ref[0, 0].reshape(-1, LANES)
    return jnp.exp(s - _cols(lse, bk)), dcap


def _dscores(p, dcap, do, v, di_ref, bk):
    """dS = P * (dO V^T - delta), through the softcap; f32, unscaled."""
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _cols(di_ref[0, 0].reshape(-1, LANES), bk))
    return ds if dcap is None else ds * dcap


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal, window,
                    bq, bk, nq, scale, softcap):
    j = pl.program_id(2)                 # kv tile (this kernel's output)
    i = pl.program_id(3)                 # q tile (innermost)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        D = q_ref.shape[-1]
        v = v_ref[0, 0]
        do = do_ref[0, 0].reshape(-1, D)                      # (R, D)
        p, dcap = _probs(q_ref, k_ref, lse_ref, i, j, masked, bq=bq, bk=bk,
                         scale=scale, softcap=softcap, causal=causal,
                         window=window)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype).T, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _dscores(p, dcap, do, v, di_ref, bk)
        q = q_ref[0, 0].reshape(-1, D)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype).T, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tiles(body, i, j, bq, bk, causal, window)

    @pl.when(i == nq - 1)
    def _flush():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                   dq_acc, *, causal, window, bq, bk, nk, scale, softcap):
    i = pl.program_id(2)
    j = pl.program_id(3)                 # kv tile (innermost)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(masked):
        k = k_ref[0, 0]
        do = do_ref[0, 0].reshape(-1, k.shape[-1])
        p, dcap = _probs(q_ref, k_ref, lse_ref, i, j, masked, bq=bq, bk=bk,
                         scale=scale, softcap=softcap, causal=causal,
                         window=window)
        ds = _dscores(p, dcap, do, v_ref[0, 0], di_ref, bk)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tiles(body, i, j, bq, bk, causal, window)

    @pl.when(j == nk - 1)
    def _flush():
        G, _, D = dq_ref.shape[2:]
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(
            dq_ref.dtype).reshape(G, bq, D)


# ---------------------------------------------------------------------------
# host-side wiring
# ---------------------------------------------------------------------------
def _layout(q, k, v):
    B, S, H, D = q.shape
    K = k.shape[2]
    qg = q.reshape(B, S, K, H // K, D).transpose(0, 2, 3, 1, 4)  # B,K,G,S,D
    return qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # B,K,T,D


def _specs(G, D, bq, bk, causal, kv_outer):
    """BlockSpecs of the q-side (B,K,G,S,·) and kv-side (B,K,T,D) operands
    for grid (B, K, outer, inner).  Under causal masking the tile index of
    the inner axis is clamped to the outer tile's live range, so skipped
    tiles repeat the previous block index and are not fetched again."""
    if kv_outer:
        def qi(j, i):
            return jnp.maximum(i, j * bk // bq) if causal else i
        q_idx = lambda b, h, j, i: (b, h, 0, qi(j, i), 0)
        kv_idx = lambda b, h, j, i: (b, h, j, 0)
    else:
        def kj(i, j):
            return jnp.minimum(j, (i * bq + bq - 1) // bk) if causal else j
        q_idx = lambda b, h, i, j: (b, h, 0, i, 0)
        kv_idx = lambda b, h, i, j: (b, h, kj(i, j), 0)
    return (pl.BlockSpec((1, 1, G, bq, D), q_idx),
            pl.BlockSpec((1, 1, G, bq, LANES), q_idx),
            pl.BlockSpec((1, 1, bk, D), kv_idx))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _fwd(q, k, v, *, causal, window, softcap, bq, bk, interpret):
    qg, kt, vt = _layout(q, k, v)
    B, K, G, S, D = qg.shape
    T = kt.shape[2]
    nq, nk = S // bq, T // bk
    q_spec, stat_spec, kv_spec = _specs(G, D, bq, bk, causal, False)
    kern = functools.partial(_fwd_kernel, causal=causal, window=window,
                             bq=bq, bk=bk, nk=nk, scale=1.0 / math.sqrt(D),
                             softcap=softcap)
    o, lse = pl.pallas_call(
        kern,
        grid=(B, K, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, G, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, K, G, S, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * bq, LANES), jnp.float32),     # running max
            pltpu.VMEM((G * bq, LANES), jnp.float32),     # running denom
            pltpu.VMEM((G * bq, D), jnp.float32),         # output acc
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qg, kt, vt)
    out = o.transpose(0, 3, 1, 2, 4).reshape(q.shape)
    return out, (o, lse)


def _bwd(q, k, v, o_blk, lse, dout, *, causal, window, softcap, bq, bk,
         interpret):
    qg, kt, vt = _layout(q, k, v)
    B, K, G, S, D = qg.shape
    T = kt.shape[2]
    nq, nk = S // bq, T // bk
    do_blk = dout.reshape(B, S, K, G, D).transpose(0, 2, 3, 1, 4)
    delta = jnp.sum(do_blk.astype(jnp.float32) * o_blk.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (B, K, G, S, LANES))
    statics = dict(causal=causal, window=window, bq=bq, bk=bk,
                   scale=1.0 / math.sqrt(D), softcap=softcap)

    q_spec, stat_spec, kv_spec = _specs(G, D, bq, bk, causal, True)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **statics),
        grid=(B, K, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qg, kt, vt, do_blk, lse, delta)

    q_spec, stat_spec, kv_spec = _specs(G, D, bq, bk, causal, False)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **statics),
        grid=(B, K, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((G * bq, D), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qg, kt, vt, do_blk, lse, delta)

    return (dq.transpose(0, 3, 1, 2, 4).reshape(q.shape),
            dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_vjp(q, k, v, causal, window, softcap, block_q, block_k,
                        interpret):
    """Differentiable fused flash attention (Pallas fwd + bwd kernels).

    q (B,S,H,D); k/v (B,T,K,D) -> (B,S,H,D), H = K·G (GQA).  S and T
    must be multiples of the (clamped) blocks."""
    out, _ = _vjp_fwd(q, k, v, causal, window, softcap, block_q, block_k,
                      interpret)
    return out


def _vjp_fwd(q, k, v, causal, window, softcap, block_q, block_k,
             interpret):
    bq, bk = min(block_q, q.shape[1]), min(block_k, k.shape[1])
    assert q.shape[1] % bq == 0 and k.shape[1] % bk == 0, (
        q.shape, k.shape, bq, bk)
    out, (o_blk, lse) = _fwd(q, k, v, causal=causal, window=window,
                             softcap=softcap, bq=bq, bk=bk,
                             interpret=interpret)
    return out, (q, k, v, o_blk, lse)


def _vjp_bwd(causal, window, softcap, block_q, block_k, interpret,
             res, dout):
    q, k, v, o_blk, lse = res
    bq, bk = min(block_q, q.shape[1]), min(block_k, k.shape[1])
    return _bwd(q, k, v, o_blk, lse, dout, causal=causal, window=window,
                softcap=softcap, bq=bq, bk=bk, interpret=interpret)


flash_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
