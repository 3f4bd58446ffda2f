"""Fused IO-aware GQA attention — Pallas TPU kernel.

TPU-native adaptation of FlashAttention: the (S, T) score matrix never
leaves VMEM.  Grid = (batch, kv_head, q_block, kv_block) with the kv axis
innermost; the online-softmax running state (m, l, acc) lives in VMEM
scratch and persists across the sequential kv iterations — the TPU idiom
replacing the GPU's per-SM shared-memory tiling.  All G query heads of a
GQA group ride in one block so each K/V tile is loaded from HBM once per
group (the arithmetic-intensity win the GPU formulation gets from warp
reuse).

Masking (causal and/or sliding-window) is positional, from program ids.
Fully-out-of-range KV tiles are skipped with ``pl.when`` (causal skips
~half the grid; sliding-window skips all tiles older than the window).

MXU layout notes:
  * last dim = head_dim (multiple of 8, <=256); second-minor multiples
    of 8; the two matmuls are (G·bq, D)x(bk, D)ᵀ and (G·bq, bk)x(bk, D).
  * fp32 accumulation (`preferred_element_type`); bf16 or f32 inputs.

Validated in interpret mode against ``repro.kernels.ref.attention_ref``
(tests/test_kernels.py sweeps shapes, dtypes, GQA ratios, windows).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, window: int, bq: int, bk: int, nk: int,
                  scale: float, softcap: float):
    i = pl.program_id(2)                 # q block
    j = pl.program_id(3)                 # kv block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile-level skip: causal => no kv block strictly after the q block;
    # window  => no kv block entirely older than the sliding window.
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
            preferred_element_type=jnp.float32) * scale      # (G*bq, bk)
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

        m_prev = m_ref[...]              # (G*bq,)
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
        out = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        o_ref[0, 0] = out.reshape(G, bq, D)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 256,
                    block_k: int = 256, interpret: bool):
    """q (B,S,H,D); k/v (B,T,K,D) -> (B,S,H,D).  H = K·G (GQA)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(D)

    # (B, K, G, S, D): the G heads of a GQA group contiguous per kv head
    qg = q.reshape(B, S, K, G, D).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)         # (B, K, T, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, bq=bq, bk=bk, nk=nk,
        scale=scale, softcap=softcap)

    out = pl.pallas_call(
        kernel,
        grid=(B, K, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D),
                         lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bq, D),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq,), jnp.float32),       # running max
            pltpu.VMEM((G * bq,), jnp.float32),       # running denom
            pltpu.VMEM((G * bq, D), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kt, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)
