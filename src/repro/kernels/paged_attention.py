"""Paged-attention decode — Pallas TPU kernel over the serving page pool.

Decode attention that reads K/V *directly from the paged KV pool*
(``serve/kvcache.PagePool`` layout: ``(n_pages + 1, page_size, K, D)``
per layer) via per-sequence block tables, so the jitted decode step never
materializes the dense ``(B, W, K, D)`` cache view that
``kvcache.gather_dense`` builds for the XLA path.

Layout and grid:

  * the block tables (``(B, P)`` int32 page ids) and per-sequence token
    counts (``(B,)``) ride in as **scalar-prefetch** operands
    (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps can
    steer each grid step's DMA to the right physical page — the standard
    TPU paged-attention trick;
  * grid = ``(batch, kv_head, kv_superblock)`` with the kv axis innermost
    and sequential; one superblock covers ``block_k // page_size``
    (possibly non-contiguous) pages, fetched as that many single-page
    block copies of the pool (one ``in_spec`` per page slot — Pallas
    block shapes must be static, the page *ids* are not);
  * online-softmax running state (m, l, acc) lives in VMEM scratch
    exactly as in ``flash_attention_bwd.py``; all G query heads of a GQA
    group ride in one block.

Masking is positional: slot ``t`` of sequence ``b`` is live iff
``t < lengths[b]`` — the pool writes sequences contiguously from
position 0, so this is the kernel-side equivalent of the dense path's
``pos >= 0`` mask (padding rows with ``lengths == 0`` produce zeros).
Superblocks entirely past ``lengths[b]`` are skipped with ``pl.when``
(table pad entries point at the pool's scratch page and are never read
live).

Validated in interpret mode against ``repro.kernels.ref
.paged_attention_ref`` across page-boundary and ragged-length cases
(tests/test_paged_attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lengths_ref, q_ref, *refs, ppb: int, ps: int,
                  nb: int, scale: float, softcap: float):
    k_refs = refs[:ppb]                    # ppb x (1, ps, 1, D) page blocks
    v_refs = refs[ppb:2 * ppb]
    o_ref = refs[2 * ppb]
    m_ref, l_ref, acc_ref = refs[2 * ppb + 1:]

    b = pl.program_id(0)
    j = pl.program_id(2)                   # kv superblock (innermost, seq.)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    base = j * (ppb * ps)                  # first token slot of this block

    @pl.when(base < length)
    def _tile():
        q = q_ref[0, 0]                    # (G, D)
        k = jnp.concatenate([r[0, :, 0, :] for r in k_refs], axis=0)
        v = jnp.concatenate([r[0, :, 0, :] for r in v_refs], axis=0)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G, bk)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]                # (G,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, tables, lengths, *,
                           block_k: int = 256, softcap: float = 0.0,
                           interpret: bool):
    """q (B,H,D) one decode token/seq; k/v pages (N,ps,K,D); tables (B,P)
    int32 page ids; lengths (B,) valid-token counts -> (B,H,D).

    ``block_k`` is fitted down to a multiple of the page size whose
    page count divides P, so any value is legal.
    """
    B, H, D = q.shape
    ps, K = k_pages.shape[1], k_pages.shape[2]
    P = tables.shape[1]
    assert H % K == 0, (H, K)
    G = H // K
    ppb = max(1, min(int(block_k) // ps, P))   # pages per superblock
    while P % ppb:
        ppb -= 1
    nb = P // ppb
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, K, G, D)
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def page_spec(t):
        # page t of superblock j: one (ps, D) tile of kv head h, DMA'd
        # from whichever physical page the table names
        return pl.BlockSpec(
            (1, ps, 1, D),
            lambda b, h, j, tab, lens, t=t: (tab[b, j * ppb + t], 0, h, 0))

    kernel = functools.partial(_paged_kernel, ppb=ppb, ps=ps, nb=nb,
                               scale=scale, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,             # tables, lengths
        grid=(B, K, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, tab, lens:
                         (b, h, 0, 0)),
            *[page_spec(t) for t in range(ppb)],
            *[page_spec(t) for t in range(ppb)],
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, tab, lens:
                               (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),        # running max
            pltpu.VMEM((G,), jnp.float32),        # running denom
            pltpu.VMEM((G, D), jnp.float32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, qg, *([k_pages] * ppb), *([v_pages] * ppb))
    return out.reshape(B, H, D)
