"""Mamba-2 SSD (state-space duality) — Pallas TPU kernel.

Chunked dual form: within a chunk of length ``c`` the recurrence is
computed as a (c x c) causal attention-like matmul (MXU work); a rank-N
state (H, N, P) carries information between chunks and lives in VMEM
scratch across the sequential chunk axis of the grid.

Grid = (batch, n_chunks); chunk axis innermost/sequential ("arbitrary"
semantics).  Per-chunk VMEM working set for the mamba2-780m config
(c=256, H=48, N=128, P=64, G=1):

    x (c,H,P) 3.1MB + decay/W (c,c,H) 12.6MB x2 + state 1.5MB  ~= 30MB

comfortably inside the ~128MB v5e VMEM; block sizes are all multiples of
(8,128) in the minor dims.  All math fp32 (the recurrence is
precision-sensitive; matches the oracle exactly).

Validated in interpret mode against ``repro.kernels.ref.ssd_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, *, chunk: int, nc: int, hpg: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        h_ref[...] = jnp.zeros_like(h_ref)

    xc = x_ref[0].astype(jnp.float32)        # (c, H, P)
    dtc = dt_ref[0].astype(jnp.float32)      # (c, H)
    A = a_ref[...].astype(jnp.float32)       # (H,)
    Bc = b_ref[0].astype(jnp.float32)        # (c, G, N)
    Cc = c_ref[0].astype(jnp.float32)        # (c, G, N)
    c, H, P = xc.shape
    G, N = Bc.shape[1], Bc.shape[2]

    a = dtc * A                              # (c, H) log-decay
    acum = jnp.cumsum(a, axis=0)

    # ---- intra-chunk (attention-like dual form) ----
    CB = jax.lax.dot_general(                # (G, c, c)
        Cc.transpose(1, 0, 2), Bc.transpose(1, 0, 2),
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    CBh = jnp.repeat(CB, hpg, axis=0)        # (H, c, c)
    diff = acum[:, None, :] - acum[None, :, :]          # (c, c, H)
    idx_l = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    idx_m = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    causal = (idx_l >= idx_m)[..., None]
    decay = jnp.exp(jnp.clip(diff, -60.0, 0.0))
    decay = jnp.where(causal, decay, 0.0)
    W = CBh.transpose(1, 2, 0) * decay * dtc[None, :, :]   # (c, c, H)
    y_intra = jnp.einsum("lmh,mhp->lhp", W, xc,
                         preferred_element_type=jnp.float32)

    # ---- inter-chunk (incoming state contribution) ----
    h = h_ref[...].astype(jnp.float32)       # (H, N, P)
    Ch = jnp.repeat(Cc, hpg, axis=1).reshape(c, H, N) if G > 1 else \
        jnp.broadcast_to(Cc, (c, H, N))
    y_inter = jnp.exp(acum)[..., None] * jnp.einsum(
        "lhn,hnp->lhp", Ch, h, preferred_element_type=jnp.float32)

    # ---- state update ----
    rest = jnp.exp(jnp.clip(acum[-1:, :] - acum, -60.0, None))   # (c, H)
    Bh = jnp.repeat(Bc, hpg, axis=1).reshape(c, H, N) if G > 1 else \
        jnp.broadcast_to(Bc, (c, H, N))
    contrib = jnp.einsum("mhn,mhp->hnp", Bh * (dtc * rest)[..., None], xc,
                         preferred_element_type=jnp.float32)
    h_new = jnp.exp(acum[-1, :])[:, None, None] * h + contrib
    h_ref[...] = h_new

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(j == nc - 1)
    def _flush():
        hout_ref[0] = h_new.astype(hout_ref.dtype)


def ssd(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool):
    """Chunked SSD scan.

    x (B,S,H,P); dt (B,S,H); A (H,); B/C (B,S,G,N).
    Returns (y (B,S,H,P) fp32, h_final (B,H,N,P) fp32).
    """
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    c = min(chunk, S)
    assert S % c == 0, (S, c)
    nc = S // c

    kernel = functools.partial(_ssd_kernel, chunk=c, nc=nc, hpg=hpg)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, c, H, P), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, c, H), lambda b, j: (b, j, 0)),
            pl.BlockSpec((H,), lambda b, j: (0,)),
            pl.BlockSpec((1, c, G, N), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, c, G, N), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, H, P), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, H, N, P), lambda b, j: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, Bm, Cm)
    return y, h_final
