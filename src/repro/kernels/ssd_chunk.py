"""Mamba-2 SSD chunk scan (arXiv:2405.21060) — Pallas TPU kernels.

One forward kernel and one backward kernel behind ``ssd_chunk_vjp``
(jax.custom_vjp).  Both run the grid (rows, chunks, head blocks): the
chunk axis is sequential, and the state carried between chunks (forward:
``h``; backward: ``dh``) of every head block lives in VMEM scratch.  Each
grid step builds its heads' L x L decay and W tiles in VMEM and consumes
them there: no tile reaches HBM.

  * ``_fwd_kernel``  — per chunk: C·Bᵀ once for the head block, then per
    head the causal decay tile ``exp(clip(s_l - s_m, -60, 0))`` from the
    chunk's cumulative log-decay ``s``, ``W = C·Bᵀ ∘ decay ∘ dt_m`` and
    ``y = W·x + exp(s)·(C·h)``; the state update
    ``h <- exp(s_L)·h + Bᵀ·(dt·exp(s_L - s)·x)``.  Emits y (f32), the
    final state and the state entering every chunk (the backward's
    residual).
  * ``_bwd_kernel``  — the chunks in reverse, carrying ``dh``: recomputes
    each chunk's tiles from the saved inputs and entering state, and
    emits dx, dB and dC (summed over the head blocks of a group in the
    output block), d(dt), d(s) and the gradient of the initial state.

The within-chunk cumulative sum ``s`` of ``dt·A`` is computed by the
caller (XLA), which also maps ``d(s)`` back to dt and A; no cumsum runs
in a kernel.

Layout: x, y and dx stay in their (B, S, H·P) layout and B/C in
(B, S, G·N): a block of ``head_block`` heads is a lane-dense (L, hb·P)
tile.  Heads narrower than 128 lanes share a lane group; a head's
products run over the whole group and a lane mask keeps its own lanes,
which costs the MXU nothing at P = 64 (a 128-column product takes the
passes of a 64-column one).  Per-head scalars along the sequence (dt and
``s``) come in two small layouts, rows (B, nhb, hb, S) and columns
(B, nhb, S, hb), so that a head's vector broadcasts along either axis of
a tile without an in-kernel transpose; d(dt) and d(s) leave in both
layouts and the caller adds them.

Numerics: every elementwise step (decay, mask, state, accumulation) is
f32.  MXU operands are cast to the inputs' dtype (bf16 on the train
path, as XLA's default-precision f32 dot rounds them) and accumulate in
f32; f32 inputs keep f32 operands.  The gradient ignores the -60 clip,
which changes it by terms of exp(-60) relative.

Gradients are validated against the XLA chunk scan and the plain
reference in tests/test_ssd_kernel.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANES = 128
HEAD_LANES = 512                # lanes (heads x head width) of a grid step
MAX_CHUNK = 512                 # an L x L f32 tile of at most 1 MiB
CLIP = -60.0                    # floor of a log-decay, as the XLA scan
VMEM_LIMIT = 64 * 2**20
F32 = jnp.float32


def head_block(H: int, G: int, P: int) -> int:
    """Heads per grid step: the most heads of one group whose lanes are a
    multiple of 128 and at most ``HEAD_LANES``; 0 where none is."""
    if H % G:
        return 0
    per_group = H // G
    return max((hb for hb in range(1, per_group + 1)
                if per_group % hb == 0 and hb * P % LANES == 0
                and hb * P <= HEAD_LANES), default=0)


def fits(H: int, P: int, G: int, N: int, chunk: int) -> bool:
    """Whether the kernels' tiles fit these widths: a chunk of whole lane
    tiles, heads that divide a lane group, a state of whole lane tiles,
    and a head block (``head_block``)."""
    return (chunk % LANES == 0 and chunk <= MAX_CHUNK
            and P <= LANES and LANES % P == 0 and N % LANES == 0
            and head_block(H, G, P) > 0)


# ---------------------------------------------------------------------------
# in-kernel helpers
# ---------------------------------------------------------------------------
def _dot(a, b, dtype):
    """a·b with operands in ``dtype``, f32 accumulation."""
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=F32)


def _dot_t(a, b, dtype):
    """a·bᵀ (contracting the last axes) with operands in ``dtype``."""
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _decay(s_col, s_row, causal):
    """The causal (L, L) decay tile exp(clip(s_l - s_m, -60, 0)), zero
    above the diagonal."""
    seg = jnp.clip(s_col - s_row, CLIP, 0.0)
    return jnp.where(causal, jnp.exp(seg), 0.0)


def _in_head(lane, i, P):
    """Lanes of the i-th head of a 128-lane group."""
    return (lane >= i * P) & (lane < (i + 1) * P)


def _spread(vals, lane, P):
    """Per-head values of a lane group, each broadcast over its head's
    lanes: (rows, 1) each -> (rows, 128)."""
    out = vals[0]
    for i, v in enumerate(vals[1:], 1):
        out = jnp.where(lane >= i * P, v, out)
    return jnp.broadcast_to(out, (out.shape[0], LANES)) \
        if out.shape[1] == 1 else out


def _total(t):
    """The sum of a 2-D array, as (1, 1)."""
    return jnp.sum(jnp.sum(t, axis=1, keepdims=True), axis=0, keepdims=True)


def _put_col(blk, col, hh):
    """``blk`` (L, hb) with column ``hh`` set to ``col`` (L, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.where(lane == hh, col, blk)


def _put_row(blk, row, hh):
    """``blk`` (hb, L) with row ``hh`` set to ``row`` (1, L)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    return jnp.where(sub == hh, row, blk)


class _Chunk:
    """One grid step's per-head vectors: dt and the cumulative log-decay
    ``s`` as rows (1, L) and columns (L, 1), and the chunk's last ``s``."""

    def __init__(self, dtr_ref, dtc_ref, sr_ref, sc_ref):
        self.dtr, self.dtc = dtr_ref[0, 0], dtc_ref[0, 0]
        self.sr, self.sc = sr_ref[0, 0], sc_ref[0, 0]
        L = self.sc.shape[0]
        self.last = self.sc[L - 1:L, :]                     # (1, hb)

    def row(self, v, hh):
        return v[hh:hh + 1, :]

    def col(self, v, hh):
        return v[:, hh:hh + 1]

    def rest(self, hh):
        """exp(clip(s_L - s_m, -60)): the decay from m to the chunk's end."""
        return jnp.exp(jnp.maximum(
            self.col(self.last, hh) - self.col(self.sc, hh), CLIP))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _fwd_kernel(x_ref, dtr_ref, dtc_ref, sr_ref, sc_ref, b_ref, c_ref,
                h0_ref, y_ref, hs_ref, hf_ref, h_scr, *, P):
    c = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[j] = h0_ref[0]

    h = h_scr[j]                                          # (N, W) f32
    hs_ref[0, 0] = h
    x = x_ref[0]                                          # (L, W)
    mx = x.dtype
    bm, cm = b_ref[0], c_ref[0]                           # (L, N)
    L = x.shape[0]
    ch = _Chunk(dtr_ref, dtc_ref, sr_ref, sc_ref)
    cb = _dot_t(cm, bm, mx)                               # (L, L)
    bt = bm.astype(mx).T                                  # (N, L)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    k = LANES // P
    for g in range(x.shape[1] // LANES):
        sl = slice(g * LANES, (g + 1) * LANES)
        heads = range(g * k, (g + 1) * k)
        xg, hg = x[:, sl], h[:, sl]
        y = None
        for i, hh in enumerate(heads):
            w = cb * _decay(ch.col(ch.sc, hh), ch.row(ch.sr, hh), causal) \
                * ch.row(ch.dtr, hh)
            yh = _dot(w, xg, mx)                          # (L, 128)
            y = yh if y is None else jnp.where(_in_head(lane, i, P), yh, y)
        e = _spread([jnp.exp(ch.col(ch.sc, hh)) for hh in heads], lane, P)
        y_ref[0, :, sl] = y + e * _dot(cm, hg, mx)
        u = _spread([ch.col(ch.dtc, hh) * ch.rest(hh) for hh in heads],
                    lane, P)
        decay = _spread([jnp.exp(ch.col(ch.last, hh)) for hh in heads],
                        lane, P)                          # (1, 128)
        h_new = decay * hg + _dot(bt, xg.astype(F32) * u, mx)
        h_scr[j, :, sl] = h_new
        hf_ref[0, :, sl] = h_new


def _bwd_kernel(x_ref, dy_ref, dtr_ref, dtc_ref, sr_ref, sc_ref, b_ref,
                c_ref, hs_ref, dhf_ref, dx_ref, db_ref, dc_ref, ddtr_ref,
                ddtc_ref, dsr_ref, dsc_ref, dh0_ref, dh_scr, *, P,
                blocks_per_group):
    c = pl.program_id(1)                                  # chunks reversed
    j = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dh_scr[j] = dhf_ref[0]

    @pl.when(j % blocks_per_group == 0)
    def _zero():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    dh, h = dh_scr[j], hs_ref[0, 0]                       # (N, W) f32
    x, dy = x_ref[0], dy_ref[0]                           # (L, W)
    mx = x.dtype
    bm, cm = b_ref[0], c_ref[0]                           # (L, N)
    L = x.shape[0]
    ch = _Chunk(dtr_ref, dtc_ref, sr_ref, sc_ref)
    cb = _dot_t(cm, bm, mx)                               # (L, L)
    ct = cm.astype(mx).T                                  # (N, L)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    below = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
             > jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    k = LANES // P
    dcb = jnp.zeros((L, L), F32)
    dcm = jnp.zeros(cm.shape, F32)
    dbm = jnp.zeros(bm.shape, F32)
    ddt_r, ds_r = jnp.zeros_like(ch.dtr), jnp.zeros_like(ch.dtr)
    ddt_c, ds_c = jnp.zeros_like(ch.dtc), jnp.zeros_like(ch.dtc)
    for g in range(x.shape[1] // LANES):
        sl = slice(g * LANES, (g + 1) * LANES)
        heads = range(g * k, (g + 1) * k)
        xg, dyg, hg, dhg = x[:, sl], dy[:, sl], h[:, sl], dh[:, sl]
        e = _spread([jnp.exp(ch.col(ch.sc, hh)) for hh in heads], lane, P)
        u = _spread([ch.col(ch.dtc, hh) * ch.rest(hh) for hh in heads],
                    lane, P)
        decay = _spread([jnp.exp(ch.col(ch.last, hh)) for hh in heads],
                        lane, P)
        # the state read-out y += exp(s)·(C·h) and the state update
        edy = e * dyg                                     # (L, 128)
        xu = xg.astype(F32) * u
        bdh = _dot(bm, dhg, mx)                           # (L, 128) B·dh
        dcm += _dot_t(edy, hg, mx)
        dbm += _dot_t(xu, dhg, mx)
        dh_scr[j, :, sl] = decay * dhg + _dot(ct, edy, mx)
        dh0_ref[0, :, sl] = dh_scr[j, :, sl]
        t_out = edy * _dot(cm, hg, mx)                    # d s, read-out
        t_upd = xg.astype(F32) * bdh                      # d u / dt·rest
        t_dec = dhg * hg                                  # d exp(s_L)
        dx = u * bdh
        for i, hh in enumerate(heads):
            m = _in_head(lane, i, P)
            rest = ch.rest(hh)                            # (L, 1)
            s_upd = jnp.sum(jnp.where(m, t_upd, 0.0), axis=1, keepdims=True)
            q = ch.col(ch.dtc, hh) * rest * s_upd         # via rest
            to_last = (jnp.sum(q, axis=0, keepdims=True)
                       + jnp.exp(ch.col(ch.last, hh)) * _total(
                           jnp.where(m, t_dec, 0.0)))
            ds_col = (jnp.sum(jnp.where(m, t_out, 0.0), axis=1,
                              keepdims=True) - q
                      + jnp.where(is_last, to_last, 0.0))
            # the intra-chunk product y += W·x
            dtr = ch.row(ch.dtr, hh)                      # (1, L)
            d = _decay(ch.col(ch.sc, hh), ch.row(ch.sr, hh), causal)
            w = cb * d * dtr
            dx = dx + jnp.where(m, _dot(w.astype(mx).T, dyg, mx), 0.0)
            dw = _dot_t(jnp.where(m, dyg, 0.0), xg, mx)   # (L, L)
            pd = dw * d
            dcb += pd * dtr
            qd = pd * cb
            ddt_r = _put_row(ddt_r, jnp.sum(qd, axis=0, keepdims=True), hh)
            qd = jnp.where(below, qd, 0.0)
            ds_col += jnp.sum(qd * dtr, axis=1, keepdims=True)
            ds_r = _put_row(ds_r, -dtr * jnp.sum(qd, axis=0, keepdims=True),
                            hh)
            ddt_c = _put_col(ddt_c, rest * s_upd, hh)
            ds_c = _put_col(ds_c, ds_col, hh)
        dx_ref[0, :, sl] = dx.astype(dx_ref.dtype)
    db_ref[0] += dbm + _dot(dcb.astype(mx).T, cm, mx)
    dc_ref[0] += dcm + _dot(dcb, bm, mx)
    ddtr_ref[0, 0] = ddt_r
    ddtc_ref[0, 0] = ddt_c
    dsr_ref[0, 0] = ds_r
    dsc_ref[0, 0] = ds_c


# ---------------------------------------------------------------------------
# host-side wiring
# ---------------------------------------------------------------------------
def _rows(v, hb):
    """(B, S, H) -> (B, H/hb, hb, S)."""
    B, S, H = v.shape
    return v.reshape(B, S, H // hb, hb).transpose(0, 2, 3, 1)


def _cols(v, hb):
    """(B, S, H) -> (B, H/hb, S, hb)."""
    B, S, H = v.shape
    return v.reshape(B, S, H // hb, hb).transpose(0, 2, 1, 3)


def _from_rows(r):
    B, nhb, hb, S = r.shape
    return r.transpose(0, 3, 1, 2).reshape(B, S, nhb * hb)


def _from_cols(c):
    B, nhb, S, hb = c.shape
    return c.transpose(0, 2, 1, 3).reshape(B, S, nhb * hb)


def _lanes(h):
    """(B, H, N, P) -> (B, N, H·P)."""
    B, H, N, P = h.shape
    return h.transpose(0, 2, 1, 3).reshape(B, N, H * P)


def _heads(h, H):
    """(B, N, H·P) -> (B, H, N, P)."""
    B, N, HP = h.shape
    return h.reshape(B, N, H, HP // H).transpose(0, 2, 1, 3)


class _Plan:
    """Shapes, grid and BlockSpecs of one call."""

    def __init__(self, x, Bm, chunk, reverse):
        B, S, H, P = x.shape
        G, N = Bm.shape[2], Bm.shape[3]
        self.B, self.S, self.H, self.P, self.G, self.N = B, S, H, P, G, N
        self.L = L = chunk
        self.nc = nc = S // chunk
        self.hb = hb = head_block(H, G, P)
        self.nhb = H // hb
        self.W = W = hb * P
        self.per_group = per_group = H // G // hb
        self.grid = (B, nc, self.nhb)
        cc = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
        self.seq = pl.BlockSpec((1, L, W), lambda b, c, j: (b, cc(c), j))
        self.row = pl.BlockSpec((1, 1, hb, L),
                                lambda b, c, j: (b, j, 0, cc(c)))
        self.col = pl.BlockSpec((1, 1, L, hb),
                                lambda b, c, j: (b, j, cc(c), 0))
        self.grp = pl.BlockSpec((1, L, N),
                                lambda b, c, j: (b, cc(c), j // per_group))
        self.state = pl.BlockSpec((1, N, W), lambda b, c, j: (b, 0, j))
        self.states = pl.BlockSpec((1, 1, N, W),
                                   lambda b, c, j: (b, cc(c), 0, j))
        self.params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT)


def _vectors(dt, s, hb):
    return _rows(dt, hb), _cols(dt, hb), _rows(s, hb), _cols(s, hb)


def _fwd(x, dt, s, Bm, Cm, h0, chunk, interpret):
    p = _Plan(x, Bm, chunk, reverse=False)
    B, S, H, P, G, N = p.B, p.S, p.H, p.P, p.G, p.N
    y, hs, hf = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P),
        grid=p.grid,
        in_specs=[p.seq, p.row, p.col, p.row, p.col, p.grp, p.grp,
                  p.state],
        out_specs=[p.seq, p.states, p.state],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * P), F32),
                   jax.ShapeDtypeStruct((B, p.nc, N, H * P), F32),
                   jax.ShapeDtypeStruct((B, N, H * P), F32)],
        scratch_shapes=[pltpu.VMEM((p.nhb, N, p.W), F32)],
        compiler_params=p.params,
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(x.reshape(B, S, H * P), *_vectors(dt, s, p.hb),
      Bm.reshape(B, S, G * N), Cm.reshape(B, S, G * N),
      _lanes(h0))
    return y.reshape(x.shape), _heads(hf, H), hs


def _bwd(x, dt, s, Bm, Cm, hs, dy, dhf, chunk, interpret):
    p = _Plan(x, Bm, chunk, reverse=True)
    B, S, H, P, G, N = p.B, p.S, p.H, p.P, p.G, p.N
    vec = jax.ShapeDtypeStruct((B, p.nhb, p.hb, S), F32)
    vec_t = jax.ShapeDtypeStruct((B, p.nhb, S, p.hb), F32)
    dx, db, dc, ddtr, ddtc, dsr, dsc, dh0 = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, blocks_per_group=p.per_group),
        grid=p.grid,
        in_specs=[p.seq, p.seq, p.row, p.col, p.row, p.col, p.grp, p.grp,
                  p.states, p.state],
        out_specs=[p.seq, p.grp, p.grp, p.row, p.col, p.row, p.col,
                   p.state],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * P), x.dtype),
                   jax.ShapeDtypeStruct((B, S, G * N), F32),
                   jax.ShapeDtypeStruct((B, S, G * N), F32),
                   vec, vec_t, vec, vec_t,
                   jax.ShapeDtypeStruct((B, N, H * P), F32)],
        scratch_shapes=[pltpu.VMEM((p.nhb, N, p.W), F32)],
        compiler_params=p.params,
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(x.reshape(B, S, H * P), dy.astype(F32).reshape(B, S, H * P),
      *_vectors(dt, s, p.hb), Bm.reshape(B, S, G * N),
      Cm.reshape(B, S, G * N), hs, _lanes(dhf.astype(F32)))
    return (dx.reshape(x.shape), _from_rows(ddtr) + _from_cols(ddtc),
            _from_rows(dsr) + _from_cols(dsc),
            db.reshape(Bm.shape).astype(Bm.dtype),
            dc.reshape(Cm.shape).astype(Cm.dtype), _heads(dh0, H))


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_chunk_vjp(x, dt, s, Bm, Cm, h0, chunk, interpret):
    """The chunked SSD core through the Pallas kernels.

    x (B,S,H,P); dt and s (B,S,H) f32, s the within-chunk cumulative sum
    of dt·A; Bm, Cm (B,S,G,N); h0 (B,H,N,P) f32.  S must be a multiple of
    ``chunk`` and the widths must pass ``fits``.  Returns y (B,S,H,P) f32
    and the final state (B,H,N,P) f32."""
    y, hf, _ = _fwd(x, dt, s, Bm, Cm, h0, chunk, interpret)
    return y, hf


def _vjp_fwd(x, dt, s, Bm, Cm, h0, chunk, interpret):
    y, hf, hs = _fwd(x, dt, s, Bm, Cm, h0, chunk, interpret)
    return (y, hf), (x, dt, s, Bm, Cm, hs)


def _vjp_bwd(chunk, interpret, res, cts):
    x, dt, s, Bm, Cm, hs = res
    dy, dhf = cts
    return _bwd(x, dt, s, Bm, Cm, hs, dy, dhf, chunk, interpret)


ssd_chunk_vjp.defvjp(_vjp_fwd, _vjp_bwd)
