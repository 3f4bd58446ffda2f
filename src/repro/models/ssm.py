"""Mamba-2 (SSD, state-space duality) mixer — arXiv:2405.21060.

Training/prefill uses the chunked SSD algorithm as a ``lax.scan`` over
sequence chunks (quadratic attention-like math within a chunk; a rank-N
recurrent state carries information between chunks). This exactly mirrors
the Pallas kernel tiling in ``repro.kernels.ssd``. Decode is the linear
recurrence h <- exp(dt·A) h + dt·B⊗x.

Sharding design (the §Perf-driven layout): the input projections are
SPLIT per stream (z / x / B / C / dt) with per-stream causal convs —
mathematically identical to the fused in_proj+conv (depthwise convs are
channel-independent), but each output is independently shardable: the
fused layout's z/xbc/dt split points do not align with a model-axis
sharding of the fused dim, which forced 1.6 GiB all-to-alls per layer
(2.1 TiB/step on the 16x16 mesh).  The SSD core itself runs under
``shard_map`` (batch over dp, heads over tp — mamba2's H=48 = 16x3) so
no collective can appear inside the chunk scan.

Shapes: x (B, S, H, P); dt (B, S, H); A (H,); B/C (B, S, G, N); state
(B, H, N, P). H heads in G groups (heads share B/C within a group).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers


# ---------------------------------------------------------------------------
# chunked SSD core
# ---------------------------------------------------------------------------
def ssd_chunked(x, dt, A, Bm, Cm, *, chunk: int, h0=None):
    """Returns (y (B,S,H,P), h_final (B,H,N,P)). All math fp32."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    S_orig = S
    if S % chunk:
        # pad with dt=0 steps: decay=1 and zero input -> state is unchanged
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // chunk

    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    A = A.astype(jnp.float32)
    Bm = Bm.astype(jnp.float32)
    Cm = Cm.astype(jnp.float32)

    xs = x.reshape(Bsz, nc, chunk, H, P).swapaxes(0, 1)
    dts = dt.reshape(Bsz, nc, chunk, H).swapaxes(0, 1)
    Bs = Bm.reshape(Bsz, nc, chunk, G, N).swapaxes(0, 1)
    Cs = Cm.reshape(Bsz, nc, chunk, G, N).swapaxes(0, 1)

    if h0 is None:
        h0 = jnp.zeros((Bsz, H, N, P), jnp.float32)

    idx = jnp.arange(chunk)
    causal = (idx[:, None] >= idx[None, :])  # (L, L)

    def step(h, inp):
        xc, dtc, Bc, Cc = inp            # (B,L,H,P), (B,L,H), (B,L,G,N)
        a = dtc * A                       # (B,L,H) log-decay (negative)
        acum = jnp.cumsum(a, axis=1)      # (B,L,H)
        # intra-chunk (attention-like dual form)
        CB = jnp.einsum("blgn,bmgn->bglm", Cc, Bc)   # (B,G,L,L)
        CB = jnp.repeat(CB, hpg, axis=1)             # (B,H,L,L)
        decay = jnp.exp(
            jnp.clip(acum[:, :, None, :] - acum[:, None, :, :], -60.0, 0.0))
        decay = jnp.where(causal[None, :, :, None], decay, 0.0)  # (B,L,L,H)
        W = CB.transpose(0, 2, 3, 1) * decay * dtc[:, None, :, :]
        y_intra = jnp.einsum("blmh,bmhp->blhp", W, xc)
        # inter-chunk (contribution of incoming state)
        Ch = jnp.broadcast_to(Cc[:, :, :, None, :],
                              (Bsz, chunk, G, hpg, N)).reshape(
            Bsz, chunk, H, N)
        y_inter = jnp.exp(acum)[..., None] * jnp.einsum(
            "blhn,bhnp->blhp", Ch, h)
        # state update
        rest = jnp.exp(jnp.clip(acum[:, -1:, :] - acum, -60.0, None))
        Bh = jnp.broadcast_to(Bc[:, :, :, None, :],
                              (Bsz, chunk, G, hpg, N)).reshape(
            Bsz, chunk, H, N)
        contrib = jnp.einsum("bmhn,bmhp->bhnp",
                             Bh * (dtc * rest)[..., None], xc)
        h_next = jnp.exp(acum[:, -1, :])[..., None, None] * h + contrib
        return h_next, y_intra + y_inter

    h_final, ys = jax.lax.scan(step, h0, (xs, dts, Bs, Cs))
    y = ys.swapaxes(0, 1).reshape(Bsz, S, H, P)
    return y[:, :S_orig], h_final


def ssd_sharded(x, dt, A, Bm, Cm, *, chunk: int, mesh, dp_axes, tp_axis):
    """SSD core under shard_map: batch over dp, heads over tp.

    Inside the manual region every tensor is local, so the chunk scan
    can emit no collectives.  Requires H % tp == 0 (mamba2: 48 = 16x3);
    falls back to the plain path otherwise.  B/C (grouped, G=1) are
    replicated over tp; dt/A/D head-tensors are tp-sliced at entry.
    """
    B, S, H, P = x.shape
    tp = mesh.shape.get(tp_axis, 1) if tp_axis else 1
    dp = tuple(a for a in dp_axes if mesh.shape.get(a, 1) > 1)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if (tp > 1 and H % tp) or (n_dp > 1 and B % n_dp):
        return ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)

    from jax.sharding import PartitionSpec as Pspec
    dp_e = (dp if len(dp) > 1 else dp[0]) if dp else None
    tp_e = tp_axis if tp > 1 else None
    sx = Pspec(dp_e, None, tp_e, None)
    sdt = Pspec(dp_e, None, tp_e)
    sA = Pspec(tp_e)
    sBC = Pspec(dp_e, None, None, None)
    sy = Pspec(dp_e, None, tp_e, None)
    sh = Pspec(dp_e, tp_e, None, None)

    def body(xl, dtl, Al, Bl, Cl):
        return ssd_chunked(xl, dtl, Al, Bl, Cl, chunk=chunk)

    manual = frozenset(dp) | ({tp_axis} if tp > 1 else set())
    if not manual:
        return ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    already = layers.manual_axes()
    return jax.shard_map(
        body, mesh=None if already else mesh,
        axis_names=manual - already if already else manual,
        in_specs=(sx, sdt, sA, sBC, sBC),
        out_specs=(sy, sh), check_vma=False,
    )(x, dt, A, Bm, Cm)


def ssd_decode_step(x, dt, A, Bm, Cm, h):
    """One token. x (B,H,P); dt (B,H); B/C (B,G,N); h (B,H,N,P)."""
    H, G = x.shape[1], Bm.shape[1]
    hpg = H // G
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    a = jnp.exp(dt * A.astype(jnp.float32))                 # (B,H)
    Bh = jnp.broadcast_to(Bm.astype(jnp.float32)[:, :, None, :],
                          (x.shape[0], G, hpg, Bm.shape[-1])
                          ).reshape(x.shape[0], H, -1)       # (B,H,N)
    Ch = jnp.broadcast_to(Cm.astype(jnp.float32)[:, :, None, :],
                          (x.shape[0], G, hpg, Cm.shape[-1])
                          ).reshape(x.shape[0], H, -1)
    h_new = a[..., None, None] * h + \
        (dt[..., None] * Bh)[..., None] * x[:, :, None, :]   # (B,H,N,P)
    y = jnp.einsum("bhn,bhnp->bhp", Ch, h_new)
    return y, h_new


# ---------------------------------------------------------------------------
# causal depthwise conv1d (+ cache)
# ---------------------------------------------------------------------------
def causal_conv1d(x, w, cache=None, length=None):
    """x (B, S, C); w (K, C) depthwise. Returns (y, new_cache (B,K-1,C)).

    Implemented as K shift-and-multiply taps rather than
    ``conv_general_dilated``: a depthwise conv is opaque to the SPMD
    partitioner (its backward triggers "involuntary full rematerialization"
    — replicating the activations over the data axis and poisoning the
    sharding of everything downstream, measured at +100GiB/step of
    spurious all-reduce on the 16x16 mesh).  K static slices + FMAs are
    elementwise ops GSPMD shards perfectly, and at K=4 they cost the same
    FLOPs the conv would.

    ``length`` (B,) int32: real (unpadded) sequence lengths.  When given,
    ``new_cache`` holds the K-1 inputs *preceding position length* rather
    than the tail of the (possibly right-padded) array — required by the
    pow2-bucketed prefill, whose padded columns must not leak into the
    decode-side conv state.
    """
    K = w.shape[0]
    S = x.shape[1]
    if cache is not None:
        x_pad = jnp.concatenate([cache.astype(x.dtype), x], axis=1)
    else:
        x_pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = None
    for j in range(K):
        tap = jax.lax.slice_in_dim(x_pad, j, j + S, axis=1) \
            * w[j].astype(x.dtype)
        y = tap if y is None else y + tap
    if K <= 1:
        return y, None
    if length is None:
        return y, x_pad[:, -(K - 1):]
    # x_pad index of real position p is p + K - 1, so the tail inputs at
    # positions [length-K+1, length-1] sit at x_pad[length .. length+K-2]
    idx = length[:, None] + jnp.arange(K - 1)[None, :]
    new_cache = jnp.take_along_axis(x_pad, idx[:, :, None], axis=1)
    return y, new_cache


# ---------------------------------------------------------------------------
# full Mamba-2 block (split projections; see module docstring)
# ---------------------------------------------------------------------------
def init_ssm(key, cfg: ModelConfig, dtype=jnp.float32):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    H = d_in // s.head_dim
    gn = s.n_groups * s.d_state
    ks = jax.random.split(key, 10)
    lo, hi = s.a_init_range
    A = lo + (hi - lo) * jax.random.uniform(ks[0], (H,))
    return {
        "in_z": layers.dense_init(ks[1], (d, d_in), dtype),
        "in_x": layers.dense_init(ks[2], (d, d_in), dtype),
        "in_b": layers.dense_init(ks[3], (d, gn), dtype),
        "in_c": layers.dense_init(ks[4], (d, gn), dtype),
        "in_dt": layers.dense_init(ks[5], (d, H), dtype),
        "conv_x_w": (jax.random.normal(ks[6], (s.d_conv, d_in)) /
                     math.sqrt(s.d_conv)).astype(dtype),
        "conv_x_b": jnp.zeros((d_in,), dtype),
        "conv_b_w": (jax.random.normal(ks[7], (s.d_conv, gn)) /
                     math.sqrt(s.d_conv)).astype(dtype),
        "conv_b_b": jnp.zeros((gn,), dtype),
        "conv_c_w": (jax.random.normal(ks[8], (s.d_conv, gn)) /
                     math.sqrt(s.d_conv)).astype(dtype),
        "conv_c_b": jnp.zeros((gn,), dtype),
        "A_log": jnp.log(A).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[9], (H,)) *
                    (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)))
        ).astype(jnp.float32),
        "norm": layers.init_norm("rmsnorm", d_in, dtype),
        "out_proj": layers.dense_init(ks[0], (d_in, d), dtype),
    }


def apply_ssm(params, x, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
              cache: Optional[dict] = None, build_cache: bool = False,
              pctx=None, token_mask=None):
    """x (B,S,d_model) -> (y, new_cache|None).

    cache = {"conv_x"/"conv_b"/"conv_c": (B,K-1,*), "state": (B,H,N,P)}.
    ``token_mask`` (B,S) bool, True = real token: right-padded positions
    get dt = 0 (decay 1, zero input — state passes through unchanged, the
    same trick ``ssd_chunked`` uses for its own chunk padding), and the
    conv caches are rebuilt from the true tail.
    """
    s = cfg.ssm
    cd = compute_dtype
    B, S, _ = x.shape
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    gn = s.n_groups * s.d_state
    xc = x.astype(cd)

    z = xc @ params["in_z"].astype(cd)
    xs = xc @ params["in_x"].astype(cd)
    bs = xc @ params["in_b"].astype(cd)
    cs = xc @ params["in_c"].astype(cd)
    dt = xc @ params["in_dt"].astype(cd)

    lengths = None
    if token_mask is not None and cache is None:
        lengths = token_mask.astype(jnp.int32).sum(axis=1)

    cx = cache["conv_x"] if cache is not None else None
    cb = cache["conv_b"] if cache is not None else None
    cc = cache["conv_c"] if cache is not None else None
    xs, ncx = causal_conv1d(xs, params["conv_x_w"], cache=cx,
                            length=lengths)
    bs, ncb = causal_conv1d(bs, params["conv_b_w"], cache=cb,
                            length=lengths)
    cs, ncc = causal_conv1d(cs, params["conv_c_w"], cache=cc,
                            length=lengths)
    xs = jax.nn.silu(xs + params["conv_x_b"].astype(xs.dtype))
    bs = jax.nn.silu(bs + params["conv_b_b"].astype(bs.dtype))
    cs = jax.nn.silu(cs + params["conv_c_b"].astype(cs.dtype))

    xin = xs.reshape(B, S, H, s.head_dim)
    Bm = bs.reshape(B, S, s.n_groups, s.d_state)
    Cm = cs.reshape(B, S, s.n_groups, s.d_state)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    if lengths is not None:
        dtv = jnp.where(token_mask[:, :, None], dtv, 0.0)
    A = -jnp.exp(params["A_log"])

    if cache is not None:
        y, h_new = ssd_decode_step(xin[:, 0], dtv[:, 0], A, Bm[:, 0],
                                   Cm[:, 0], cache["state"])
        y = y[:, None]
        new_cache = {"conv_x": ncx, "conv_b": ncb, "conv_c": ncc,
                     "state": h_new}
    else:
        if pctx is not None and pctx.mesh is not None:
            y, h_final = ssd_sharded(xin, dtv, A, Bm, Cm, chunk=s.chunk,
                                     mesh=pctx.mesh, dp_axes=pctx.dp_axes,
                                     tp_axis=pctx.tp_axis)
        else:
            y, h_final = ssd_chunked(xin, dtv, A, Bm, Cm, chunk=s.chunk)
        new_cache = ({"conv_x": ncx, "conv_b": ncb, "conv_c": ncc,
                      "state": h_final} if build_cache else None)

    y = y + params["D"][:, None] * xin.astype(jnp.float32)
    y = y.reshape(B, S, d_in).astype(cd)
    y = layers.apply_norm(params["norm"], y * jax.nn.silu(z), "rmsnorm",
                          cfg.norm_eps)
    out = y.astype(cd) @ params["out_proj"].astype(cd)
    return out, new_cache


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    gn = s.n_groups * s.d_state
    return {
        "conv_x": jnp.zeros((batch, s.d_conv - 1, d_in), dtype),
        "conv_b": jnp.zeros((batch, s.d_conv - 1, gn), dtype),
        "conv_c": jnp.zeros((batch, s.d_conv - 1, gn), dtype),
        "state": jnp.zeros((batch, H, s.d_state, s.head_dim), jnp.float32),
    }
