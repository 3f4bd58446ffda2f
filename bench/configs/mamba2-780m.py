"""Plain float32 reference of mamba2-780m, and the weights the seed gives.

The Mamba-2 language model as published (arXiv:2405.21060, the
``mamba_ssm`` Mamba2 layer): token embedding; per layer an RMSNorm and the
Mamba-2 mixer added to the residual; a final RMSNorm and the tied
embedding as the output head.  The mixer projects to z, x, B, C and dt
(no bias), runs a causal depthwise conv (with bias) and SiLU over x, B and
C, discretises with dt = softplus(dt + dt_bias) and A = -exp(A_log), runs
the state-space model with the SSD algorithm of the paper's minimal
listing (``ssd_minimal_discrete``), adds D * x, gates with SiLU(z) before a
grouped RMSNorm (one group) and projects back.  The state-space part is
kept in float32 in the control as well; only matrix products change
precision there.  Nothing here imports the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference as R


def _dims(c):
    m = c["mamba2_layer"]
    d_in = m["expand"] * c["d_model"]
    return (c["d_model"], d_in, d_in // m["headdim"],
            m["ngroups"] * m["d_state"], m["d_conv"])


def padded_vocab(c):
    return -(-c["vocab_size"] // 128) * 128


def shapes(c):
    d, d_in, H, gn, K = _dims(c)
    L = c["n_layer"]
    return {
        "embed": (padded_vocab(c), d), "norm": (L, d),
        "in_z": (L, d, d_in), "in_x": (L, d, d_in), "in_b": (L, d, gn),
        "in_c": (L, d, gn), "in_dt": (L, d, H),
        "conv_x_w": (L, K, d_in), "conv_x_b": (L, d_in),
        "conv_b_w": (L, K, gn), "conv_b_b": (L, gn),
        "conv_c_w": (L, K, gn), "conv_c_b": (L, gn),
        "A_log": (L, H), "D": (L, H), "dt_bias": (L, H),
        "gate_norm": (L, d_in), "out_proj": (L, d_in, d),
        "final_norm": (d,),
    }


def init(key, c, dtype=jnp.float32):
    """The weights for the seed's ``key``, in ``dtype``: see ``assumed``
    in the configuration file."""
    sh = shapes(c)
    m = c["mamba2_layer"]
    keys = dict(zip(sorted(sh), jax.random.split(key, len(sh))))
    d, d_in = c["d_model"], _dims(c)[1]

    def tn(name, fan_in):
        return (jax.random.truncated_normal(keys[name], -2.0, 2.0, sh[name])
                / math.sqrt(fan_in)).astype(dtype)

    p = {n: jnp.ones(sh[n], dtype) for n in
         ("norm", "gate_norm", "final_norm", "D")}
    p["embed"] = (jax.random.normal(keys["embed"], sh["embed"])
                  * 0.02).astype(dtype)
    for n in ("in_z", "in_x", "in_b", "in_c", "in_dt"):
        p[n] = tn(n, d)
    p["out_proj"] = tn("out_proj", d_in)
    for n in ("conv_x", "conv_b", "conv_c"):
        p[n + "_w"] = (jax.random.normal(keys[n + "_w"], sh[n + "_w"])
                       / math.sqrt(m["d_conv"])).astype(dtype)
        p[n + "_b"] = jnp.zeros(sh[n + "_b"], dtype)
    lo, hi = m["A_init_range"]
    p["A_log"] = jnp.log(jax.random.uniform(
        keys["A_log"], sh["A_log"], minval=lo, maxval=hi)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(keys["dt_bias"], sh["dt_bias"])
                 * (math.log(m["dt_max"]) - math.log(m["dt_min"]))
                 + math.log(m["dt_min"]))
    p["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return p


def segsum(x):
    """Stable segment sum: out[..., i, j] = sum(x[..., j+1:i+1]) for
    j <= i, -inf above the diagonal."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))
    below = jnp.tril(jnp.ones((T, T), bool), -1)
    xx = jnp.where(below, xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def ssd(X, A, B, C, chunk):
    """The paper's ``ssd_minimal_discrete``: X (b, l, h, p) = x * dt,
    A (b, l, h) = A * dt, B and C (b, l, h, n).  Zero initial state."""
    b, l, h, p = X.shape
    c = l // chunk
    X, B, C = (t.reshape(b, c, chunk, *t.shape[2:]) for t in (X, B, C))
    A = A.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)      # (b, h, c, l)
    A_cum = jnp.cumsum(A, -1)
    L = jnp.exp(segsum(A))                                    # (b,h,c,l,s)
    CB = jnp.einsum("bclhn,bcshn->bhcls", C, B, precision=R.HIGHEST)
    Y_diag = jnp.einsum("bhcls,bcshp->bclhp", CB * L, X,
                        precision=R.HIGHEST)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)          # (b, h, c, l)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", B, decay_states, X,
                        precision=R.HIGHEST)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                            precision=R.HIGHEST)
    states = new_states[:, :-1]
    Y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cum),
                       precision=R.HIGHEST)
    return (Y_diag + Y_off).reshape(b, l, h, p)


def causal_conv(x, w, bias):
    """Depthwise causal conv over the sequence: x (B, S, C), w (K, C)."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + S] * w[j].astype(jnp.float32) for j in range(K))
    return y + bias.astype(jnp.float32)


PER_LAYER = ("norm", "in_z", "in_x", "in_b", "in_c", "in_dt", "conv_x_w",
             "conv_x_b", "conv_b_w", "conv_b_b", "conv_c_w", "conv_c_b",
             "A_log", "D", "dt_bias", "gate_norm", "out_proj")


def hidden(p, tokens, c, mm):
    """Final-normed hidden states (B, S, d) in float32 for ``tokens``."""
    m = c["mamba2_layer"]
    eps = m["rmsnorm_eps"]
    d, d_in, H, gn, _ = _dims(c)
    B_, S = tokens.shape
    G, N, P = m["ngroups"], m["d_state"], m["headdim"]
    x = p["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        h = R.rmsnorm(x, lp["norm"], eps)
        z = mm("bsd,de->bse", h, lp["in_z"])
        xs = jax.nn.silu(causal_conv(mm("bsd,de->bse", h, lp["in_x"]),
                                     lp["conv_x_w"], lp["conv_x_b"]))
        bs = jax.nn.silu(causal_conv(mm("bsd,de->bse", h, lp["in_b"]),
                                     lp["conv_b_w"], lp["conv_b_b"]))
        cs = jax.nn.silu(causal_conv(mm("bsd,de->bse", h, lp["in_c"]),
                                     lp["conv_c_w"], lp["conv_c_b"]))
        dt = jax.nn.softplus(mm("bsd,dh->bsh", h, lp["in_dt"])
                             + lp["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(lp["A_log"].astype(jnp.float32))
        xh = xs.reshape(B_, S, H, P)
        rep = lambda t: jnp.repeat(t.reshape(B_, S, G, N), H // G, axis=2)
        y = ssd(xh * dt[..., None], A * dt, rep(bs), rep(cs),
                m["chunk_size"])
        y = y + lp["D"].astype(jnp.float32)[:, None] * xh
        y = R.rmsnorm(y.reshape(B_, S, d_in) * jax.nn.silu(z),
                      lp["gate_norm"], eps)
        return x + mm("bse,ed->bsd", y, lp["out_proj"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x,
                        {n: p[n] for n in PER_LAYER})
    return R.rmsnorm(x, p["final_norm"], eps)
