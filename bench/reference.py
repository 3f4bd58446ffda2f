"""Plain float32 pieces shared by the configurations' references.

Nothing here imports the program.  Matrix products go through an ``mm``
function so that one model definition serves both the reference
(float32 at ``highest`` precision) and its control (the same model with
every matrix product in float8, per-tensor scaled, as fp8 training runs
it: the step below the bfloat16 that the configurations state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(spec, x, w):
    return jnp.einsum(spec, x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def to_fp8(a, dtype=jnp.float8_e4m3fn):
    """Round ``a`` to float8 with one scale per tensor (amax over the
    format's largest finite value)."""
    a = a.astype(jnp.float32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(a)))
    top = float(jnp.finfo(dtype).max)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def mm_fp8(spec, x, w):
    """A matrix product in float8 as fp8 training does it: operands in
    e4m3, the incoming gradient in e5m2, each tensor scaled by its own
    amax; products accumulate in float32."""
    return jnp.einsum(spec, to_fp8(x), to_fp8(w), precision=HIGHEST)


def _mm_fp8_fwd(spec, x, w):
    xq, wq = to_fp8(x), to_fp8(w)
    return jnp.einsum(spec, xq, wq, precision=HIGHEST), (xq, wq)


def _mm_fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST),
                     *res)
    return vjp(to_fp8(g, jnp.float8_e5m2))


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


MM = {"float32": mm_f32, "float8": mm_fp8}


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding, rotate-half layout: x (B, S, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, :, None].astype(jnp.float32) * inv     # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, mm, block=512):
    """Causal grouped-query attention, one block of queries at a time.
    q (B, S, H, D); k, v (B, S, K, D) with H a multiple of K."""
    B, S, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    block = min(block, S)
    assert S % block == 0, (S, block)
    pos = jnp.arange(S)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 1)
        s = mm("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(D))
        qpos = i * block + jnp.arange(block)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(S // block))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def mean_xent(hidden, table, labels, mm, chunk=1024):
    """Mean next-token cross entropy over all (B*S) positions, the
    logits made ``chunk`` rows at a time."""
    D = hidden.shape[-1]
    h = hidden.reshape(-1, D)
    lab = labels.reshape(-1)
    T = h.shape[0]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)

    def body(tot, xs):
        hc, lc = xs
        logits = mm("td,vd->tv", hc, table)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lc[:, None], -1)[:, 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                          (h.reshape(-1, chunk, D), lab.reshape(-1, chunk)))
    return tot / T


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_train_steps(model, c, hp, mm, shardings=None):
    """One jitted AdamW step of the plain model: decoupled weight decay on
    every leaf of two or more dimensions, global-norm clipping, bias
    correction, constant learning rate; ``hp`` as the traffic file
    states it.  Returns ``step(params, m, v, batch, t) -> (params, m, v,
    loss, clipped-gradient leaf norms)``."""
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    lr, wd, clip = hp["lr"], hp["weight_decay"], hp["grad_clip"]

    def loss_fn(params, batch):
        h = model.hidden(params, batch["inputs"], c, mm)
        return mean_xent(h, params["embed"], batch["labels"], mm)

    def step(params, m, v, batch, t):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        g = {k: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
             for k, x in g.items()}
        tf = t.astype(jnp.float32)
        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            new_m[k] = b1 * m[k] + (1 - b1) * g[k]
            new_v[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
            upd = (new_m[k] / (1 - b1 ** tf)) / (
                jnp.sqrt(new_v[k] / (1 - b2 ** tf)) + eps)
            decay = wd * params[k] if params[k].ndim >= 2 else 0.0
            new_p[k] = params[k] - lr * (upd + decay)
        return new_p, new_m, new_v, loss, leaf_norms(g)

    if shardings is None:
        return jax.jit(step, donate_argnums=(0, 1, 2))
    ps, bs = shardings
    return jax.jit(step, in_shardings=(ps, ps, ps, bs, None),
                   out_shardings=(ps, ps, ps, None, None),
                   donate_argnums=(0, 1, 2))


def train_readings(model, c, hp, seed_key, batches, mm, dtype=jnp.float32,
                   shardings=None):
    """The plain model's first ``len(batches)`` training steps from the
    weights the seed gives: each step's loss, the first clipped gradient's
    leaf norms, and the leaf norms of the weights' change over the steps."""
    ps = shardings[0] if shardings else None
    init = jax.jit(lambda k: model.init(k, c, dtype), out_shardings=ps)
    params = init(seed_key)
    zeros = jax.jit(lambda p: {k: jnp.zeros_like(x) for k, x in p.items()},
                    out_shardings=ps)
    m, v = zeros(params), zeros(params)
    step = make_train_steps(model, c, hp, mm, shardings)
    losses, grad1 = [], None
    for t, batch in enumerate(batches, start=1):
        params, m, v, loss, gnorms = step(params, m, v, batch,
                                          jnp.int32(t))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = {k: float(x) for k, x in gnorms.items()}
    del m, v
    delta = jax.jit(lambda p, k: leaf_norms(
        {n: p[n] - w for n, w in model.init(k, c, dtype).items()}))(
            params, seed_key)
    return {"loss": losses, "grad1": grad1,
            "delta": {k: float(x) for k, x in delta.items()}}


def make_served_logits(model, c, mm, n_out):
    """Jitted ``f(params, tokens, start) -> (n_out, V)`` float32 logits of
    the plain model at positions ``start - 1 ..  start + n_out - 2`` of
    ``tokens`` (the positions that predicted the served tokens
    ``tokens[start:]``).  ``tokens`` may be padded at its end: the model
    is causal, so the padding changes no position before it."""
    def f(params, tokens, start):
        h = model.hidden(params, tokens[None], c, mm)[0]
        h = jax.lax.dynamic_slice_in_dim(h, start - 1, n_out, 0)
        return mm("td,vd->tv", h, params["embed"])
    return jax.jit(f)
