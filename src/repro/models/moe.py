"""Mixture-of-Experts FFN.

Three execution paths:
  * ``dense``  — computes every expert for every token, weighted by gates.
                 O(E) FLOPs; the numerical oracle for tests and tiny configs.
  * ``sorted`` — dropless-with-capacity sort-based dispatch (MegaBlocks-style
                 gather/scatter, no one-hot matmuls).  Runs per data shard
                 with expert weights gathered (the paper's "ZeRO-3 sharded
                 training" baseline: parameters sharded, gathered per layer).
  * ``ep``     — expert parallelism via ``shard_map`` over the model axis:
                 expert weights stay sharded (E over model, d over data);
                 every model rank computes its local experts for the data
                 shard's tokens and partial outputs are psum-combined.
                 (beyond-paper optimization; see EXPERIMENTS.md §Perf).

Shared experts are fused into one wide MLP (a sum of independent MLPs is
exactly a block-diagonal wide MLP).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (d, m.n_experts), jnp.float32),
        "wi": layers.dense_init(ks[1], (m.n_experts, d, m.d_ff_expert), dtype),
        "wg": layers.dense_init(ks[2], (m.n_experts, d, m.d_ff_expert), dtype),
        "wo": layers.dense_init(ks[3], (m.n_experts, m.d_ff_expert, d), dtype),
    }
    if m.n_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], d, m.n_shared_experts * m.d_ff_shared, cfg.act, dtype)
    return p


def route(x2d, router_w, top_k: int):
    """x2d (T, d) -> gates (T, k) fp32 (renormalized), idx (T, k) int32."""
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # aux load-balance loss (Switch): E * sum_e f_e * p_e
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, idx.astype(jnp.int32), aux


def _expert_ffn(xe, wi, wg, wo, act: str):
    """xe (E, C, d); weights (E, d, f)/(E, f, d) -> (E, C, d)."""
    h = jnp.einsum("ecd,edf->ecf", xe, wi)
    if act == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xe, wg)
    elif act == "geglu":
        h = jax.nn.gelu(h) * jnp.einsum("ecd,edf->ecf", xe, wg)
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def default_capacity(T: int, E: int, k: int, cf: float) -> int:
    c = int(math.ceil(T * k / E * cf))
    return max(4, min(T, c))


def moe_sorted(params, x2d, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
               capacity: Optional[int] = None, expert_slice=None):
    """Sort-based dropless-with-capacity dispatch on one token shard.

    ``expert_slice``: optional (start, count) restricting computation to a
    contiguous expert range (used by the EP path); tokens routed to other
    experts contribute zero here.
    """
    m = cfg.moe
    cd = compute_dtype
    T, d = x2d.shape
    E, k = m.n_experts, m.top_k
    gates, idx, aux = route(x2d, params["router"], k)

    C = capacity if capacity is not None else default_capacity(
        T, E, k, m.capacity_factor)

    eid = idx.reshape(-1)                       # (T*k,)
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    gv = gates.reshape(-1)

    order = jnp.argsort(eid)                    # stable
    eid_s, tid_s, gv_s = eid[order], tid[order], gv[order]
    counts = jnp.zeros((E,), jnp.int32).at[eid_s].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k, dtype=jnp.int32) - offsets[eid_s]
    keep = pos < C

    if expert_slice is not None:
        e0, en = expert_slice
        if params["wi"].shape[0] == en:
            # weights are already the local [e0, e0+en) slice (EP shard)
            wi, wg, wo = params["wi"], params["wg"], params["wo"]
        else:
            wi = jax.lax.dynamic_slice_in_dim(params["wi"], e0, en, 0)
            wg = jax.lax.dynamic_slice_in_dim(params["wg"], e0, en, 0)
            wo = jax.lax.dynamic_slice_in_dim(params["wo"], e0, en, 0)
        keep = keep & (eid_s >= e0) & (eid_s < e0 + en)
        erow = eid_s - e0
        n_local = en
    else:
        wi, wg, wo = params["wi"], params["wg"], params["wo"]
        erow = eid_s
        n_local = E

    safe_e = jnp.where(keep, erow, 0)
    safe_p = jnp.where(keep, pos, C)            # C -> dropped (mode="drop")
    xe = jnp.zeros((n_local, C, d), cd).at[safe_e, safe_p].set(
        x2d[tid_s].astype(cd) * keep[:, None].astype(cd), mode="drop")
    ye = _expert_ffn(xe, wi.astype(cd), wg.astype(cd), wo.astype(cd), cfg.act)
    contrib = ye[safe_e, jnp.minimum(safe_p, C - 1)] * \
        (gv_s * keep.astype(jnp.float32))[:, None].astype(cd)
    y = jnp.zeros((T, d), cd).at[tid_s].add(contrib)
    return y, aux


def moe_dense(params, x2d, cfg: ModelConfig, compute_dtype=jnp.bfloat16):
    """Oracle: all experts for all tokens, gate-weighted."""
    m = cfg.moe
    cd = compute_dtype
    gates, idx, aux = route(x2d, params["router"], m.top_k)
    full_gates = jnp.zeros((x2d.shape[0], m.n_experts), jnp.float32)
    full_gates = full_gates.at[
        jnp.arange(x2d.shape[0])[:, None], idx].add(gates)
    xe = jnp.broadcast_to(x2d.astype(cd)[None],
                          (m.n_experts,) + x2d.shape)
    ye = _expert_ffn(xe, params["wi"].astype(cd), params["wg"].astype(cd),
                     params["wo"].astype(cd), cfg.act)   # (E, T, d)
    y = jnp.einsum("etd,te->td", ye, full_gates.astype(cd))
    return y, aux


def apply_moe(params, x, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
              impl: str = "sorted", pctx=None, capacity: Optional[int] = None):
    """x (B, S, d) -> (B, S, d). Adds shared-expert path if configured."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    if impl == "dense":
        y2d, aux = moe_dense(params, x2d, cfg, compute_dtype)
    elif impl == "ep" and pctx is not None and pctx.mesh is not None:
        y2d, aux = _moe_ep(params, x2d, cfg, compute_dtype, pctx, capacity)
    else:
        y2d, aux = moe_sorted(params, x2d, cfg, compute_dtype=compute_dtype,
                              capacity=capacity)
    y = y2d.reshape(B, S, d)
    if cfg.moe.n_shared_experts:
        y = y + layers.apply_mlp(params["shared"], x, cfg.act, compute_dtype)
    return y, aux


# ---------------------------------------------------------------------------
# expert parallelism (shard_map over the model/tp axis)
# ---------------------------------------------------------------------------
def _moe_ep(params, x2d, cfg, compute_dtype, pctx, capacity):
    """EP: experts sharded over ``pctx.tp_axis``; tokens replicated over it.

    Every model rank computes its E/n_tp local experts for the data shard's
    tokens; partial outputs psum over the tp axis. Expert weights may carry
    an extra FSDP sharding over the data axes (gathered inside).
    """
    from jax.sharding import PartitionSpec as P
    mesh = pctx.mesh
    tp = pctx.tp_axis
    n_tp = mesh.shape[tp]
    m = cfg.moe
    assert m.n_experts % n_tp == 0, (m.n_experts, n_tp)
    e_local = m.n_experts // n_tp

    dp = tuple(pctx.dp_axes)
    n_dp_total = 1
    for a in dp:
        n_dp_total *= mesh.shape[a]

    def _fsdp_dim(shape):
        """Mirror core.policy: FSDP-shard the largest divisible non-E dim."""
        cands = [(shape[d], d) for d in (1, 2)
                 if shape[d] % n_dp_total == 0 and shape[d] >= n_dp_total]
        return max(cands)[1] if cands else None

    dims = {k: (_fsdp_dim(params[k].shape) if pctx.fsdp_experts else None)
            for k in ("wi", "wg", "wo")}

    def w_sp(k):
        ent = [tp, None, None]
        if dims[k] is not None:
            ent[dims[k]] = dp if len(dp) > 1 else dp[0]
        return P(*ent)

    x_spec = P(dp)           # (T, d): T sharded over dp, replicated over tp
    w_spec = {"router": P(), "wi": w_sp("wi"), "wg": w_sp("wg"),
              "wo": w_sp("wo")}
    eparams = {k: params[k] for k in ("router", "wi", "wg", "wo")}

    def body(ep, xs):
        gathered = {}
        for k in ("wi", "wg", "wo"):
            w = ep[k]
            if dims[k] is not None:
                w = jax.lax.all_gather(w, dp, axis=dims[k], tiled=True)
            gathered[k] = w
        ep = dict(ep, **gathered)
        rank = jax.lax.axis_index(tp)
        T = xs.shape[0]
        cap = capacity if capacity is not None else default_capacity(
            T, m.n_experts, m.top_k, m.capacity_factor)
        y, aux = moe_sorted(
            ep, xs, cfg, compute_dtype=compute_dtype, capacity=cap,
            expert_slice=(rank * e_local, e_local))
        y = jax.lax.psum(y, tp)
        # aux varies over dp shards and is duplicated over tp: global mean.
        n_dp = 1
        for a in dp:
            n_dp *= mesh.shape[a]
        aux = jax.lax.psum(aux, (tp,) + dp) / (n_tp * n_dp)
        return y, aux

    # inside a manual-axis region (the compressed pod exchange) the mesh
    # argument must be omitted so the context mesh (with its Manual axes)
    # is used; manualize only the axes this shard_map owns.
    kwargs = dict(in_specs=(w_spec, x_spec), out_specs=(x_spec, P()),
                  check_vma=False)
    already = layers.manual_axes()
    if already:
        own = frozenset(dp + (tp,)) - already
        return jax.shard_map(body, axis_names=own, **kwargs)(eparams, x2d)
    return jax.shard_map(body, mesh=mesh, **kwargs)(eparams, x2d)
