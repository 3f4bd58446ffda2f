"""Composable decoder stacks.

A model's ``block_pattern`` (e.g. Griffin's (rglru, rglru, attn_local)
repeating) is compiled into *segments*: the smallest repeating unit is
``lax.scan``-ned over its repeat count (keeping HLO size ~O(unit), essential
for 48-layer models), and any remainder prefix becomes a second short
segment. Per-slot parameters are stacked along a leading layer axis.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ATTN_LOCAL, RGLRU, SSM, ModelConfig)
from repro.models import attention, layers, moe, rglru, ssm


# ---------------------------------------------------------------------------
# run context (how to execute; orthogonal to the params)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Mesh-aware execution context (None mesh = single device)."""
    mesh: Any = None
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: Optional[str] = "model"
    fsdp_experts: bool = True


@dataclasses.dataclass(frozen=True)
class RunCtx:
    compute_dtype: Any = jnp.bfloat16
    attn_impl: str = "xla"            # xla | full
    attn_blocks: Tuple[int, int] = (512, 512)
    moe_impl: str = "sorted"          # dense | sorted | ep
    moe_capacity: Optional[int] = None
    remat: str = "block"              # none | block
    cache_capacity: int = 0
    pctx: ParallelCtx = ParallelCtx()


# ---------------------------------------------------------------------------
# segment planning
# ---------------------------------------------------------------------------
def plan_segments(pattern: Sequence[str]) -> List[Tuple[Tuple[str, ...], int]]:
    """[(unit, repeats), ...] — unit*repeats (+ prefix remainder) == pattern."""
    pattern = tuple(pattern)
    L = len(pattern)
    for u in range(1, L + 1):
        unit = pattern[:u]
        k = L // u
        if unit * k == pattern[:u * k] and pattern[u * k:] == unit[:L - u * k]:
            segs = [(unit, k)]
            rem = pattern[u * k:]
            if rem:
                segs.append((rem, 1))
            return segs
    raise AssertionError("unreachable")


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------
def _has_ffn(cfg: ModelConfig) -> bool:
    return cfg.d_ff > 0 or cfg.moe is not None


def init_block(key, cfg: ModelConfig, blk: str, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": layers.init_norm(cfg.norm, cfg.d_model, dtype)}
    if blk in (ATTN, ATTN_LOCAL):
        p["attn"] = attention.init_attention(ks[0], cfg, dtype)
    elif blk == SSM:
        p["ssm"] = ssm.init_ssm(ks[0], cfg, dtype)
    elif blk == RGLRU:
        p["rglru"] = rglru.init_rglru(ks[0], cfg, dtype)
    else:
        raise ValueError(blk)
    if _has_ffn(cfg):
        if not cfg.parallel_residual:
            p["norm2"] = layers.init_norm(cfg.norm, cfg.d_model, dtype)
        if cfg.moe is not None:
            p["moe"] = moe.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff,
                                       cfg.act, dtype)
    return p


def apply_block(p, x, blk: str, cfg: ModelConfig, ctx: RunCtx, *,
                positions, cache=None, kv_mask=None):
    """Returns (x, new_cache, aux)."""
    cd = ctx.compute_dtype
    h = layers.apply_norm(p["norm1"], x, cfg.norm, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)

    if blk in (ATTN, ATTN_LOCAL):
        has_mesh = ctx.pctx.mesh is not None
        batch_axes = tuple(ctx.pctx.dp_axes) if has_mesh else ()
        tp = ctx.pctx.tp_axis
        head_axis = (tp if has_mesh and tp in getattr(
            ctx.pctx.mesh, "shape", {})
            and cfg.n_heads % ctx.pctx.mesh.shape[tp] == 0 else None)
        mix, new_cache = attention.apply_attention(
            p["attn"], h, cfg, local=(blk == ATTN_LOCAL),
            positions=positions, compute_dtype=cd,
            impl=("full" if ctx.attn_impl == "full" else "xla"),
            cache=cache, blocks=ctx.attn_blocks, kv_mask=kv_mask,
            cache_capacity=ctx.cache_capacity, batch_axes=batch_axes,
            head_axis=head_axis, mesh=ctx.pctx.mesh,
            tp_axis=ctx.pctx.tp_axis)
    elif blk == SSM:
        mix, new_cache = ssm.apply_ssm(
            p["ssm"], h, cfg, compute_dtype=cd, cache=(
                cache if isinstance(cache, dict) else None),
            build_cache=(cache == "init"), pctx=ctx.pctx,
            token_mask=kv_mask)
    elif blk == RGLRU:
        has_mesh = ctx.pctx.mesh is not None
        mix, new_cache = rglru.apply_rglru(
            p["rglru"], h, cfg, compute_dtype=cd, cache=(
                cache if isinstance(cache, dict) else None),
            build_cache=(cache == "init"),
            batch_axes=(tuple(ctx.pctx.dp_axes) if has_mesh else ()),
            model_axis=(ctx.pctx.tp_axis if has_mesh else None),
            token_mask=kv_mask)
    else:
        raise ValueError(blk)

    if not _has_ffn(cfg):
        return x + mix.astype(x.dtype), new_cache, aux

    if cfg.parallel_residual:
        if cfg.moe is not None:
            f, aux = moe.apply_moe(p["moe"], h, cfg, compute_dtype=cd,
                                   impl=ctx.moe_impl, pctx=ctx.pctx,
                                   capacity=ctx.moe_capacity)
        else:
            f = layers.apply_mlp(p["mlp"], h, cfg.act, cd)
        return x + (mix + f).astype(x.dtype), new_cache, aux

    x = x + mix.astype(x.dtype)
    h2 = layers.apply_norm(p["norm2"], x, cfg.norm, cfg.norm_eps)
    if cfg.moe is not None:
        f, aux = moe.apply_moe(p["moe"], h2, cfg, compute_dtype=cd,
                               impl=ctx.moe_impl, pctx=ctx.pctx,
                               capacity=ctx.moe_capacity)
    else:
        f = layers.apply_mlp(p["mlp"], h2, cfg.act, cd)
    return x + f.astype(x.dtype), new_cache, aux


# ---------------------------------------------------------------------------
# cache scaffolding
# ---------------------------------------------------------------------------
def init_block_cache(cfg: ModelConfig, blk: str, batch: int, max_seq: int,
                     dtype=jnp.bfloat16):
    if blk in (ATTN, ATTN_LOCAL):
        return attention.init_decode_cache(
            cfg, batch, max_seq, local=(blk == ATTN_LOCAL), dtype=dtype)
    if blk == SSM:
        return ssm.init_ssm_cache(cfg, batch)
    if blk == RGLRU:
        return rglru.init_rglru_cache(cfg, batch)
    raise ValueError(blk)


def init_stack_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     dtype=jnp.bfloat16):
    """Caches stacked to mirror the segment structure of the params."""
    caches = {}
    for si, (unit, k) in enumerate(plan_segments(cfg.pattern)):
        seg = {}
        for slot, blk in enumerate(unit):
            one = init_block_cache(cfg, blk, batch, max_seq, dtype)
            seg[f"slot{slot}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (k,) + a.shape)
                if k > 1 else a, one)
        caches[f"seg{si}"] = seg
    return caches


# ---------------------------------------------------------------------------
# stack init / apply
# ---------------------------------------------------------------------------
def init_stack(key, cfg: ModelConfig, dtype=jnp.float32):
    params = {}
    segs = plan_segments(cfg.pattern)
    keys = jax.random.split(key, len(segs))
    for si, (unit, k) in enumerate(segs):
        seg_p = {}
        slot_keys = jax.random.split(keys[si], len(unit))
        for slot, blk in enumerate(unit):
            lkeys = jax.random.split(slot_keys[slot], k)
            per_layer = [init_block(lkeys[i], cfg, blk, dtype)
                         for i in range(k)]
            seg_p[f"slot{slot}"] = (
                jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
                if k > 1 else per_layer[0])
        params[f"seg{si}"] = seg_p
    return params


def apply_stack(params, x, cfg: ModelConfig, ctx: RunCtx, *,
                positions, caches=None, kv_mask=None):
    """Returns (x, new_caches|None, aux_sum).

    ``caches``: None (training), "init" (prefill -> build caches), or the
    stacked cache pytree (decode).
    """
    segs = plan_segments(cfg.pattern)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Optional[Dict[str, Any]] = None if caches is None else {}

    for si, (unit, k) in enumerate(segs):
        seg_p = params[f"seg{si}"]
        seg_c = None
        if isinstance(caches, dict):
            seg_c = caches[f"seg{si}"]

        def unit_body(x_aux, slot_params_caches, unit=unit):
            xx, aux = x_aux
            slot_p, slot_c = slot_params_caches
            out_caches = {}
            for slot, blk in enumerate(unit):
                c_in = (slot_c[f"slot{slot}"] if slot_c is not None
                        else ("init" if caches == "init" else None))
                xx, nc, a = apply_block(
                    slot_p[f"slot{slot}"], xx, blk, cfg, ctx,
                    positions=positions, cache=c_in, kv_mask=kv_mask)
                if nc is not None:
                    out_caches[f"slot{slot}"] = nc
                aux = aux + a
            return (xx, aux), (out_caches if out_caches else None)

        body = unit_body
        if ctx.remat == "block":
            body = jax.checkpoint(unit_body, prevent_cse=False)

        if k == 1:
            (x, aux_total), seg_new_c = body(
                (x, aux_total), (seg_p, seg_c))
        else:
            def scan_body(carry, xs):
                return body(carry, xs)
            (x, aux_total), seg_new_c = jax.lax.scan(
                scan_body, (x, aux_total), (seg_p, seg_c))
        if new_caches is not None and seg_new_c is not None:
            new_caches[f"seg{si}"] = seg_new_c

    return x, new_caches, aux_total
