"""LM wrapper: embeddings -> stack -> final norm -> head (+ losses).

Handles the three input modes of the assigned pool:
  * tokens       — usual LM (int32 token ids)
  * embeddings   — VLM/audio stubs: ``input_specs()`` feeds precomputed
                   patch/frame embeddings (B, S, d_model) straight to the
                   stack (the modality frontend is out of scope per the
                   assignment); labels remain token ids for the LM head.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, scopes, transformer
from repro.models.transformer import RunCtx


def init_lm(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "embed": layers.init_embedding(ks[0], cfg.padded_vocab, cfg.d_model,
                                       dtype),
        "stack": transformer.init_stack(ks[1], cfg, dtype),
        "final_norm": layers.init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = {"table": layers.embed_init(
            ks[2], (cfg.padded_vocab, cfg.d_model), dtype)}
    if cfg.pos_embedding == "learned":
        p["pos_embed"] = layers.embed_init(ks[3], (cfg.max_seq, cfg.d_model),
                                           dtype)
    return p


@scopes.scoped(scopes.EMBED)
def embed_inputs(params, inputs, cfg: ModelConfig, ctx: RunCtx, positions):
    cd = ctx.compute_dtype
    if cfg.input_mode == "embeddings":
        x = inputs.astype(cd)
    else:
        x = layers.embed_tokens(params["embed"], inputs, cd)
    if cfg.pos_embedding == "sinusoidal":
        x = x + layers.sinusoidal_positions(positions, cfg.d_model, cd)
    elif cfg.pos_embedding == "learned":
        x = x + params["pos_embed"].astype(cd)[positions]
    return x


def head_table(params, cfg: ModelConfig):
    return (params["embed"]["table"] if cfg.tie_embeddings
            else params["head"]["table"])


def forward(params, inputs, cfg: ModelConfig, ctx: RunCtx, *,
            positions=None, caches=None, kv_mask=None,
            return_hidden: bool = False):
    """Returns (logits_or_hidden, new_caches, aux)."""
    B = inputs.shape[0]
    S = inputs.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed_inputs(params, inputs, cfg, ctx, positions)
    x, new_caches, aux = transformer.apply_stack(
        params["stack"], x, cfg, ctx, positions=positions, caches=caches,
        kv_mask=kv_mask)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    if return_hidden:
        return x, new_caches, aux
    logits = layers.unembed(head_table(params, cfg), x, ctx.compute_dtype)
    return logits, new_caches, aux


def lm_loss(params, batch, cfg: ModelConfig, ctx: RunCtx, *,
            xent_chunk: int = 0, aux_weight: float = 0.01):
    """batch: {"inputs": tokens|embeds, "labels": (B,S) int32,
    optional "mask": (B,S)}. Returns (loss, metrics)."""
    hidden, _, aux = forward(params, batch["inputs"], cfg, ctx,
                             return_hidden=True)
    table = head_table(params, cfg)
    mask = batch.get("mask")
    with jax.named_scope(scopes.HEAD):
        if xent_chunk and hidden.shape[1] % xent_chunk == 0:
            xent = layers.chunked_softmax_xent(
                hidden, table, batch["labels"], chunk=xent_chunk,
                compute_dtype=ctx.compute_dtype, mask=mask)
        else:
            logits = layers.unembed(table, hidden, ctx.compute_dtype)
            xent = layers.softmax_xent(logits, batch["labels"], mask)
    loss = xent + aux_weight * aux
    return loss, {"loss": loss, "xent": xent, "aux": aux}
