"""Plain float32 reference of qwen2-0.5b, and the weights the seed gives.

The Qwen2 decoder as published (arXiv:2407.10671, the Hugging Face
``Qwen2ForCausalLM``): token embedding; per layer an RMSNorm, grouped-query
attention with biased q/k/v projections and rotary positions (rotate-half,
theta from the file), an RMSNorm and a SwiGLU MLP, each added to the
residual; a final RMSNorm and the tied embedding as the output head.
Per-layer weights are stacked along a leading layer axis.  Nothing here
imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference as R


def shapes(c):
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd, F, V = d // H, c["intermediate_size"], c["vocab_size"]
    return {
        "embed": (V, d), "attn_norm": (L, d),
        "wq": (L, d, H, hd), "wk": (L, d, K, hd), "wv": (L, d, K, hd),
        "bq": (L, H, hd), "bk": (L, K, hd), "bv": (L, K, hd),
        "wo": (L, H, hd, d), "mlp_norm": (L, d),
        "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d),
        "final_norm": (d,),
    }


def init(key, c, dtype=jnp.float32):
    """The weights for the seed's ``key``, in ``dtype``: see ``assumed``
    in the configuration file."""
    sh = shapes(c)
    keys = dict(zip(sorted(sh), jax.random.split(key, len(sh))))
    d = c["hidden_size"]
    H = c["num_attention_heads"]

    def tn(name, fan_in):
        return (jax.random.truncated_normal(keys[name], -2.0, 2.0, sh[name])
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    def normal(name, std):
        return (jax.random.normal(keys[name], sh[name]) * std).astype(dtype)

    p = {n: jnp.ones(sh[n], dtype) for n in
         ("attn_norm", "mlp_norm", "final_norm")}
    p["embed"] = normal("embed", 0.02)
    for n in ("bq", "bk", "bv"):
        p[n] = normal(n, 0.02)
    for n in ("wq", "wk", "wv", "w_gate", "w_up"):
        p[n] = tn(n, d)
    p["wo"] = tn("wo", H * (d // H))
    p["w_down"] = tn("w_down", c["intermediate_size"])
    return p


PER_LAYER = ("attn_norm", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
             "mlp_norm", "w_gate", "w_up", "w_down")


def hidden(p, tokens, c, mm):
    """Final-normed hidden states (B, S, d) in float32 for ``tokens``."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = p["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        h = R.rmsnorm(x, lp["attn_norm"], eps)
        q = mm("bsd,dhe->bshe", h, lp["wq"]) + lp["bq"].astype(jnp.float32)
        k = mm("bsd,dke->bske", h, lp["wk"]) + lp["bk"].astype(jnp.float32)
        v = mm("bsd,dke->bske", h, lp["wv"]) + lp["bv"].astype(jnp.float32)
        q, k = R.rope(q, pos, theta), R.rope(k, pos, theta)
        a = R.causal_attention(q, k, v, mm)
        x = x + mm("bshe,hed->bsd", a, lp["wo"])
        h = R.rmsnorm(x, lp["mlp_norm"], eps)
        f = jax.nn.silu(mm("bsd,df->bsf", h, lp["w_gate"])) \
            * mm("bsd,df->bsf", h, lp["w_up"])
        return x + mm("bsf,fd->bsd", f, lp["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x,
                        {n: p[n] for n in PER_LAYER})
    return R.rmsnorm(x, p["final_norm"], eps)
