"""Pure-jnp oracles for the Pallas kernels (the allclose targets).

These delegate to the model-zoo reference implementations so the kernels
are validated against exactly the math the framework trains/serves with.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.attention import decode_attention, full_attention


def attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0):
    """q (B,S,H,D); k/v (B,T,K,D) -> (B,S,H,D)."""
    return full_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap)


def paged_attention_ref(q, k_pages, v_pages, tables, lengths, *,
                        softcap=0.0):
    """Dense oracle for the paged decode kernel: gather the block tables
    into the dense ``(B, T, K, D)`` cache view (exactly what the serving
    engine's XLA path materializes), then run the model zoo's
    ``decode_attention`` with the positional mask the pool maintains.

    q (B,H,D); k/v pages (N,ps,K,D); tables (B,P) int32; lengths (B,)
    valid-token counts -> (B,H,D).
    """
    B = q.shape[0]
    ps = k_pages.shape[1]
    P = tables.shape[1]
    k = k_pages[tables].reshape((B, P * ps) + k_pages.shape[2:])
    v = v_pages[tables].reshape((B, P * ps) + v_pages.shape[2:])
    t = jnp.arange(P * ps, dtype=jnp.int32)[None, :]
    cache_pos = jnp.where(t < lengths[:, None], t, -1)
    return decode_attention(q[:, None], k, v, cache_pos,
                            softcap=softcap)[:, 0]
