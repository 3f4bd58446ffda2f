"""Flash-attention backward Pallas kernels vs jax.grad of the oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention_bwd import flash_attention_vjp
from repro.kernels.ref import attention_ref

KEY = jax.random.PRNGKey(11)
K1, K2, K3, K4 = jax.random.split(KEY, 4)

CASES = [
    # B, S, H, K, D, causal, window
    (2, 128, 4, 2, 32, True, 0),
    (1, 128, 4, 4, 64, True, 0),      # MHA
    (1, 128, 6, 1, 32, False, 0),     # MQA, bidirectional
    (1, 256, 4, 2, 32, True, 64),     # sliding window
]


@pytest.mark.parametrize("B,S,H,K,D,causal,window", CASES)
def test_flash_bwd_matches_oracle_grads(B, S, H, K, D, causal, window):
    q = jax.random.normal(K1, (B, S, H, D))
    k = jax.random.normal(K2, (B, S, K, D))
    v = jax.random.normal(K3, (B, S, K, D))
    ct = jax.random.normal(K4, (B, S, H, D))   # upstream cotangent

    def loss_kernel(q, k, v):
        out = flash_attention_vjp(q, k, v, causal, window, 0.0, 64, 64,
                                  True)
        return jnp.sum(out * ct)

    def loss_oracle(q, k, v):
        out = attention_ref(q, k, v, causal=causal, window=window)
        return jnp.sum(out * ct)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, go, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                   err_msg=name)


def test_flash_bwd_forward_matches_fwd_kernel():
    from repro.kernels.flash_attention import flash_attention
    q = jax.random.normal(K1, (1, 128, 4, 32))
    k = jax.random.normal(K2, (1, 128, 2, 32))
    v = jax.random.normal(K3, (1, 128, 2, 32))
    a = flash_attention_vjp(q, k, v, True, 0, 0.0, 64, 64, True)
    b = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_flash_bwd_block_independence():
    q = jax.random.normal(K1, (1, 128, 2, 32))
    k = jax.random.normal(K2, (1, 128, 2, 32))
    v = jax.random.normal(K3, (1, 128, 2, 32))
    ct = jnp.ones((1, 128, 2, 32))

    def g(bq, bk):
        return jax.grad(lambda q: jnp.sum(
            flash_attention_vjp(q, k, v, True, 0, 0.0, bq, bk, True)
            * ct))(q)

    np.testing.assert_allclose(g(32, 64), g(128, 32), atol=5e-4, rtol=5e-4)
