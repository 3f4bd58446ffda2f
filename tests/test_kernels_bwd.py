"""Flash-attention backward Pallas kernels vs jax.grad of the oracle, and
which calls ``apply_attention`` sends to them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, get_config, reduced
from repro.configs.base import ATTN, ATTN_LOCAL
from repro.kernels.flash_attention_bwd import (flash_attention_vjp,
                                               kernel_blocks)
from repro.kernels.ref import attention_ref
from repro.models import attention

KEY = jax.random.PRNGKey(11)
K1, K2, K3, K4 = jax.random.split(KEY, 4)
BF16 = jnp.bfloat16

CASES = [
    # B, S, H, K, D, causal, window, softcap, dtype, (block_q, block_k)
    (2, 128, 4, 2, 32, True, 0, 0.0, jnp.float32, (64, 64)),
    (1, 128, 4, 4, 64, True, 0, 0.0, jnp.float32, (64, 64)),      # MHA
    (1, 128, 6, 1, 32, False, 0, 0.0, jnp.float32, (64, 64)),     # MQA
    (1, 256, 4, 2, 32, True, 64, 0.0, jnp.float32, (64, 64)),     # window
    (1, 128, 4, 2, 32, True, 0, 5.0, jnp.float32, (64, 64)),      # softcap
    # qwen2-0.5b's GQA ratio: the kernels' own blocks, and several tiles
    # per row with a diagonal that cuts through tiles
    (1, 512, 14, 2, 64, True, 0, 0.0, BF16, kernel_blocks(7, 64)),
    (2, 256, 14, 2, 64, True, 0, 0.0, BF16, (32, 128)),
]
# bf16 MXU operands (P, dO, V, dS, Q, K) round to 8 bits of mantissa: a
# normwise error of a few 2^-8, against 1e-6 for f32 operands
TOL = {jnp.float32: 5e-4, BF16: 2e-2}


@pytest.mark.parametrize("B,S,H,K,D,causal,window,softcap,dtype,blocks",
                         CASES)
def test_flash_bwd_matches_oracle_grads(B, S, H, K, D, causal, window,
                                        softcap, dtype, blocks):
    q, k, v, ct = (jax.random.normal(key, shape).astype(dtype)
                   for key, shape in ((K1, (B, S, H, D)), (K2, (B, S, K, D)),
                                      (K3, (B, S, K, D)), (K4, (B, S, H, D))))

    def loss_kernel(q, k, v):
        out = flash_attention_vjp(q, k, v, causal, window, softcap,
                                  *blocks, True)
        return jnp.sum(out.astype(jnp.float32) * ct.astype(jnp.float32))

    def loss_oracle(q, k, v):
        out = attention_ref(q, k, v, causal=causal, window=window,
                            softcap=softcap)
        return jnp.sum(out * ct.astype(jnp.float32))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(loss_oracle, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    tol = TOL[dtype]
    for a, b, name in zip(gk, go, ("dq", "dk", "dv")):
        assert a.dtype == dtype, name
        a = np.asarray(a.astype(jnp.float32))
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, atol=tol, rtol=tol,
                                       err_msg=name)
        else:
            err = np.linalg.norm(a - b) / np.linalg.norm(b)
            assert err < tol, (name, err)


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


def test_kernel_blocks_fit_the_lanes():
    for G in (1, 2, 4, 7, 8, 16):
        for D in (64, 128, 256):
            bq, bk = kernel_blocks(G, D)
            assert bq >= 8 and bq & (bq - 1) == 0, (G, D, bq)
            assert bk % 128 == 0, (G, D, bk)
    # more query heads per group, or wider heads: fewer queries per tile
    assert kernel_blocks(16, 64)[0] < kernel_blocks(1, 64)[0]
    assert kernel_blocks(8, 128)[0] < kernel_blocks(8, 64)[0]


# ---------------------------------------------------------------------------
# dispatch: which calls of apply_attention reach the kernels
# ---------------------------------------------------------------------------
# qwen2-0.5b's attention heads on a narrow residual stream
CFG = dataclasses.replace(reduced(get_config("qwen2-0.5b")), n_heads=14,
                          n_kv_heads=2, d_head=64)
BK = kernel_blocks(7, 64)[1]


@pytest.fixture
def on_tpu(monkeypatch):
    """``apply_attention`` as it dispatches on the TPU (the kernels still
    run in interpret mode here); records which path each call took."""
    taken = []
    for name in ("flash_kernel_attention", "flash_attention_xla"):
        fn = getattr(attention, name)
        monkeypatch.setattr(attention, name,
                            lambda *a, _fn=fn, _n=name, **kw:
                            taken.append(_n) or _fn(*a, **kw))
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    return taken


def _apply(S, kv_mask=None):
    params = attention.init_attention(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(K1, (1, S, CFG.d_model))
    out, _ = attention.apply_attention(
        params, x, CFG, local=False, positions=jnp.arange(S)[None],
        compute_dtype=jnp.float32, kv_mask=kv_mask)
    return out


@pytest.mark.parametrize("S,masked", [
    pytest.param(BK + BK // 2, False, id="length-not-divisible"),
    pytest.param(BK, True, id="kv_mask"),
])
def test_dispatch_keeps_xla_path(on_tpu, monkeypatch, S, masked):
    mask = jnp.arange(S)[None] < S - 3 if masked else None
    want = _apply(S, mask)
    assert on_tpu == ["flash_attention_xla"]
    on_tpu.clear()
    monkeypatch.setattr(attention, "on_tpu", lambda: False)
    np.testing.assert_array_equal(_apply(S, mask), want)
    assert on_tpu == ["flash_attention_xla"]


def test_dispatch_takes_kernel_on_tpu(on_tpu, monkeypatch):
    got = _apply(BK)
    assert on_tpu == ["flash_kernel_attention"]
    on_tpu.clear()
    monkeypatch.setattr(attention, "on_tpu", lambda: False)
    want = _apply(BK)
    assert on_tpu == ["flash_attention_xla"]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_interpret_default_is_backend_derived():
    # off the TPU the kernels run in the interpreter, whatever the
    # dispatch predicate is patched to
    assert attention.default_interpret() == (jax.default_backend() != "tpu")


ATTN_ARCHS = [a for a in REGISTRY
              if {ATTN, ATTN_LOCAL} & set(get_config(a).pattern)]


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_dispatch_at_published_widths(monkeypatch, arch):
    """On the TPU every attention model at its published widths takes the
    kernel at a length its ``block_k`` divides and keeps the XLA path at
    one it does not; windowed layers always keep the XLA path."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    cfg = get_config(arch)
    D = cfg.head_dim
    bk = kernel_blocks(cfg.n_heads // cfg.n_kv_heads, D)[1]

    def takes(S, blk):
        q = jax.ShapeDtypeStruct((1, S, cfg.n_heads, D), BF16)
        k = jax.ShapeDtypeStruct((1, S, cfg.n_kv_heads, D), BF16)
        window = cfg.local_window if blk == ATTN_LOCAL else 0
        return attention.kernel_takes(q, k, window, None)

    for blk in {ATTN, ATTN_LOCAL} & set(cfg.pattern):
        assert takes(4 * bk, blk) == (blk == ATTN), blk
        assert not takes(4 * bk + bk // 2, blk), blk
