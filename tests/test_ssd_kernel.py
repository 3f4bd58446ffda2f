"""The Pallas SSD kernel pair (``kernels.ssd_chunk``, interpret mode)
against ``ssd_chunked``'s XLA chunk scan and the benchmark's plain
reference (``bench/configs/mamba2-780m.py``, the paper's minimal SSD
listing), and which calls ``ssd_chunked`` sends to it."""
import dataclasses
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ssd_chunk
from repro.models import attention, ssm

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import harness  # noqa: E402

REF = harness.load_module(harness.BENCH / "configs" / "mamba2-780m.py")

# rows, length, heads, head width, groups, state, chunk
CASES = [
    pytest.param(2, 512, 4, 64, 1, 128, 128, id="G1-chunk128"),
    pytest.param(2, 512, 4, 64, 2, 128, 256, id="G2-chunk256"),
    pytest.param(2, 320, 4, 64, 1, 128, 128, id="length-not-divisible"),
    pytest.param(1, 256, 2, 128, 1, 256, 128, id="P128-N256"),
]


def _inputs(B, S, H, P, G, N, seed=0):
    """dt and A from mamba2's own initial ranges (dt in [1e-3, 0.1]
    log-uniform, A in [-16, -1]), x, B and C normal, and the weights of
    a seeded scalar of (y, h_final)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jnp.exp(jax.random.uniform(ks[1], (B, S, H), minval=math.log(1e-3),
                                    maxval=math.log(0.1)))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    wy = jax.random.normal(ks[5], (B, S, H, P))
    wh = jax.random.normal(ks[6], (B, H, N, P))
    return (x, dt, A, Bm, Cm), wy, wh


def _reference(x, dt, A, Bm, Cm, chunk):
    """(y, h_final) of the plain listing; its chunk divides the length."""
    S, H, G = x.shape[1], x.shape[2], Bm.shape[2]
    rep = lambda t: jnp.repeat(t, H // G, axis=2)
    y = REF.ssd(x * dt[..., None], A * dt, rep(Bm), rep(Cm),
                math.gcd(S, chunk))
    acum = jnp.cumsum(A * dt, axis=1)
    h = jnp.einsum("blhn,blh,blhp->bhnp", rep(Bm),
                   jnp.exp(acum[:, -1:] - acum) * dt, x,
                   precision="highest")
    return y, h


def _on_tpu(fn):
    """``fn`` with ``ssd_chunked`` dispatching as on the TPU: to the
    kernels, which still run in the interpreter here."""
    def run(*a, **kw):
        on_tpu = attention.on_tpu
        attention.on_tpu = lambda: True
        try:
            return fn(*a, **kw)
        finally:
            attention.on_tpu = on_tpu
    return run


def _paths(chunk):
    """The kernel path, the XLA scan and the reference, each as a
    function of (x, dt, A, B, C) -> (y, h_final)."""
    scan = lambda *a, **kw: ssm.ssd_chunked(*a, chunk=chunk, **kw)
    return {"kernel": _on_tpu(scan), "scan": scan,
            "reference": lambda *a: _reference(*a, chunk)}


def _nrm(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# Normwise, as test_ssd_chunked_gradients_match_the_reference: float32
# on every side (MXU operands of f32 inputs stay f32), differing in the
# order of sums; measured under 5e-8 (y, h_final) and 1.2e-6 (gradients
# but A's) against the reference.  d(A) sums every position's d(dt·A)·dt,
# terms that nearly cancel, so no float32 order of sums holds it to 1e-5:
# the XLA scan's own d(A) lies up to 2.9e-5 from the reference (the
# P128-N256 case).  The kernel's d(A) is held to where the scan's lies,
# plus 1e-5, from the reference and from the scan (measured: 1.8e-5 and
# 1.3e-5 in that case, at most 4.1e-6 and 3.5e-6 in the others).
TOL = 1e-5


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", CASES)
def test_kernel_forward_matches_scan_and_reference(B, S, H, P, G, N, chunk):
    args, _, _ = _inputs(B, S, H, P, G, N)
    with jax.default_matmul_precision("highest"):
        out = {k: f(*args) for k, f in _paths(chunk).items()}
    for want in ("scan", "reference"):
        for i, name in enumerate(("y", "h_final")):
            err = _nrm(out["kernel"][i], out[want][i])
            assert err < TOL, (want, name, err)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", CASES)
def test_kernel_gradients_match_scan_and_reference(B, S, H, P, G, N,
                                                   chunk):
    """jax.grad of a seeded scalar of (y, h_final) with respect to x, dt,
    A, B and C: the backward kernel against the scan's and the
    reference's autodiff."""
    args, wy, wh = _inputs(B, S, H, P, G, N)

    def scalar(fn):
        def f(*a):
            y, h = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(h * wh)
        return jax.grad(f, argnums=range(5))

    with jax.default_matmul_precision("highest"):
        grads = {k: scalar(f)(*args) for k, f in _paths(chunk).items()}
    for i, name in enumerate(("x", "dt", "A", "B", "C")):
        got = grads["kernel"][i]
        assert got.shape == args[i].shape and got.dtype == args[i].dtype
        tol = TOL if name != "A" else TOL + _nrm(grads["scan"][i],
                                                 grads["reference"][i])
        for want in ("reference", "scan"):
            err = _nrm(got, grads[want][i])
            assert err < tol, (name, want, err, tol)


def test_kernel_token_mask_tail():
    """A right-padded batch as ``apply_ssm`` hands it over: dt = 0 past
    each row's length, so the state passes through the tail unchanged;
    outputs and gradients (through the mask, as in the model) as the
    scan's, and h_final as the reference's over the real tokens only."""
    B, S, H, P, G, N, chunk = 2, 384, 4, 64, 1, 128, 128
    (x, dt, A, Bm, Cm), wy, wh = _inputs(B, S, H, P, G, N, seed=3)
    lengths = jnp.array([S, 200])
    mask = (jnp.arange(S)[None] < lengths[:, None])[:, :, None]

    def scalar(fn):
        def f(x, dt, A, Bm, Cm):
            y, h = fn(x, jnp.where(mask, dt, 0.0), A, Bm, Cm)
            return jnp.sum(y * wy) + jnp.sum(h * wh), (y, h)
        return jax.value_and_grad(f, argnums=range(5), has_aux=True)

    paths = _paths(chunk)
    with jax.default_matmul_precision("highest"):
        (_, (yk, hk)), gk = scalar(paths["kernel"])(x, dt, A, Bm, Cm)
        (_, (ys, hs)), gs = scalar(paths["scan"])(x, dt, A, Bm, Cm)
        _, h_short = _reference(x[1:, :200], dt[1:, :200], A,
                                Bm[1:, :200], Cm[1:, :200], chunk)
    assert _nrm(yk, ys) < TOL and _nrm(hk, hs) < TOL
    assert _nrm(hk[1:], h_short) < TOL
    for name, a, b in zip(("x", "dt", "A", "B", "C"), gk, gs):
        tol = TOL if name != "A" else 2 * TOL
        assert _nrm(a, b) < tol, name
    # past a row's length, dt's gradient is the mask's zero
    assert not np.any(np.asarray(gk[1])[1, 200:])


def test_kernel_bf16_operands():
    """The train path's dtype: x, B and C in bf16 reach the kernels
    as they are, and the MXU operands (W, x, h, B, C and the cotangents)
    are bf16 with f32 accumulation, as XLA's default-precision f32 dot
    rounds them on the TPU.  Against the scan on the same bf16 inputs
    in f32 at highest precision: one rounding to 8 significant bits
    (2^-9 relative) per operand, two operands per product, so a
    normwise gap of a few 2^-9; bound 4 * 2^-9."""
    B, S, H, P, G, N, chunk = 1, 256, 4, 64, 1, 128, 128
    (x, dt, A, Bm, Cm), wy, wh = _inputs(B, S, H, P, G, N, seed=5)
    x, Bm, Cm = (t.astype(jnp.bfloat16) for t in (x, Bm, Cm))

    def scalar(fn):
        def f(*a):
            y, h = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(h * wh), (y, h)
        return jax.value_and_grad(f, argnums=range(5), has_aux=True)

    paths = _paths(chunk)
    with jax.default_matmul_precision("highest"):
        (_, outk), gk = scalar(paths["kernel"])(x, dt, A, Bm, Cm)
        (_, outs), gs = scalar(paths["scan"])(x, dt, A, Bm, Cm)
    tol = 4 * 2.0 ** -9
    for name, a, b in zip(("y", "h_final", "x", "dt", "A", "B", "C"),
                          (*outk, *gk), (*outs, *gs)):
        assert a.dtype == b.dtype, name
        assert _nrm(a.astype(jnp.float32), b.astype(jnp.float32)) < tol, name


def test_kernel_takes_an_initial_state():
    B, S, H, P, G, N, chunk = 1, 256, 4, 64, 1, 128, 128
    args, wy, wh = _inputs(B, S, H, P, G, N, seed=7)
    h0 = jax.random.normal(jax.random.PRNGKey(8), (B, H, N, P))

    def scalar(fn):
        def f(h0, x):
            y, h = fn(x, *args[1:], h0=h0)
            return jnp.sum(y * wy) + jnp.sum(h * wh)
        return jax.value_and_grad(f, argnums=(0, 1))

    paths = _paths(chunk)
    with jax.default_matmul_precision("highest"):
        vk, gk = scalar(paths["kernel"])(h0, args[0])
        vs, gs = scalar(paths["scan"])(h0, args[0])
    assert abs(float(vk - vs)) / abs(float(vs)) < TOL
    for a, b in zip(gk, gs):
        assert _nrm(a, b) < TOL


# ---------------------------------------------------------------------------
# dispatch: which calls of ssd_chunked reach the kernels
# ---------------------------------------------------------------------------
CELL = get_config("mamba2-780m")
CELL_ROWS = 4                 # one chip's rows of the cell's batch


def _widths(cfg, rows, S):
    s = cfg.ssm
    H = s.expand * cfg.d_model // s.head_dim
    x = jax.ShapeDtypeStruct((rows, S, H, s.head_dim), jnp.bfloat16)
    Bm = jax.ShapeDtypeStruct((rows, S, s.n_groups, s.d_state),
                              jnp.bfloat16)
    return x, Bm, s.chunk


def test_cell_widths_take_the_kernel_on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert ssm.kernel_takes(*_widths(CELL, CELL_ROWS, 2048))
    # a head count that no head block divides keeps the scan
    x, Bm, chunk = _widths(CELL, CELL_ROWS, 2048)
    odd = jax.ShapeDtypeStruct(x.shape[:2] + (47,) + x.shape[3:], x.dtype)
    assert not ssm.kernel_takes(odd, Bm, chunk)


def test_cpu_and_reduced_configs_keep_the_scan(monkeypatch):
    assert not ssm.kernel_takes(*_widths(CELL, CELL_ROWS, 2048))
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert not ssm.kernel_takes(*_widths(reduced(CELL), 2, 128))


@pytest.mark.parametrize("S,SSM_WIDTHS,takes", [
    pytest.param(256, dict(head_dim=64, d_state=128, chunk=128), True,
                 id="kernel-widths"),
    pytest.param(256, dict(head_dim=16, d_state=16, chunk=32), False,
                 id="reduced-widths"),
])
def test_apply_ssm_dispatch(monkeypatch, S, SSM_WIDTHS, takes):
    """``apply_ssm`` on a faked TPU sends the call to the kernels exactly
    when ``kernel_takes`` says so, with the same output as the CPU's
    scan."""
    cfg = reduced(CELL)
    cfg = dataclasses.replace(cfg, d_model=128, ssm=dataclasses.replace(
        cfg.ssm, **SSM_WIDTHS))
    params = ssm.init_ssm(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.d_model))
    run = lambda: ssm.apply_ssm(params, x, cfg,
                                compute_dtype=jnp.float32)[0]
    want = run()
    calls = []
    vjp = ssd_chunk.ssd_chunk_vjp
    monkeypatch.setattr(ssd_chunk, "ssd_chunk_vjp",
                        lambda *a: calls.append(1) or vjp(*a))
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    with jax.default_matmul_precision("highest"):
        got = run()
    assert bool(calls) == takes
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_head_block_fits_the_lanes():
    # mamba2-780m: 48 heads of 64 in one group -> 8 heads, 512 lanes
    assert ssd_chunk.head_block(48, 1, 64) == 8
    for H, G, P in ((48, 1, 64), (4, 2, 64), (4, 1, 128), (16, 1, 32),
                    (24, 4, 64)):
        hb = ssd_chunk.head_block(H, G, P)
        assert hb and (H // G) % hb == 0, (H, G, P)
        assert hb * P % ssd_chunk.LANES == 0, (H, G, P)
        assert hb * P <= ssd_chunk.HEAD_LANES, (H, G, P)
    assert ssd_chunk.head_block(3, 1, 64) == 0
    assert not ssd_chunk.fits(48, 64, 1, 128, 96)
    assert not ssd_chunk.fits(48, 64, 1, 16, 256)
    assert not ssd_chunk.fits(48, 16 * 3, 1, 128, 256)
