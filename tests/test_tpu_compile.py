"""Compile rehearsal for TPU v5e: the main path's programs at the widths
``chip_smoke.py`` runs, mamba2-780m's ZeRO-3 train step over four chips
as its benchmark cell runs it, and the Pallas kernels, compiled for a
described (not attached) ``v5e:2x2`` chip.  Nothing runs; this catches what the
chip's compiler refuses (tiling, layouts, unlowered primitives, memory)
before chip time is spent.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import functools
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 2**30          # one TPU v5e chip
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on chip 0, with the persistent compilation cache off (a
    compile for a described chip is written to it but cannot be read
    back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _smoke_train_step(one_chip, smoke):
    from repro.configs.base import ShapeConfig
    from repro.launch import specs, train
    from repro.train import trainer
    args = train.parse_args(list(smoke.TRAIN_ARGS))
    cfg, policy, optcfg, schedcfg = train.build(args)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    step = trainer.make_train_step(cfg, policy, optcfg, schedcfg,
                                   shape=shape)
    return jax.jit(step, donate_argnums=(0,)).lower(
        _on(specs.state_structs(cfg, policy, optcfg), one_chip),
        _on(specs.batch_structs(cfg, shape), one_chip)).compile()


@pytest.fixture(scope="module")
def xla_train_step(one_chip, smoke):
    """The smoke's train step as a CPU-driven trace builds it: attention
    through the XLA tile scans."""
    return _smoke_train_step(one_chip, smoke)


@pytest.fixture(scope="module")
def kernel_train_step(one_chip, smoke):
    """The same step as the chip builds it: attention through the Pallas
    flash kernels, the backend predicates turned to the TPU's answers."""
    from repro.models import attention
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "on_tpu", lambda: True)
        mp.setattr(attention, "default_interpret", lambda: False)
        return _smoke_train_step(one_chip, smoke)


def test_full_width_train_step_fits_one_chip(xla_train_step,
                                             kernel_train_step):
    for compiled in (xla_train_step, kernel_train_step):
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes > 0      # the donated state is reused
        assert _total_bytes(compiled) < HBM_BYTES, m


# a score tile saved for the backward: f32[..., 512, 512] written into the
# scan's stacked residuals
TILE_RESIDUAL = re.compile(
    r"= f32\[[\d,]*512,512\][^=]*(dynamic-update-slice|fusion)\(")


def test_train_step_runs_attention_in_the_flash_kernels(xla_train_step,
                                                        kernel_train_step):
    """On the chip the train step's attention is the three Pallas kernels
    (forward, in the forward and the remat recompute, and both backward
    kernels), every one under ``attn_core``; the f32 score tiles the XLA
    scans save go, and the step needs less memory."""
    text = kernel_train_step.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    names = [re.search(r"%(\S+) = ", ln).group(1) for ln in calls]
    count = lambda kernel: sum(kernel in n for n in names)
    assert count("flash_attention_fwd") == 2, names     # forward, remat
    assert count("flash_attention_bwd_dkv") == 1, names
    assert count("flash_attention_bwd_dq") == 1, names
    assert all('/attn_core/' in re.search(r'op_name="([^"]*)"', ln).group(1)
               for ln in calls)
    assert TILE_RESIDUAL.search(xla_train_step.as_text())
    assert not TILE_RESIDUAL.search(text)
    assert _total_bytes(kernel_train_step) < _total_bytes(xla_train_step)


@pytest.mark.parametrize("width", ["decode", "prefill_chunk"])
def test_full_width_paged_serve_step_compiles(one_chip, smoke, width):
    from repro.configs import get_config
    from repro.configs.base import PolicyConfig
    from repro.launch import serve, specs
    from repro.serve import AsyncServeEngine
    args = serve.parse_args([*smoke.SERVE_ARGS, "--slots",
                             str(max(smoke.SERVE_SLOTS))])
    cfg = get_config(args.arch)
    policy = PolicyConfig(compute_dtype="float32", remat="none",
                          attn_impl="full")
    eng = AsyncServeEngine(cfg, None, policy, n_slots=args.slots,
                           max_seq=args.max_seq, page_size=args.page_size,
                           prefill_chunk=args.prefill_chunk, mode="paged")
    B, P = args.slots, eng.pool.pages_for(args.max_seq)
    W = 1 if width == "decode" else args.prefill_chunk
    rows = [jax.ShapeDtypeStruct((B, W), d, sharding=one_chip)
            for d in (I32, I32, jnp.bool_)]
    compiled = eng._paged_step.lower(
        _on(specs.param_structs(cfg, policy), one_chip),
        _on(jax.eval_shape(lambda: eng.pool.pages), one_chip),
        jax.ShapeDtypeStruct((B, P), I32, sharding=one_chip), *rows,
        jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)).compile()
    assert _total_bytes(compiled) < HBM_BYTES


# the mamba2 cell's global batch (rows x tokens), split over the data axis
MAMBA2_BATCH, MAMBA2_SEQ = 16, 2048


def _mamba2_zero3_step(devices):
    """mamba2-780m's train step at the widths and policy of its benchmark
    cell (global batch 16 x 2048, ZeRO-3, bf16 compute, f32 AdamW, block
    remat, the state donated), jitted over a (data, model) = (n, 1) mesh
    of ``devices`` as ``trainer.jit_train_step`` does."""
    from repro.configs import get_config
    from repro.configs.base import PolicyConfig, ShapeConfig
    from repro.launch import specs
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw
    from repro.train import trainer
    cfg = get_config("mamba2-780m")
    policy = PolicyConfig(compute_dtype="bfloat16", param_dtype="float32",
                          remat="block", zero_stage=3, attn_impl="xla")
    optcfg = adamw.AdamWConfig(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8,
                               weight_decay=0.1, grad_clip=1.0)
    shape = ShapeConfig("bench", MAMBA2_SEQ, MAMBA2_BATCH, "train")
    mesh = make_mesh((len(devices), 1), ("data", "model"),
                     devices=devices)
    state = specs.state_structs(cfg, policy, optcfg)
    batch = specs.batch_structs(cfg, shape)
    step = trainer.jit_train_step(
        trainer.make_train_step(cfg, policy, optcfg, None, mesh=mesh,
                                shape=shape),
        state, cfg, policy, mesh, batch)
    return step.lower(state, batch).compile()


# per chip, what XLA may allocate of a v5e's 16 GiB
V5E_USABLE = 15.75 * 2**30


@pytest.fixture(scope="module")
def mamba2_zero3_four_chips(topo, one_chip):
    """The cell's step as a CPU-driven trace builds it: the SSD core
    through the XLA chunk scan."""
    return _mamba2_zero3_step(topo.devices)


@pytest.fixture(scope="module")
def mamba2_kernel_four_chips(topo, one_chip):
    """The same step as the chip builds it: the SSD core through the
    Pallas kernels, the backend predicates turned to the TPU's answers."""
    from repro.models import attention
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "on_tpu", lambda: True)
        mp.setattr(attention, "default_interpret", lambda: False)
        return _mamba2_zero3_step(topo.devices)


def _ssd_kernel_calls(compiled):
    """op_name of every Pallas call of the step, by kernel name."""
    calls = {}
    for ln in compiled.as_text().splitlines():
        if "custom-call(" in ln and "tpu_custom_call" in ln:
            name = re.search(r"%(\S+) = ", ln).group(1)
            calls[name] = re.search(r'op_name="([^"]*)"', ln).group(1)
    return calls


def _passes(op_names):
    """(forward, block-remat recompute, backward) of ``op_names``."""
    fwd = [n for n in op_names
           if not n.startswith("jit(train_step)/transpose")]
    remat = [n for n in op_names if "rematted_computation" in n]
    bwd = [n for n in op_names if n.startswith("jit(train_step)/transpose")
           and "rematted_computation" not in n]
    return fwd, remat, bwd


def test_mamba2_zero3_train_step_fits_four_chips(mamba2_zero3_four_chips):
    """The mamba2 cell's step, state and all, fits each of four chips."""
    m = mamba2_zero3_four_chips.memory_analysis()
    assert m.alias_size_in_bytes > 0      # the donated state is reused
    assert _total_bytes(mamba2_zero3_four_chips) <= V5E_USABLE, m


def test_mamba2_ssd_dots_are_scoped_in_every_pass(
        mamba2_kernel_four_chips):
    """On the chip every matrix product of the SSD core is inside the
    Pallas kernels, and every kernel call is under ``ssd``: in the
    forward pass, the block-remat recompute and the backward pass."""
    calls = _ssd_kernel_calls(mamba2_kernel_four_chips)
    ssd = [n for k, n in calls.items() if k.startswith("ssd_chunk_")]
    assert ssd and all("/ssd/" in n for n in ssd), calls
    fwd, remat, bwd = _passes(ssd)
    assert len(fwd) >= 1 and len(remat) >= 1 and len(bwd) >= 1, calls


def test_mamba2_step_runs_the_ssd_kernels(mamba2_kernel_four_chips):
    """The cell's step compiled as the chip builds it: the forward
    kernel in the forward pass and in the block-remat recompute, the
    backward kernel in the backward pass, each once per layer scan and
    under ``/ssd/``; no chunk ``while`` loop is left under ``ssd``; the
    step fits and its temporaries stay under 5 GiB per chip."""
    calls = _ssd_kernel_calls(mamba2_kernel_four_chips)
    fwd, remat, bwd = _passes(list(calls.values()))
    kernel = lambda names, k: [n for n in names if f"/{k}/" in n]
    assert len(kernel(fwd, "ssd_chunk_fwd")) == 1, calls
    assert len(kernel(remat, "ssd_chunk_fwd")) == 1, calls
    assert len(kernel(bwd, "ssd_chunk_bwd")) == 1, calls
    assert len(calls) == 3 and all("/ssd/" in n for n in calls.values())
    loops = [n for n in re.findall(r' while\(.*op_name="([^"]*)"',
                                   mamba2_kernel_four_chips.as_text())
             if "/ssd/" in n]
    assert not loops, loops
    m = mamba2_kernel_four_chips.memory_analysis()
    assert m.alias_size_in_bytes > 0
    assert _total_bytes(mamba2_kernel_four_chips) <= V5E_USABLE, m
    assert m.temp_size_in_bytes <= 5 * 2**30, m.temp_size_in_bytes / 2**30


def test_mamba2_ssd_scan_stacks_no_tiles(topo, mamba2_zero3_four_chips):
    """The chunk scan saves each chunk's inputs and carried state for the
    backward, not its per-head L x L tiles: no array of the compiled step
    holds (chunks, rows per chip, L, L, heads) elements, in whatever
    layout, and the temporaries stay under 5 GiB per chip.  Stacking the
    tiles (the scan body without its checkpoint) compiled to 77 lines of
    such arrays and 7.46 GiB of temporaries; recomputing them, 3.83."""
    from repro.configs import get_config
    cfg = get_config("mamba2-780m")
    s = cfg.ssm
    heads = s.expand * cfg.d_model // s.head_dim
    rows = MAMBA2_BATCH // len(topo.devices)
    stack = (MAMBA2_SEQ // s.chunk) * rows * s.chunk * s.chunk * heads
    stacked = [ln for ln in mamba2_zero3_four_chips.as_text().splitlines()
               if any(math.prod(int(d) for d in dims.split(",")) == stack
                      for dims in re.findall(r"\[(\d+(?:,\d+)+)\]", ln))]
    assert not stacked, stacked[:3]
    temp = mamba2_zero3_four_chips.memory_analysis().temp_size_in_bytes
    assert temp <= 5 * 2**30, temp / 2**30


# qwen2-0.5b attention widths: 14 query heads over 2 kv heads of 64
Q = (4, 2048, 14, 64)
KV = (4, 2048, 2, 64)


def _flash_fwd():
    """The kernel's forward alone: the dense-prefill program."""
    from repro.kernels.flash_attention_bwd import (flash_attention_vjp,
                                                   kernel_blocks)
    bq, bk = kernel_blocks(Q[2] // KV[2], Q[3])

    def forward(q, k, v):
        return flash_attention_vjp(q, k, v, True, 0, 0.0, bq, bk, False)
    return forward, ((Q, BF16), (KV, BF16), (KV, BF16))


def _flash_vjp():
    from repro.kernels.flash_attention_bwd import (flash_attention_vjp,
                                                   kernel_blocks)
    bq, bk = kernel_blocks(Q[2] // KV[2], Q[3])

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention_vjp(
            *a, True, 0, 0.0, bq, bk, False).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)
    return grads, ((Q, BF16), (KV, BF16), (KV, BF16))


def _paged_decode():
    from repro.kernels.paged_attention import paged_decode_attention
    pages = ((256, 16, 2, 64), F32)
    return (functools.partial(paged_decode_attention, interpret=False),
            (((4, 14, 64), F32), pages, pages, ((4, 64), I32), ((4,), I32)))


def _refused(reason, raises=Exception):
    return pytest.mark.xfail(strict=True, reason=reason, raises=raises)


@pytest.mark.parametrize("kernel", [
    pytest.param(_flash_fwd, id="flash_attention"),
    pytest.param(_flash_vjp, id="flash_attention_vjp"),
    pytest.param(_paged_decode, id="paged_decode_attention", marks=_refused(
        "the Pallas TPU lowering requires the last two dims of a block "
        "shape divisible by 8 and 128 or equal to the array's: page "
        "block (1, 16, 1, 64) of pages (256, 16, 2, 64)", ValueError)),
])
def test_pallas_kernel_compiles_for_tpu(one_chip, kernel):
    fn, shapes = kernel()
    compiled = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
