"""Compile rehearsal for TPU v5e: the main path's programs at the widths
``chip_smoke.py`` runs, and the Pallas kernels, compiled for a described
(not attached) ``v5e:2x2`` chip.  Nothing runs; this catches what the
chip's compiler refuses (tiling, layouts, unlowered primitives, memory)
before chip time is spent.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import functools
import importlib.util
import os

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
    back without one) and the tuned-block registry empty (its entries
    were tuned on the CPU, so the chip runs the defaults)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import registry
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    registry.set_registry(None)
    yield SingleDeviceSharding(topo.devices[0])
    registry.reset_registry()
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


def test_full_width_train_step_fits_one_chip(one_chip, smoke):
    from repro.configs.base import ShapeConfig
    from repro.launch import specs, train
    from repro.train import trainer
    args = train.parse_args(list(smoke.TRAIN_ARGS))
    cfg, policy, optcfg, schedcfg = train.build(args)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    step = trainer.make_train_step(cfg, policy, optcfg, schedcfg,
                                   shape=shape)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        _on(specs.state_structs(cfg, policy, optcfg), one_chip),
        _on(specs.batch_structs(cfg, shape), one_chip)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes > 0          # the donated state is reused
    assert _total_bytes(compiled) < HBM_BYTES, m


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


# qwen2-0.5b attention widths: 14 query heads over 2 kv heads of 64
Q = (4, 2048, 14, 64)
KV = (4, 2048, 2, 64)


def _flash_fwd():
    from repro.kernels.flash_attention import flash_attention
    return (functools.partial(flash_attention, causal=True, interpret=False),
            ((Q, BF16), (KV, BF16), (KV, BF16)))


def _flash_vjp():
    from repro.kernels.flash_attention_bwd import flash_attention_vjp

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention_vjp(
            *a, True, 0, 0.0, 256, 256, False).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)
    return grads, ((Q, BF16), (KV, BF16), (KV, BF16))


def _paged_decode():
    from repro.kernels.paged_attention import paged_decode_attention
    pages = ((256, 16, 2, 64), F32)
    return (functools.partial(paged_decode_attention, interpret=False),
            (((4, 14, 64), F32), pages, pages, ((4, 64), I32), ((4,), I32)))


def _ssd():
    # mamba2-780m: 48 heads of 64, one group, state 128, chunk 256
    from repro.kernels.ssd import ssd
    return (functools.partial(ssd, chunk=256, interpret=False),
            (((1, 2048, 48, 64), BF16), ((1, 2048, 48), F32), ((48,), F32),
             ((1, 2048, 1, 128), BF16), ((1, 2048, 1, 128), BF16)))


def _rglru():
    from repro.kernels.rglru import rglru
    seq = ((1, 2048, 2560), F32)
    return (functools.partial(rglru, block_seq=128, interpret=False),
            (seq, seq))


def _refused(reason, raises=Exception):
    return pytest.mark.xfail(strict=True, reason=reason, raises=raises)


@pytest.mark.parametrize("kernel", [
    pytest.param(_flash_fwd, id="flash_attention"),
    pytest.param(_flash_vjp, id="flash_attention_vjp", marks=_refused(
        "Mosaic failed to compile TPU kernel: Invalid input layout "
        "(tpu.reshape of the row statistics)")),
    pytest.param(_paged_decode, id="paged_decode_attention", marks=_refused(
        "the Pallas TPU lowering requires the last two dims of a block "
        "shape divisible by 8 and 128 or equal to the array's: page "
        "block (1, 16, 1, 64) of pages (256, 16, 2, 64)", ValueError)),
    pytest.param(_ssd, id="ssd", marks=_refused(
        "Unimplemented primitive in Pallas TPU lowering: cumsum",
        NotImplementedError)),
    pytest.param(_rglru, id="rglru", marks=_refused(
        "Unimplemented primitive in Pallas TPU lowering: dynamic_slice",
        NotImplementedError)),
])
def test_pallas_kernel_compiles_for_tpu(one_chip, kernel):
    fn, shapes = kernel()
    compiled = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
