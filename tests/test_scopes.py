"""The program's layer names in a profiler trace: the device scopes reach
the compiled programs' ``op_name`` metadata (through the layer scan,
block remat and the backward pass), and the serving loop writes its host
spans once per engine iteration, in order."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import PolicyConfig, ShapeConfig
from repro.models import lm, scopes
from repro.optim import adamw
from repro.serve import AsyncServeEngine, ServeRequest
from repro.train import trainer

SEQ = 256


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes_in(names):
    """Every scope that is a path component of some op_name, also inside
    transform wrappers such as ``transpose(jvp(attn_core))``."""
    found = set()
    for n in names:
        for comp in n.split("/"):
            core = comp.split("(")[-1].rstrip(")")
            if core in scopes.DEVICE_SCOPES:
                found.add(core)
    return found


def _train_hlo(arch):
    cfg = reduced(get_config(arch))
    policy = PolicyConfig(compute_dtype="bfloat16", remat="block",
                          attn_impl="xla")
    opt = adamw.AdamWConfig()
    step = trainer.make_train_step(cfg, policy, opt,
                                   shape=ShapeConfig("t", SEQ, 2, "train"))
    state = jax.eval_shape(lambda k: trainer.init_state(k, cfg, policy, opt),
                           jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    return jax.jit(step).lower(state, {"inputs": ids, "labels": ids}
                               ).compile().as_text()


def _serve_hlo():
    cfg = reduced(get_config("qwen2-0.5b"))
    eng = AsyncServeEngine(cfg, lm.init_lm(jax.random.PRNGKey(0), cfg),
                           PolicyConfig(compute_dtype="float32",
                                        remat="none"),
                           n_slots=2, max_seq=64, page_size=8,
                           prefill_chunk=8)
    B, W, P = 2, 8, 8
    z = jnp.zeros((B, W), jnp.int32)
    return eng._paged_step.lower(
        eng.params, eng.pool.pages, jnp.full((B, P), eng.pool.trash),
        z, z, jnp.zeros((B, W), bool), jnp.zeros((B,), jnp.int32)
    ).compile().as_text()


PROGRAMS = {
    "qwen2-train": (lambda: _train_hlo("qwen2-0.5b"),
                    {"embed", "norm", "attn_proj", "attn_core", "mlp",
                     "head", "optimizer"}),
    "mamba2-train": (lambda: _train_hlo("mamba2-780m"),
                     {"embed", "norm", "ssd", "head", "optimizer"}),
    "qwen2-paged-serve": (_serve_hlo,
                          {"embed", "norm", "attn_proj", "attn_core", "mlp",
                           "head", "kv_gather", "kv_scatter"}),
}


def test_programs_cover_every_scope():
    covered = set().union(*(want for _, want in PROGRAMS.values()))
    assert covered == set(scopes.DEVICE_SCOPES)
    assert len(set(scopes.DEVICE_SCOPES)) == len(scopes.DEVICE_SCOPES)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_reach_optimized_hlo(program):
    make, want = PROGRAMS[program]
    assert want <= _scopes_in(_op_names(make()))


def test_attn_core_labels_dots_in_every_pass():
    """Forward, remat recompute and backward dots of the scanned,
    remat'd layer stack all carry ``attn_core``."""
    names = [n for n in _op_names(_train_hlo("qwen2-0.5b"))
             if n.endswith("dot_general") and "/attn_core/" in n]
    fwd = [n for n in names if not n.startswith("jit(train_step)/transpose")]
    remat = [n for n in names if "rematted_computation" in n]
    bwd = [n for n in names if n.startswith("jit(train_step)/transpose")
           and "rematted_computation" not in n]
    assert fwd and remat and bwd, names
    assert all("/while/body/" in n for n in names)     # inside the scan


def test_attn_core_labels_flash_kernels_in_every_pass(monkeypatch):
    """On the TPU the train step's attention is the Pallas flash kernels:
    the forward kernel in the forward pass and again in the remat
    recompute, and both backward kernels, each under ``attn_core``.  The
    step is lowered for the TPU (no chip needed) with the backend
    predicates turned to the TPU's answers; each custom call's location
    is the ``op_name`` it compiles to."""
    from repro.models import attention
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "default_interpret", lambda: False)
    cfg = reduced(get_config("qwen2-0.5b"))
    seq = attention.fab.kernel_blocks(
        cfg.n_heads // cfg.n_kv_heads, cfg.head_dim)[1]
    policy = PolicyConfig(compute_dtype="bfloat16", remat="block",
                          attn_impl="xla")
    opt = adamw.AdamWConfig()
    step = trainer.make_train_step(cfg, policy, opt,
                                   shape=ShapeConfig("t", seq, 2, "train"))
    state = jax.eval_shape(lambda k: trainer.init_state(k, cfg, policy, opt),
                           jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    text = jax.jit(step).trace(state, {"inputs": ids, "labels": ids}).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    names = [locs[n] for n in re.findall(
        r'custom_call @tpu_custom_call\(.*loc\((#loc\d+)\)$', text, re.M)]
    assert all("attn_core" in n.split("/") for n in names), names
    kernels = sorted(n.split("/")[-2] for n in names)
    assert kernels == ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                       "flash_attention_fwd", "flash_attention_fwd"], names
    remat = [n for n in names if "rematted_computation" in n]
    assert len(remat) == 1 and "flash_attention_fwd" in remat[0], names


# ---------------------------------------------------------------------------
# host spans of the serving loop
# ---------------------------------------------------------------------------
def _host_events(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
    return sorted(out)


@pytest.mark.parametrize("fused", [True, False])
def test_serve_spans_once_per_iteration_in_order(tmp_path, fused):
    cfg = reduced(get_config("qwen2-0.5b"))
    eng = AsyncServeEngine(cfg, lm.init_lm(jax.random.PRNGKey(0), cfg),
                           PolicyConfig(compute_dtype="float32",
                                        remat="none"),
                           n_slots=2, max_seq=64, page_size=8,
                           prefill_chunk=8, fused=fused)
    rng = np.random.RandomState(0)
    for i in range(2):
        assert eng.submit(ServeRequest(
            i, list(rng.randint(0, cfg.vocab_size, 12)), max_new=6))
    eng.step()                                     # compile outside
    iters = 4
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(iters):
            with jax.profiler.TraceAnnotation("test:iteration"):
                assert eng.step() > 0
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    events = _host_events(path)
    its = [(a, b) for a, b, n in events if n == "test:iteration"]
    assert len(its) == iters
    spans = [e for e in events if e[2].startswith(scopes.SPAN_PREFIX)]
    assert {n for _, _, n in spans} == {
        f"{scopes.SPAN_PREFIX}serve.{p}" for p in scopes.SERVE_PHASES}
    # fused: one step per iteration; unfused: the prefill chunks, then the
    # decode rows, each planned, and run when the plan holds rows
    want = (r"admit plan inputs device tokens" if fused else
            r"admit( plan( inputs device tokens)?){2}")
    for a, b in its:
        inside = [n.rsplit(".", 1)[-1] for s, e, n in spans
                  if a <= s and e <= b]
        assert re.fullmatch(want, " ".join(inside)), inside
    assert all(any(a <= s and e <= b for a, b in its)
               for s, e, _ in spans)                # none outside
