"""Device time by the program's layers: the HLO parser's scope rules, the
attribution of a trace's operations, the per-layer readers, and a
recorded v5e trace of a scanned, remat'd program with two scopes."""
import pathlib

import pytest

from bench import harness, scopes
from bench.trace import Chip, Trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"

# a TPU-style module: a fused matmul whose root is the layer scan's
# dynamic-update-slice, a fusion with no matmul, a plain op, and ops with
# no known scope
HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(bf16[4,8]{1,0})->bf16[4,8]{1,0}}

%fused_computation (param_0: bf16[2,4,8], param_1: bf16[4,8], param_2: s32[]) -> bf16[2,4,8] {
  %param_1 = bf16[4,8]{1,0:T(8,128)(2,1)} parameter(1)
  %convolution.3 = bf16[4,8]{1,0:T(8,128)(2,1)} convolution(bf16[4,8]{1,0} %param_1, bf16[4,8]{1,0} %param_1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn_core/bqd,bkd->bqk/dot_general" source_file="a.py" source_line=1}
  %convert.1 = bf16[1,4,8]{2,1,0} bitcast(bf16[4,8]{1,0} %convolution.3)
  %add.9 = bf16[4]{0} add(bf16[4]{0} %param_1, bf16[4]{0} %param_1), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/add"}
  %param_0 = bf16[2,4,8]{2,1,0} parameter(0)
  %param_2 = s32[] parameter(2)
  ROOT %dynamic-update-slice.5 = bf16[2,4,8]{2,1,0} dynamic-update-slice(bf16[2,4,8]{2,1,0} %param_0, bf16[1,4,8]{2,1,0} %convert.1, s32[] %param_2), metadata={op_name="jit(step)/transpose(jvp())/while/body/dynamic_update_slice"}
}

%fused_computation.1 (param_0.1: f32[4,8]) -> f32[4,8] {
  %param_0.1 = f32[4,8]{1,0} parameter(0)
  ROOT %multiply.2 = f32[4,8]{1,0} multiply(f32[4,8]{1,0} %param_0.1, f32[4,8]{1,0} %param_0.1), metadata={op_name="jit(step)/optimizer/mul"}
}

ENTRY %main.7 (x: bf16[4,8]) -> bf16[4,8] {
  %x = bf16[4,8]{1,0} parameter(0)
  %bitcast_dynamic-update-slice_fusion.96 = bf16[2,4,8]{2,1,0:T(8,128)(2,1)} fusion(bf16[2,4,8]{2,1,0} %x, bf16[4,8]{1,0} %x, s32[] %x), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp())/while/body/dynamic_update_slice"}
  %multiply_fusion = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %x), kind=kLoop, calls=%fused_computation.1
  %reduce.4 = f32[] reduce(f32[4,8]{1,0} %x, f32[] %x), dimensions={0,1}, to_apply=%add, metadata={op_name="jit(step)/transpose(jvp(head))/reduce_sum"}
  %copy.245 = bf16[4,8]{1,0} copy(bf16[4,8]{1,0} %x)
  ROOT %tanh.1 = bf16[4,8]{1,0} tanh(bf16[4,8]{1,0} %x), metadata={op_name="jit(head)/tanh"}
}
"""


def test_known_scopes_are_the_programs():
    from repro.models import scopes as program_scopes
    assert scopes.KNOWN == program_scopes.DEVICE_SCOPES


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/jvp()/while/body/closed_call/norm/mul", "norm"),
    ("jit(f)/transpose(jvp(attn_core))/dot_general", "attn_core"),
    ("jit(f)/head/closed_call/norm/rsqrt", "norm"),        # innermost
    ("jit(head)/tanh", None),                               # a jit, not a scope
    ("jit(f)/while/body/dynamic_slice", None),
    (None, None),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_fusion_takes_the_scope_of_the_matmul_it_holds():
    m = scopes.op_scopes(HLO)
    assert list(m) == ["jit_step"]
    t = m["jit_step"]
    # the root is the scan's dynamic-update-slice; the matmul inside wins
    assert t["bitcast_dynamic-update-slice_fusion.96"] == "attn_core"
    # no matmul, no metadata of its own: the root's scope
    assert t["multiply_fusion"] == "optimizer"
    assert t["reduce.4"] == "head"
    assert t["copy.245"] == scopes.UNSCOPED
    assert t["tanh.1"] == scopes.UNSCOPED


def test_largest_matmul_decides():
    text = HLO.replace(
        '  %add.9 = bf16[4]{0} add(bf16[4]{0} %param_1, bf16[4]{0} %param_1)',
        '  %dot.9 = bf16[64,8]{1,0} dot(bf16[4]{0} %param_1, bf16[4]{0} %param_1)')
    t = scopes.op_scopes(text)["jit_step"]
    assert t["bitcast_dynamic-update-slice_fusion.96"] == "mlp"


def _trace():
    # chip 0 runs two programs whose instructions share names; chip 1 the
    # step alone.  Window 0-10.
    c0 = Chip(ops=[(0.0, 2.0, "%fusion.1"), (2.0, 3.0, "%copy.2"),
                   (4.0, 5.0, "%fusion.1"), (5.0, 6.0, "%while.3"),
                   (5.0, 5.5, "%fusion.4")],
              modules=[(0.0, 3.0, "jit_step(123)"),
                       (4.0, 6.0, "jit_other(456)")])
    c1 = Chip(ops=[(0.0, 2.0, "%fusion.1"), (9.0, 11.0, "%fusion.4")],
              modules=[(0.0, 2.0, "jit_step(123)"),
                       (9.0, 11.0, "jit_step(123)")])
    return Trace({0: c0, 1: c1}, [(0.0, 10.0, "bench:window")])


MAP = {"jit_step": {"fusion.1": "attn_core", "copy.2": "unscoped",
                    "fusion.4": "mlp"},
       "jit_other": {"fusion.1": "head", "fusion.4": "optimizer"}}


def test_scope_seconds_by_module_and_window():
    s = scopes.scope_seconds(_trace(), MAP)
    # chip 0: attn_core 2, unscoped 1 (copy), head 1, optimizer 0.5 (the
    # while holds fusion.4, so only the leaf counts); chip 1: attn_core 2,
    # mlp 1 (clipped at the window's end); mean over two chips
    assert s == pytest.approx({"attn_core": 2.0, "unscoped": 0.5,
                               "head": 0.5, "optimizer": 0.25, "mlp": 0.5})
    assert scopes.top_unscoped(_trace(), MAP) == [("%copy.2",
                                                   pytest.approx(0.5))]


def test_ops_outside_any_known_program_are_unscoped():
    s = scopes.scope_seconds(_trace(), {})
    assert set(s) == {"unscoped"}


def _read(name, meas):
    return harness.load_module(harness.BENCH / "metrics"
                               / f"{name}.py").read(meas)


LAYER = ["attn_core_ms.train", "mlp_ms.train", "head_ms.train",
         "optimizer_ms.train", "attn_core_roofline.train"]


@pytest.mark.parametrize("name", LAYER)
def test_readers_find_nothing_without_scopes(name):
    base = {"kind": "train", "steps": 4, "attn_core_flops": 1e12,
            "peak": {"bf16_flops_per_s": 197e12}}
    assert _read(name, dict(base, trace={"busy_s": 1.0})) is None
    assert _read(name, dict(base, trace={"scope_s": {"unscoped": 1.0}})) \
        is None


def test_readers_read_scope_seconds():
    meas = {"kind": "train", "steps": 4, "attn_core_flops": 4.33e12,
            "peak": {"bf16_flops_per_s": 197e12},
            "trace": {"scope_s": {"attn_core": 2.0, "mlp": 0.8, "head": 0.4,
                                  "optimizer": 0.12, "unscoped": 0.1}}}
    assert _read("attn_core_ms.train", meas) == pytest.approx(500.0)
    assert _read("mlp_ms.train", meas) == pytest.approx(200.0)
    assert _read("head_ms.train", meas) == pytest.approx(100.0)
    assert _read("optimizer_ms.train", meas) == pytest.approx(30.0)
    assert _read("attn_core_roofline.train", meas) == pytest.approx(
        100 * 4.33e12 * 4 / (2.0 * 197e12))


def test_attention_core_flops_of_the_train_cell():
    cell = harness.find_cell("qwen2-0.5b.train.s4k")
    t = cell.traffic
    f = scopes.attention_core_train(cell.config, t["batch"], t["seq"])
    assert f == pytest.approx(4.33e12, rel=1e-3)


def test_recorded_chip_trace_lands_in_its_two_scopes():
    """A scanned, remat'd gradient with two equal matmuls per layer under
    ``attn_core`` and ``mlp`` (``fixtures/record_scopes.py``, one v5e):
    every operation that holds a matmul lands in its scope, about half
    the scoped time in each; what is left unscoped is the layer scan's
    own slicing of weights, gradients and saved inputs (and the loss),
    6.1% of the busy time as recorded."""
    import re
    from bench import trace as tr
    t = tr.load(FIXTURES / "v5e_scopes.xplane.pb")
    m = scopes.op_scopes((FIXTURES / "v5e_scopes.hlo.txt").read_text())
    s = scopes.scope_seconds(t, m)
    busy = tr.reduce(t)["busy_s"]
    assert set(s) == {"attn_core", "mlp", scopes.UNSCOPED}
    assert s["attn_core"] + s["mlp"] >= 0.90 * busy, s
    assert 0.45 <= s["attn_core"] / (s["attn_core"] + s["mlp"]) <= 0.55, s
    plumbing = re.compile(r"^%(bitcast_)?(dynamic-slice|dynamic-update-slice"
                          r"|broadcast|copy|multiply_convert)")
    left = [name for name, sec in scopes.top_unscoped(t, m, top=1000)
            if sec >= 1e-3 * busy]
    assert left and all(plumbing.match(n) for n in left), left
