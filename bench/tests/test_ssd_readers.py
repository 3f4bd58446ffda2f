"""The Mamba-2 SSD core's work from shapes against hand counts, and its
two readers (``ssd_ms.train``, ``ssd_roofline.train``) on synthetic
measurements."""
import pytest

from bench import flops, harness, ssd_work

# d 4, d_in 8, heads 2 of 4, state 2, one group, chunk 4
MAMBA = {"d_model": 4, "n_layer": 3, "vocab_size": 100,
         "mamba2_layer": {"d_state": 2, "expand": 2, "headdim": 4,
                          "ngroups": 1, "chunk_size": 4, "d_conv": 4}}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def test_core_flops_by_hand_and_as_flops_counts_them():
    # C.B^T over the causal half of the chunk 2*2*1*2, its product with x
    # 2*2*2*4, state read-out and update 2*2*2*2*4: 8 + 32 + 64
    assert ssd_work.core_flops_per_token(MAMBA) == 104
    # bench/flops.ssd_layer is the projections (176 + 64) and this core
    assert flops.ssd_layer(MAMBA) == 176 + 64 + 104
    B, S = 3, 8
    assert ssd_work.train_flops(MAMBA, B, S) == 3 * B * S * 3 * 104


def test_core_bytes_by_hand():
    # x 8 and B, C 2 each in bf16: 2 * 12; dt 2 and y 8 in f32: 4 * 10
    assert ssd_work.core_bytes_per_token(MAMBA, 2) == 24 + 40
    assert ssd_work.core_bytes_per_token(MAMBA, 4) == 48 + 40
    assert ssd_work.train_bytes(MAMBA, 3, 8, 2) == 3 * 3 * 8 * 3 * 64


def _meas(**kw):
    m = {"kind": "train", "chips": 4, "steps": 5, "peak": PEAK,
         "ssd_core_flops": 8000.0, "ssd_core_bytes": 2000.0,
         "trace": {"scope_s": {"ssd": 100.0, "head": 1.0}}}
    m.update(kw)
    return m


def test_ssd_ms_per_step():
    read = _reader("ssd_ms.train")
    assert read(_meas()) == pytest.approx(1e3 * 100.0 / 5)


def test_ssd_roofline_takes_the_larger_bound_per_chip():
    read = _reader("ssd_roofline.train")
    # per chip: FLOPs 8000 / 100 / 4 = 20 s, bytes 2000 / 10 / 4 = 50 s;
    # five steps' least time 250 s over 100 s under ``ssd`` would be 250%
    with pytest.raises(ValueError, match="miscounted"):
        read(_meas())
    assert read(_meas(steps=1)) == pytest.approx(50.0)
    assert read(_meas(steps=1, ssd_core_bytes=400.0)) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["ssd_ms.train", "ssd_roofline.train"])
@pytest.mark.parametrize("change", [
    {"trace": {}},                                  # no scope_s yet
    {"trace": {"scope_s": {"head": 1.0}}},          # no ssd scope ran
    {"kind": "serve"},
    {"steps": 0},
])
def test_nothing_to_read_gives_none(name, change):
    assert _reader(name)(_meas(**change)) is None


def test_roofline_needs_the_counts():
    m = _meas(steps=1)
    del m["ssd_core_flops"]
    assert _reader("ssd_roofline.train")(m) is None
