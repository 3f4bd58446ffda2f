"""The reduction from a profiler trace to busy/idle time, exposed
collectives, program time and labelled gaps."""
import pathlib

import pytest

from bench import trace as tr
from bench.trace import Chip, Trace

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_one_chip.xplane.pb"


def test_union_merges_overlaps_and_drops_empty():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 3.0), (2.0, 2.5), (4.0, 5.0)]
    assert tr.union(iv) == [(0.0, 2.5), (4.0, 5.0)]
    assert tr.total(tr.union(iv)) == pytest.approx(3.5)


def test_minus_and_gaps():
    a = [(0.0, 10.0)]
    b = [(1.0, 2.0), (4.0, 6.0), (9.0, 12.0)]
    assert tr.minus(a, b) == [(0.0, 1.0), (2.0, 4.0), (6.0, 9.0)]
    assert tr.gaps([(1.0, 2.0)], 0.0, 3.0) == [(0.0, 1.0), (2.0, 3.0)]


def _two_chips():
    # chip 0: compute 0-4, an all-gather 3-6 (1 s of it exposed: 4-5 is
    # covered by nothing... and 5-6 by compute), compute 5-8
    c0 = Chip(ops=[(0.0, 4.0, "fusion.1"), (3.0, 6.0, "all-gather.2"),
                   (5.0, 8.0, "convolution.3")],
              modules=[(0.0, 4.0, "jit_step"), (5.0, 8.0, "jit_step")])
    # chip 1: busy 0-2 and 6-10, a reduce-scatter 2-3 with nothing else
    c1 = Chip(ops=[(0.0, 2.0, "fusion.1"), (2.0, 3.0, "reduce-scatter.4"),
                   (6.0, 10.0, "fusion.5")], modules=[])
    spans = [(0.0, 10.0, "bench:window"), (4.0, 5.0, "bench:data_feed"),
             (3.5, 5.5, "bench:step"), (8.0, 10.0, "bench:wait_step")]
    return Trace({0: c0, 1: c1}, spans)


def test_reduce_multi_chip_busy_idle_and_collectives():
    red = tr.reduce(_two_chips(), programs=("jit_step",))
    assert red["window_s"] == pytest.approx(10.0)
    assert red["busy_by_chip_s"][0] == pytest.approx(8.0)   # 0-6, 5-8
    assert red["busy_by_chip_s"][1] == pytest.approx(7.0)   # 0-3, 6-10
    assert red["busy_s"] == pytest.approx(7.5)              # mean of chips
    assert red["idle_share_worst"] == pytest.approx(0.3)    # chip 1
    # chip 0: all-gather 3-6 less compute 0-4 and 5-8 -> 4-5; chip 1: 2-3
    assert red["exposed_collective_s_worst"] == pytest.approx(1.0)
    assert red["program_s"] == pytest.approx(7.0)
    assert red["program_runs"] == 2
    assert red["program_gaps_s"] == [pytest.approx(0.0)]   # 4-5 is busy


def test_gaps_are_labelled_by_the_innermost_covering_span():
    red = tr.reduce(_two_chips())
    gaps = dict((round(s, 6), name) for name, s in
                red["breakdown"]["idle_gaps"])
    assert gaps == {2.0: "wait_step"}                      # chip 0: 8-10
    t = _two_chips()
    t.chips[0].ops.remove((3.0, 6.0, "all-gather.2"))
    red = tr.reduce(t)
    # 4-5 idle: data_feed (1 s cover, 1 s long) beats step (2 s long)
    labels = {name for name, _ in red["breakdown"]["idle_gaps"]}
    assert labels == {"data_feed", "wait_step"}


def test_breakdown_sums_op_time_per_chip_within_the_window():
    red = tr.reduce(_two_chips())
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((4.0 + 2.0) / 2)
    assert ops["fusion.5"] == pytest.approx(4.0 / 2)


def test_live_spans_restrict_program_gaps():
    t = Trace({0: Chip(ops=[(0.0, 1.0, "f"), (2.0, 3.0, "f"), (5.0, 6.0, "f")],
                       modules=[(0.0, 1.0, "jit__paged_step_fn"),
                                (2.0, 3.0, "jit__paged_step_fn"),
                                (5.0, 6.0, "jit__paged_step_fn")])},
              [(0.0, 6.0, "bench:window"), (0.0, 3.5, "bench:live")])
    red = tr.reduce(t, programs=("_paged_step",), live="bench:live")
    assert red["program_gaps_s"] == [pytest.approx(1.0)]   # 1-2 only


def test_recorded_chip_trace():
    """A trace recorded on one v5e chip (``fixtures/record.py``): two jitted
    programs three times inside ``bench:window``, a 5 ms host sleep after
    each pass.  The device's clock runs about 1.3 ms ahead of the host
    spans in it, so the first pass's two programs fall before the window
    and four are counted."""
    t = tr.load(FIXTURE)
    assert list(t.chips) == [0]
    assert len(t.chips[0].modules) == 6 and len(t.chips[0].ops) == 15
    assert all(" = " not in n for _, _, n in t.chips[0].ops)
    assert any(n == "bench:window" for _, _, n in t.spans)
    red = tr.reduce(t, programs=("jit",))
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["program_runs"] == 4
    # every idle gap of 4 ms or more lies in the host's sleep
    long = [n for n, s in red["breakdown"]["idle_gaps"] if s >= 0.004]
    assert long and set(long) == {"idle"}
    assert red["exposed_collective_s_worst"] == 0.0


def test_nested_ops_count_once_and_collectives_inside_a_loop_show():
    # a while op (0-10) holding a fusion (0-4), an all-gather (4-6) and a
    # fusion (6-10): the loop hides nothing, the all-gather is exposed
    ops = [(0.0, 10.0, "%while.1"), (0.0, 4.0, "%fusion.2"),
           (4.0, 6.0, "%all-gather.3"), (6.0, 10.0, "%fusion.4")]
    t = Trace({0: Chip(ops=ops, modules=[])}, [(0.0, 10.0, "bench:window")])
    red = tr.reduce(t)
    assert red["busy_s"] == pytest.approx(10.0)
    assert red["exposed_collective_s_worst"] == pytest.approx(2.0)
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert "%while.1" not in names and "%all-gather.3" in names
