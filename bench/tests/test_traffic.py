"""The generator: seeded, deterministic, the same work for every seed,
due times kept apart from submit times; and the run's refusal off the
chip."""
import copy
import subprocess
import sys

import numpy as np

from bench import harness, traffic
from bench.tests.cells import ROOT, SERVE, benchmark


def _serve_mix():
    return copy.deepcopy(harness.find_cell(SERVE, benchmark()).traffic)


def test_train_rows_are_seeded_and_differ_by_step_and_seed():
    t = harness.find_cell("qwen2-0.5b.train.s4k").traffic
    a = traffic.train_batch(t, 2**33 + 5, 0, 151936)
    b = traffic.train_batch(t, 2**33 + 5, 0, 151936)
    c = traffic.train_batch(t, 2**33 + 5, 1, 151936)
    d = traffic.train_batch(t, 6, 0, 151936)
    assert a["inputs"].shape == (t["batch"], t["seq"])
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    np.testing.assert_array_equal(a["labels"][:, :-1], a["inputs"][:, 1:])
    assert not np.array_equal(a["inputs"], c["inputs"])
    assert not np.array_equal(a["inputs"], d["inputs"])
    assert not np.array_equal(a["inputs"][0], a["inputs"][1])
    assert a["inputs"].min() >= 0 and a["inputs"].max() < 151936


def test_schedule_is_deterministic_and_seeds_share_the_work():
    t = _serve_mix()
    s1 = traffic.serve_schedule(t, 11, 151936, 60.0)
    s1b = traffic.serve_schedule(t, 11, 151936, 60.0)
    s2 = traffic.serve_schedule(t, 2**35 + 3, 151936, 60.0)
    assert [(a.due, a.prompt, a.max_new) for a in s1] == \
        [(a.due, a.prompt, a.max_new) for a in s1b]
    assert [a.due for a in s1] != [a.due for a in s2]
    # one multiset of sizes for every seed, permuted by the seed
    work = traffic.serve_work(t, 60.0)
    again = traffic.serve_work(t, 60.0)
    for a, b in zip(work, again):
        np.testing.assert_array_equal(a, b)
    for s in (s1, s2):
        outs = sorted(a.max_new for a in s)
        pool = sorted(work[3].tolist())
        assert all(outs.count(x) <= pool.count(x) for x in set(outs))


def test_schedule_follows_the_file():
    t = _serve_mix()
    s = traffic.serve_schedule(t, 3, 151936, 200.0)
    rate = len(s) / 200.0
    assert abs(rate - t["arrivals"]["rate_per_s"]) \
        < 0.25 * t["arrivals"]["rate_per_s"]
    pre = t["prompt"]["shared_prefix"]
    heads = {tuple(a.prompt[:pre["length"]]) for a in s}
    assert len(heads) <= pre["count"]
    for a in s:
        user = len(a.prompt) - pre["length"]
        assert t["prompt"]["user"]["min"] <= user <= t["prompt"]["user"]["max"]
        assert t["output"]["min"] <= a.max_new <= t["output"]["max"]
        assert len(a.prompt) + a.max_new <= t["engine"]["max_seq"]
    assert all(b.due >= a.due for a, b in zip(s, s[1:]))


def test_run_refuses_a_host_without_a_tpu():
    """Off the chip the benchmark exits nonzero and prints no result."""
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-0.5b.train.s4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
