"""The one generator that every traffic file is read by.

Training mixes: token rows drawn per step from the seed.  Serving mixes:
an open-loop arrival schedule whose due times are fixed before the run
and kept apart from the times requests are actually submitted.  Each
distribution's parameters are the traffic file's; see the file for the
numbers.  The Zipf token draw is the one of
``repro.data.pipeline.SyntheticDataset`` (ids ``zipf(a) - 1`` clipped to
the vocabulary), copied here so that the yardstick cannot move.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np

from bench.harness import seed_rng

TRAIN_SALT, SERVE_SALT, BASE_SALT = 1, 2, 3


def zipf_ids(rng: np.random.Generator, shape, a: float, vocab: int):
    return np.minimum(rng.zipf(a, size=shape) - 1, vocab - 1).astype(
        np.int32)


def train_batch(t: Dict[str, Any], seed: int, step: int, vocab: int
                ) -> Dict[str, np.ndarray]:
    """Step ``step``'s rows: ``batch`` rows of ``seq + 1`` ids, inputs and
    next-token labels.  Every (seed, step) gives other rows."""
    tok = t["tokens"]
    if tok["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {tok['dist']!r}")
    rng = seed_rng(seed, TRAIN_SALT, step)
    ids = zipf_ids(rng, (t["batch"], t["seq"] + 1), tok["a"], vocab)
    return {"inputs": ids[:, :-1], "labels": ids[:, 1:]}


def _lengths(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


@dataclasses.dataclass
class Arrival:
    due: float                      # seconds after the traffic starts
    prefix: int                     # which shared prefix
    prompt: List[int]
    max_new: int


def serve_work(t: Dict[str, Any], span_s: float):
    """The mix's work for a span: gaps, prefix choices, user-part and
    output lengths, drawn from the file's ``base_seed`` alone."""
    arr = t["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = int(math.ceil(arr["rate_per_s"] * span_s * 1.25)) + 8
    base = seed_rng(t["base_seed"], BASE_SALT)
    gaps = base.exponential(1.0 / arr["rate_per_s"], size=n)
    pre = t["prompt"]["shared_prefix"]
    pop = 1.0 / np.arange(1, pre["count"] + 1) ** pre["zipf_s"]
    prefix = base.choice(pre["count"], size=n, p=pop / pop.sum())
    return (gaps, prefix, _lengths(base, t["prompt"]["user"], n),
            _lengths(base, t["output"], n))


def serve_schedule(t: Dict[str, Any], seed: int, vocab: int,
                   span_s: float) -> List[Arrival]:
    """Arrivals over ``span_s`` seconds.

    The multiset of gaps, prefix choices, user-part and output lengths
    (``serve_work``) is the same for every ``seed``; the seed permutes
    each of them and draws every token id.  So seeds differ in order and
    content, not in the amount of work."""
    pre = t["prompt"]["shared_prefix"]
    rng = seed_rng(seed, SERVE_SALT)
    gaps, prefix, user, out = (rng.permutation(x)
                               for x in serve_work(t, span_s))
    prefixes = rng.integers(0, vocab, size=(pre["count"], pre["length"]))
    due = np.cumsum(gaps)
    res = []
    for i in range(len(due)):
        if due[i] >= span_s:
            break
        body = rng.integers(0, vocab, size=int(user[i]))
        res.append(Arrival(float(due[i]), int(prefix[i]),
                           prefixes[prefix[i]].tolist() + body.tolist(),
                           int(out[i])))
    return res


def longest_request(t: Dict[str, Any]) -> int:
    """Most tokens (prompt + output) any request of the mix can hold."""
    return (t["prompt"]["shared_prefix"]["length"]
            + t["prompt"]["user"]["max"] + t["output"]["max"])


def shortest_request(t: Dict[str, Any]) -> int:
    return (t["prompt"]["shared_prefix"]["length"]
            + t["prompt"]["user"]["min"] + t["output"]["min"])
