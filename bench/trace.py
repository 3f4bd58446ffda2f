"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: per-chip busy and idle time, exposed collective time, the
device time of each jitted program, the idle gaps labelled by the host
span that was open, and the breakdown printed with a traced run.

Only JAX's own reader (``jax.profiler.ProfileData``) is used.  Device
planes are ``/device:TPU:<n>``; their line ``XLA Ops`` holds one event per
operation run and ``XLA Modules`` one per jitted program run.  The
benchmark's host spans (``jax.profiler.TraceAnnotation`` named
``bench:<what>``) sit on the host plane on the same clock; the span
``bench:window`` marks the measured window.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all")
WINDOW = "bench:window"
SPAN_PREFIX = "bench:"

Interval = Tuple[float, float]          # seconds on the trace's clock


@dataclasses.dataclass
class Chip:
    ops: List[Tuple[float, float, str]]      # (start, end, op name)
    modules: List[Tuple[float, float, str]]  # (start, end, program name)


@dataclasses.dataclass
class Trace:
    chips: Dict[int, Chip]
    spans: List[Tuple[float, float, str]]    # host spans named bench:*


def find_xplane(root) -> pathlib.Path:
    files = sorted(pathlib.Path(root).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    chips: Dict[int, Chip] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            chips[int(m.group(1))] = Chip(
                ops=_events(lines.get(OPS_LINE)),
                modules=_events(lines.get(MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in _events(ln)
                          if e[2].startswith(SPAN_PREFIX)]
    return Trace(chips, sorted(spans))


def _events(line) -> List[Tuple[float, float, str]]:
    """(start, end, name) of a line's events; an operation's name is the
    HLO instruction's name (``%fusion.12``), not its whole text."""
    if line is None:
        return []
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
             e.name.split(" = ", 1)[0])
            for e in line.events]


def leaves(ev: List[Tuple[float, float, str]]
           ) -> List[Tuple[float, float, str]]:
    """The events that hold no other event: a ``while`` or ``call`` op
    spans the operations of its body, which are on the same line."""
    ev = sorted(ev, key=lambda e: (e[0], -e[1]))
    end = [(float("inf"), float("inf"), "")]
    return [e for e, nxt in zip(ev, ev[1:] + end)
            if not (nxt[0] < e[1] and nxt[1] <= e[1])]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(iv: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def total(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def minus(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (sorted, disjoint) intervals ``a`` not covered by the
    (sorted, disjoint) ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    return minus([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def window_of(tr: Trace) -> Interval:
    w = [(a, b) for a, b, n in tr.spans if n == WINDOW]
    if w:
        return w[0]
    ends = [x for c in tr.chips.values() for e in c.ops for x in e[:2]]
    if not ends:
        raise ValueError("trace holds no device operation and no window")
    return min(ends), max(ends)


def label_gap(gap: Interval, spans: Sequence[Tuple[float, float, str]]
              ) -> str:
    """The host span (other than the window) that covers most of ``gap``;
    among equal covers the shortest, i.e. the innermost."""
    best, key = "no host span", (0.0, 0.0)
    for a, b, name in spans:
        if name == WINDOW:
            continue
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > 0 and (ov, -(b - a)) > key:
            best, key = name[len(SPAN_PREFIX):], (ov, -(b - a))
    return best


def reduce(tr: Trace, *, programs: Sequence[str] = (), top: int = 10,
           live: Optional[str] = None) -> Dict:
    """Per-chip busy/idle, exposed collectives, program time and gaps.

    ``programs``: substrings of jitted program names whose device time is
    summed (``program_s``) and between whose consecutive runs the idle
    gaps are measured (``program_gaps_s``), counting only gaps that lie
    inside a host span named ``live`` when that is given."""
    if not tr.chips:
        raise ValueError("trace holds no TPU device plane")
    lo, hi = window_of(tr)
    win = hi - lo
    busy, idle, exposed = {}, {}, {}
    for cid, chip in tr.chips.items():
        ops = clip([(a, b) for a, b, _ in chip.ops], lo, hi)
        u = union(ops)
        busy[cid] = total(u)
        idle[cid] = 1.0 - busy[cid] / win
        leaf = leaves(chip.ops)
        coll = union(clip([(a, b) for a, b, n in leaf
                           if COLLECTIVE.search(n)], lo, hi))
        comp = union(clip([(a, b) for a, b, n in leaf
                           if not COLLECTIVE.search(n)], lo, hi))
        exposed[cid] = total(minus(coll, comp))
    n = len(tr.chips)
    by_op: Dict[str, float] = defaultdict(float)
    for chip in tr.chips.values():
        for a, b, name in leaves(chip.ops):
            if b > lo and a < hi:
                by_op[name] += (min(b, hi) - max(a, lo)) / n
    first = tr.chips[min(tr.chips)]
    u0 = union(clip([(a, b) for a, b, _ in first.ops], lo, hi))
    g0 = sorted(gaps(u0, lo, hi), key=lambda g: g[0] - g[1])
    out = {
        "window_s": win,
        "busy_s": sum(busy.values()) / n,
        "busy_by_chip_s": busy,
        "idle_share_worst": max(idle.values()),
        "exposed_collective_s_worst": max(exposed.values()),
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[label_gap(g, tr.spans), g[1] - g[0]]
                          for g in g0[:top]],
        },
    }
    if programs:
        mods = sorted((a, b) for a, b, name in first.modules
                      if any(p in name for p in programs)
                      and b > lo and a < hi)
        out["program_s"] = total(clip(mods, lo, hi))
        out["program_runs"] = len(mods)
        pg = [(b0, a1) for (_, b0), (a1, _) in zip(mods, mods[1:])
              if a1 > b0]
        if live is not None:
            spans = [(a, b) for a, b, name in tr.spans if name == live]
            pg = [g for g in pg if any(a <= g[0] and g[1] <= b
                                       for a, b in spans)]
        # the device may run other programs in such a gap: only the part
        # with no operation on the chip counts as idle
        out["program_gaps_s"] = [total(minus([g], u0)) for g in pg]
    return out
