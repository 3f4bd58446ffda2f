"""Plumbing shared by every cell: finding the cell's files by name, the
program's configuration, the chip check, seeds, the compile counter, the
per-layer metric readers and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

  bench/configs/<config>.json    sizes as run, source, program mapping
  bench/configs/<config>.py      plain float32 reference + seeded weights
  bench/traffic/<traffic>.json   the mix: its ``kind`` names the driver
  bench/drivers/<kind>.py        how a kind of traffic is run and checked
  bench/limits/<cell>.json       the limits ``correct`` is judged by
  bench/metrics/<metric>.py      reader of one per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
_T0 = time.monotonic()


def log(msg: str) -> None:
    """A progress line on standard error, with the seconds since start."""
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_json(path) -> Any:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots
    and dashes)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    limits: Dict[str, Any]          # bench/limits/<cell>.json
    model: Any                      # bench/configs/<config>.py
    bench: Dict[str, Any]           # BENCHMARK.json

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfile = ROOT / conf["file"]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(cfile), traffic_name=w["traffic"],
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json"),
        model=load_module(cfile.with_suffix(".py")), bench=bench)


def lookup(c: Dict[str, Any], key: str) -> Any:
    """``a/b`` reads ``c["a"]["b"]``."""
    for part in key.split("/"):
        c = c[part]
    return c


# ---------------------------------------------------------------------------
# the program's side of a configuration
# ---------------------------------------------------------------------------
def program_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` with every mapped size taken from the
    configuration file (the file is what is run)."""
    from repro.configs import get_config
    p = c["program"]
    base = get_config(p["arch"])
    kw = {f: lookup(c, k) for f, k in p["fields"].items()}
    for group, fields in p.get("groups", {}).items():
        kw[group] = dataclasses.replace(
            getattr(base, group),
            **{f: lookup(c, k) for f, k in fields.items()})
    kinds = set(base.pattern)
    if len(kinds) != 1:
        raise ValueError(f"{p['arch']}: mixed block pattern {kinds}; map "
                         f"it in the configuration file")
    kw["block_pattern"] = (base.pattern[0],) * kw.get("n_layers",
                                                      base.n_layers)
    return dataclasses.replace(base, **kw)


def to_program(flat: Dict[str, Any], c: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's flat weights as the program's nested tree."""
    tree: Dict[str, Any] = {}
    for name, path in c["program"]["params"].items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = flat[name]
    return tree


def from_program(tree: Dict[str, Any], c: Dict[str, Any]) -> Dict[str, Any]:
    """The program's tree (or one shaped like it) as the flat names."""
    return {name: lookup(tree, path)
            for name, path in c["program"]["params"].items()}


def check_layout(cfg, c: Dict[str, Any], model) -> None:
    """The program's parameter tree has exactly the mapped leaves, each of
    the reference's shape."""
    import jax
    from repro.models import lm
    got = jax.eval_shape(lambda k: lm.init_lm(k, cfg), jax.random.PRNGKey(0))
    leaves = {"/".join(str(getattr(k, "key", k)) for k in path): v.shape
              for path, v in jax.tree_util.tree_leaves_with_path(got)}
    want = {c["program"]["params"][n]: tuple(s)
            for n, s in model.shapes(c).items()}
    if leaves != want:
        diff = sorted(set(leaves.items()) ^ set(want.items()))
        raise ValueError(f"program parameters differ from the "
                         f"configuration's map: {diff[:6]}")


# ---------------------------------------------------------------------------
# chip, seeds, compiles
# ---------------------------------------------------------------------------
def require_chips(n: int):
    """The first ``n`` TPU devices; raises NoChip otherwise (never falls
    back to the CPU)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                     f"this benchmark runs on the chip only")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def seed_key(seed: int, *salt: int):
    """A JAX key for ``seed`` (any whole number, also beyond 32 bits)."""
    import jax
    word = np.random.SeedSequence([int(seed), *salt]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


class CompileCounter:
    """Counts executables JAX builds (compiled or read from the persistent
    cache) while ``counting`` is set."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.counting = False
        self.n = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.counting and event == self.EVENT:
            self.n += 1
            self.names.append(kw.get("fun_name", "?"))


# ---------------------------------------------------------------------------
# peaks and shares
# ---------------------------------------------------------------------------
def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def share(part: float, whole: float, name: str) -> float:
    """``part / whole`` in percent; a share of a peak or a roofline above
    100 means the work or the time was miscounted, so it is an error."""
    pct = 100.0 * part / whole
    if not 0.0 <= pct <= 100.0:
        raise ValueError(f"{name}: {pct:.3f}% of the peak ({part:.6g} of "
                         f"{whole:.6g}); the work or the time is miscounted")
    return pct


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------
def per_layer(cell: Cell, meas: Dict[str, Any]) -> Dict[str, Any]:
    """Every per-layer metric of ``BENCHMARK.json`` that this cell reports,
    each read by ``bench/metrics/<name>.py``; a reader that finds nothing
    returns None and the metric is left out."""
    reports = {m["name"] for m in cell.bench["end_to_end"]
               if cell.name in m.get("workloads", [cell.name])}
    out = {}
    for m in cell.bench["per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]) \
                or m["moves"] not in reports:
            continue
        val = load_module(BENCH / "metrics" / f"{m['name']}.py").read(meas)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def end_to_end(cell: Cell, values: Dict[str, float]) -> Dict[str, Any]:
    out = {}
    for m in cell.bench["end_to_end"]:
        if cell.name in m.get("workloads", [cell.name]):
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return out


def judge(readings: Dict[str, float], limits: Dict[str, Any]):
    """``{name: {"value", "limit"}}`` for every limit, and whether all
    hold.  A reading that is missing or not a number fails."""
    compared, ok = {}, True
    for name, lim in limits["limits"].items():
        v = readings.get(name)
        good = v is not None and np.isfinite(v) and v <= lim["limit"]
        ok = ok and bool(good)
        compared[name] = {"value": None if v is None else float(v),
                          "limit": lim["limit"]}
    return compared, ok


def device_info(devs, *, peak_bytes: int, trace: Optional[dict] = None):
    import jax
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak_bytes)}
    if trace is not None:
        info["busy_s"] = trace["busy_s"]
        info["window_s"] = trace["window_s"]
    return info


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def emit(result: Dict[str, Any]) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output (``compared`` last)."""
    for name, c in result["compared"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "compared"]
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)


# ---------------------------------------------------------------------------
# host spans and the profiler (both no-ops in an untraced run)
# ---------------------------------------------------------------------------
class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def span(trace_dir, name: str):
    """A host span ``name`` in the profiler's trace when tracing."""
    if not trace_dir:
        return _Null()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def profile(trace_dir):
    if not trace_dir:
        return _Null()
    import jax
    return jax.profiler.trace(str(trace_dir))
