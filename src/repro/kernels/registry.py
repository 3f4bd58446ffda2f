"""Tuned-config registry: measured-best kernel block configs.

The autotuner (``repro.kernels.autotune``) sweeps block-size candidates
per (kernel, shape-bucket, dtype, variant) cell and persists the winners
here; the dispatch layer (``repro.kernels.ops``) and the step builders
(``train.trainer`` / ``serve.engine``) resolve their block sizes from
this registry instead of hardcoded defaults.

Key format (one flat string so the JSON file is greppable and diffable):

    <kernel>|<dim>=<bucket>,...|<dtype>|<variant>

e.g. ``flash_attention|d=64,g=4,s=256,t=256|float32|causal``.  Sequence
dims are bucketed to the next power of two so a 384-token prefill reuses
the 512 cell; head/feature dims are exact (they change the VMEM working
set shape, not just its size).

Registry file schema (``results/tuned_configs.json`` by default, or
``$REPRO_TUNED_CONFIGS``):

    {"version": 1,
     "schema_version": 1,
     "configs": {"<key>": {"blocks": {"block_q": 128, ...},
                           "us": 812.4,          # best measured us/call
                           "default_us": 991.2,  # default-config us/call
                           "n_candidates": 9,
                           "backend": "cpu"}}}

Lookups that miss fall back to the caller's defaults — an empty or absent
registry reproduces the pre-tuning behaviour exactly.  An entry whose
``backend`` differs from ``jax.default_backend()`` is a miss.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Any, Dict, Mapping, Optional, Tuple

import jax

DEFAULT_PATH = os.path.join("results", "tuned_configs.json")
ENV_VAR = "REPRO_TUNED_CONFIGS"

# bucketed dims (next pow2 >= floor); others exact.  ``b`` (decode batch)
# buckets from 1 so tiny serving batches don't all collapse into one cell
_BUCKET_FLOOR = {"s": 32, "t": 32, "b": 1}


def bucket_pow2(n: int, floor: int = 32) -> int:
    """Next power of two >= n (>= floor): shape buckets for seq dims."""
    b = floor
    while b < n:
        b *= 2
    return b


def make_key(kernel: str, *, dtype: str, variant: str = "",
             **dims: int) -> str:
    """Canonical registry key; seq/batch dims (s, t, b) are bucketed to
    the next power of two, every other dim (head/feature widths) stays
    exact."""
    parts = []
    for name in sorted(dims):
        v = int(dims[name])
        if name in _BUCKET_FLOOR:
            v = bucket_pow2(v, _BUCKET_FLOOR[name])
        parts.append(f"{name}={v}")
    return f"{kernel}|{','.join(parts)}|{dtype}|{variant}"


@dataclasses.dataclass
class TunedEntry:
    """One registry cell: winning blocks + the measurement behind them."""
    blocks: Dict[str, int]
    us: float = 0.0                   # best candidate, measured us/call
    default_us: float = 0.0           # default config, measured us/call
    n_candidates: int = 0
    backend: str = ""

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, js: Mapping[str, Any]) -> "TunedEntry":
        return cls(blocks={k: int(v) for k, v in js["blocks"].items()},
                   us=float(js.get("us", 0.0)),
                   default_us=float(js.get("default_us", 0.0)),
                   n_candidates=int(js.get("n_candidates", 0)),
                   backend=str(js.get("backend", "")))

    @property
    def speedup(self) -> float:
        """Measured default/best ratio (1.0 when either side is missing)."""
        if self.us <= 0 or self.default_us <= 0:
            return 1.0
        return self.default_us / self.us


class Registry:
    """In-memory tuned-config table with JSON round-trip."""

    def __init__(self, entries: Optional[Dict[str, TunedEntry]] = None,
                 path: str = ""):
        self.entries: Dict[str, TunedEntry] = dict(entries or {})
        self.path = path

    # ------------------------------------------------------------- access --
    def get(self, key: str) -> Optional[TunedEntry]:
        return self.entries.get(key)

    def put(self, key: str, entry: TunedEntry) -> None:
        self.entries[key] = entry

    def lookup(self, kernel: str, defaults: Mapping[str, int], *,
               dtype: str, variant: str = "", **dims: int) -> Dict[str, int]:
        """Tuned blocks for the cell, or ``defaults`` on a miss.

        An entry tuned on another backend is a miss: blocks that the CPU
        interpreter ranked say nothing about the TPU."""
        entry = self.get(make_key(kernel, dtype=dtype, variant=variant,
                                  **dims))
        if entry is None or entry.backend not in ("",
                                                  jax.default_backend()):
            return dict(defaults)
        out = dict(defaults)
        out.update(entry.blocks)
        return out

    def __len__(self) -> int:
        return len(self.entries)

    # ---------------------------------------------------------- round-trip --
    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path or DEFAULT_PATH
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        js = {"version": 1,
              "schema_version": 1,
              "configs": {k: e.to_json()
                          for k, e in sorted(self.entries.items())}}
        with open(path, "w") as f:
            json.dump(js, f, indent=2)
            f.write("\n")
        self.path = path
        return path

    @classmethod
    def load(cls, path: str) -> "Registry":
        with open(path) as f:
            js = json.load(f)
        entries = {k: TunedEntry.from_json(v)
                   for k, v in js.get("configs", {}).items()}
        return cls(entries, path=path)


# ---------------------------------------------------------------------------
# process-wide active registry (dispatch-time resolution)
# ---------------------------------------------------------------------------
_lock = threading.Lock()
_active: Optional[Registry] = None
_loaded = False


def set_registry(reg: Optional[Registry]) -> None:
    """Install ``reg`` as the process-wide registry (None -> defaults)."""
    global _active, _loaded
    with _lock:
        _active = reg
        _loaded = True


def reset_registry() -> None:
    """Drop the cached registry; next lookup re-reads env/disk."""
    global _active, _loaded
    with _lock:
        _active = None
        _loaded = False


def get_registry() -> Optional[Registry]:
    """The active registry: set_registry() > $REPRO_TUNED_CONFIGS >
    ``results/tuned_configs.json`` if present > None (pure defaults)."""
    global _active, _loaded
    with _lock:
        if _loaded:
            return _active
        path = os.environ.get(ENV_VAR, "") or DEFAULT_PATH
        if os.path.exists(path):
            try:
                _active = Registry.load(path)
            except (OSError, ValueError, KeyError):
                _active = None       # malformed file: behave as untuned
        _loaded = True
        return _active


# ---------------------------------------------------------------------------
# per-kernel resolvers (the shape-keyed lookups the stack calls)
# ---------------------------------------------------------------------------
def fit_block(block: int, dim: int) -> int:
    """Largest size <= ``block`` that divides ``dim``.

    Pow2 bucketing means a tuned block can come from a neighbouring
    sequence length (e.g. blocks tuned at the 256 bucket applied to
    S=192); the kernels assert divisibility, so tuned values are fitted
    to the actual dim before dispatch.  Bounded: at most ``block``
    decrements (block <= 512 everywhere)."""
    b = max(1, min(int(block), int(dim)))
    while dim % b:
        b -= 1
    return b


def _dtype_name(dtype) -> str:
    import numpy as np
    try:
        return np.dtype(dtype).name
    except TypeError:
        return getattr(dtype, "name", None) or str(dtype)


def attention_variant(causal: bool, window: int) -> str:
    if window > 0:
        return "window"
    return "causal" if causal else "full"


def attention_blocks(S: int, T: int, D: int, G: int, dtype,
                     causal: bool, window: int,
                     defaults: Tuple[int, int] = (256, 256),
                     kernel: str = "flash_attention") -> Tuple[int, int]:
    """(block_q, block_k) for an attention cell; defaults on miss."""
    reg = get_registry()
    if reg is None:
        return defaults
    out = reg.lookup(kernel, {"block_q": defaults[0], "block_k": defaults[1]},
                     dtype=_dtype_name(dtype),
                     variant=attention_variant(causal, window),
                     s=S, t=T, d=D, g=G)
    return fit_block(out["block_q"], S), fit_block(out["block_k"], T)


def decode_attention_blocks(B: int, T: int, D: int, G: int, dtype,
                            causal: bool = True, window: int = 0,
                            defaults: Tuple[int, int] = (1, 256),
                            kernel: str = "decode_attention"
                            ) -> Tuple[int, int]:
    """(block_q, block_k) for the (B, 1, cache_len) decode shape.

    Decode cells key on the *batch* bucket and the cache length — the
    working set is the KV history, not the single query token (S is
    always 1, so it is omitted from the key): the serving engine's
    decode-step batching and the autotuner share the bucket vocabulary
    ``decode_attention|b=<batch>,t=<cache_len>,d=…,g=…``.  ``block_q``
    is fitted to 1 on a miss (one query row); ``block_k`` tiles the
    cache scan.
    """
    reg = get_registry()
    if reg is None:
        return 1, fit_block(defaults[1], T)
    out = reg.lookup(kernel,
                     {"block_q": defaults[0], "block_k": defaults[1]},
                     dtype=_dtype_name(dtype),
                     variant=attention_variant(causal, window),
                     b=B, t=T, d=D, g=G)
    return fit_block(out["block_q"], 1), fit_block(out["block_k"], T)


def ssd_chunk(S: int, H: int, P: int, G: int, N: int, dtype,
              default: int = 256) -> int:
    reg = get_registry()
    if reg is None:
        return default
    return fit_block(
        reg.lookup("ssd", {"chunk": default}, dtype=_dtype_name(dtype),
                   s=S, h=H, p=P, g=G, n=N)["chunk"], S)


def rglru_block(S: int, W: int, dtype, default: int = 128) -> int:
    reg = get_registry()
    if reg is None:
        return default
    return fit_block(
        reg.lookup("rglru", {"block_seq": default},
                   dtype=_dtype_name(dtype), s=S, w=W)["block_seq"], S)


def kernel_speedups(reg: Optional[Registry] = None) -> Dict[str, float]:
    """Per-kernel measured speedup (default_us / best_us), averaged over
    every tuned cell of that kernel — the calibration signal
    ``core.costmodel.CalibratedCost`` layers onto the analytic terms.
    Uses the active registry when ``reg`` is None."""
    reg = reg if reg is not None else get_registry()
    if reg is None:
        return {}
    acc: Dict[str, Tuple[float, int]] = {}
    for key, entry in reg.entries.items():
        kernel = key.split("|", 1)[0]
        s = entry.speedup
        if s <= 0:
            continue
        tot, n = acc.get(kernel, (0.0, 0))
        acc[kernel] = (tot + s, n + 1)
    return {k: tot / n for k, (tot, n) in acc.items() if n}
