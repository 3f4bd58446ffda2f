"""Device time by the program's layers: from a compiled program's HLO text
to ``{instruction: scope}``, and from a profiler trace to seconds per
scope.

The program names its layers with ``jax.named_scope`` (``embed``,
``attn_core``, ``mlp``, ...); XLA keeps the name as a path component of
each instruction's ``op_name`` metadata, wrapped by transforms in the
backward pass (``transpose(jvp(attn_core))``).  The trace names an
operation by its instruction only (``%fusion.12``), so the map is built
from the program's optimized HLO (``compiled.as_text()``):

* a plain instruction takes the innermost known scope of its ``op_name``;
* a ``fusion`` takes the scope of the dot, convolution or custom-call it
  holds (searching nested fusions too), the one with the most output
  elements where there are several: a fusion's own metadata is its root's,
  and the root of a fused matmul is often the layer scan's
  ``dynamic-update-slice``.  A fusion that holds none takes its own
  ``op_name``'s scope, else its root's;
* an instruction with no known scope is ``unscoped``.

The map is keyed by HLO module name, the name the trace's ``XLA Modules``
line gives a program (less its fingerprint), so two programs' ``fusion.12``
cannot collide.  The scope names are the yardstick's own copy; a test
checks them against the program's ``repro.models.scopes``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

from bench import trace as tr

KNOWN = ("embed", "norm", "attn_proj", "attn_core", "mlp", "head",
         "optimizer", "ssd", "kv_gather", "kv_scatter")
UNSCOPED = "unscoped"
MATMUL = ("dot", "convolution", "custom-call")

_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_DIMS = re.compile(r"[a-z]\w*\[([\d,]*)\]")


class _Instr(NamedTuple):
    opcode: str
    elements: int                 # output elements, summed over a tuple
    calls: Optional[str]          # the computation a fusion calls
    op_name: Optional[str]
    root: bool


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost path component of ``op_name`` that names a known
    scope, also inside transform wrappers; None if there is none."""
    for comp in reversed((op_name or "").split("/")):
        core = comp.split("(")[-1].rstrip(")")
        if core in KNOWN and not comp.startswith(("jit(", "pjit(")):
            return core
    return None


def _elements(shape: str) -> int:
    n = 0
    for dims in _DIMS.findall(shape):
        k = 1
        for d in filter(None, dims.split(",")):
            k *= int(d)
        n += k
    return n


def _parse(text: str) -> Tuple[str, Dict[str, Dict[str, _Instr]]]:
    """(module name, {computation: {instruction: _Instr}})."""
    module, comps, cur = "", {}, None
    for line in text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        if line and not line[0].isspace():
            m = _COMPUTATION.match(line)
            cur = comps.setdefault(m.group(1), {}) if m else None
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        calls = _CALLS.search(line)
        op = _OP_NAME.search(line)
        cur[m.group(2)] = _Instr(m.group(4), _elements(m.group(3)),
                                 calls.group(1) if calls else None,
                                 op.group(1) if op else None,
                                 bool(m.group(1)))
    return module, comps


def op_scopes(text: str) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: scope}}`` of one compiled program's HLO
    text (``compiled.as_text()``); instruction names without ``%``."""
    module, comps = _parse(text)

    def matmuls(comp: str, seen=()) -> List[_Instr]:
        out = []
        for ins in comps.get(comp, {}).values():
            if ins.opcode in MATMUL:
                out.append(ins)
            elif ins.calls and ins.calls not in seen:
                out += matmuls(ins.calls, seen + (comp,))
        return out

    def fusion_scope(ins: _Instr) -> Optional[str]:
        mm = sorted(matmuls(ins.calls), key=lambda i: -i.elements)
        if mm:
            s = scope_of(mm[0].op_name)
            if s:
                return s
        s = scope_of(ins.op_name)
        if s:
            return s
        root = [i for i in comps.get(ins.calls, {}).values() if i.root]
        return scope_of(root[0].op_name) if root else None

    out: Dict[str, str] = {}
    for instrs in comps.values():
        for name, ins in instrs.items():
            s = (fusion_scope(ins) if ins.opcode == "fusion" and ins.calls
                 else scope_of(ins.op_name))
            out[name] = s or UNSCOPED
    return {module: out}


def _module_name(event_name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def attributed(t: tr.Trace, scopes: Dict[str, Dict[str, str]]
               ) -> Iterator[Tuple[str, str, float]]:
    """``(operation, scope, seconds / chips)`` of every leaf operation in
    the window, counted as ``trace.reduce`` counts
    ``breakdown.device_ops``; an operation takes the map of the program
    whose run holds its start on that chip."""
    lo, hi = tr.window_of(t)
    n = len(t.chips)
    for chip in t.chips.values():
        mods = sorted(chip.modules)
        starts = [a for a, _, _ in mods]
        for a, b, name in tr.leaves(chip.ops):
            if b <= lo or a >= hi:
                continue
            i = bisect.bisect_right(starts, a) - 1
            table = {}
            if i >= 0 and a < mods[i][1]:
                table = scopes.get(_module_name(mods[i][2]), {})
            yield (name, table.get(name.lstrip("%"), UNSCOPED),
                   (min(b, hi) - max(a, lo)) / n)


def scope_seconds(t: tr.Trace, scopes: Dict[str, Dict[str, str]]
                  ) -> Dict[str, float]:
    """Seconds of leaf operations per scope inside the window, a mean over
    chips; operations of no known scope count under ``unscoped``."""
    out: Dict[str, float] = defaultdict(float)
    for _, scope, sec in attributed(t, scopes):
        out[scope] += sec
    return dict(out)


def top_unscoped(t: tr.Trace, scopes: Dict[str, Dict[str, str]],
                 top: int = 10) -> List[Tuple[str, float]]:
    """The operations of no known scope that took most time in the
    window."""
    by_op: Dict[str, float] = defaultdict(float)
    for name, scope, sec in attributed(t, scopes):
        if scope == UNSCOPED:
            by_op[name] += sec
    return sorted(by_op.items(), key=lambda kv: -kv[1])[:top]


def attention_core_train(c: Dict, batch: int, seq: int) -> float:
    """FLOPs of one training step's causal attention core (Q.K^T and P.V
    over the keys at or before each query), three times the forward
    pass: ``3 * B * S * L * 4 * H * hd * (S + 1) / 2``."""
    H = c["num_attention_heads"]
    hd = c["hidden_size"] // H
    return (3.0 * batch * seq * c["num_hidden_layers"] * 4 * H * hd
            * (seq + 1) / 2)
