"""Names of the program's layers in a profiler trace.

Device scopes name the operations of one layer inside a jitted program:
``jax.named_scope`` puts the name on every operation traced under it, and
XLA keeps it in each instruction's ``op_name`` metadata through ``jit``,
``scan``, ``checkpoint`` and the backward pass.  Where scopes nest, the
innermost names the operation (the final norm inside ``head`` is
``norm``).

Host spans name what the serving loop does between device programs;
``jax.profiler.TraceAnnotation`` writes them into the profiler's trace on
the device planes' clock and costs a no-op when no profiler is running.

Capture both with ``jax.profiler.trace(<dir>)`` around a run and open the
trace in Perfetto or TensorBoard (``docs/telemetry.md``).
"""
from __future__ import annotations

import functools

import jax

# device scopes, one per layer boundary
EMBED = "embed"
NORM = "norm"
ATTN_PROJ = "attn_proj"       # q/k/v projections and the output projection
ATTN_CORE = "attn_core"       # scores, softmax and P.V, in every path
MLP = "mlp"
HEAD = "head"                 # output projection + cross entropy / argmax
OPTIMIZER = "optimizer"       # clip, schedule, AdamW
SSD = "ssd"                   # the Mamba-2 state-space scan
KV_GATHER = "kv_gather"       # paged pool -> dense cache view
KV_SCATTER = "kv_scatter"     # new K/V -> paged pool

DEVICE_SCOPES = (EMBED, NORM, ATTN_PROJ, ATTN_CORE, MLP, HEAD, OPTIMIZER,
                 SSD, KV_GATHER, KV_SCATTER)

# host spans: every span the program writes starts with this prefix
SPAN_PREFIX = "repro:"
SERVE_PHASES = ("admit", "plan", "inputs", "device", "tokens")


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``.

    A fresh scope is entered on each call, so nested and concurrent
    traces keep their own name stacks."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def serve_span(phase: str) -> jax.profiler.TraceAnnotation:
    """The host span ``repro:serve.<phase>`` of one serving-loop phase."""
    return jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}serve.{phase}")
