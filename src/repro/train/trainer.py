"""Training step builder: model + policy -> jit-able, shardable train_step.

The step builder realizes the paper's §V-4 software ladder plus the
beyond-paper rungs:

  * zero_stage=0, fp32            -> "DP"    (params+states replicated)
  * zero_stage=0, hierarchical    -> "DDP"   (overlappable bucketed reduce)
  * compute_dtype=bf16            -> "mixed precision"
  * zero_stage=1/3                -> "sharded training" (ZeRO)
  * grad_compression="int8_ef"    -> int8 EF on the slow pod axis
  * grad_accum>1                  -> microbatch scan (memory headroom)

All distribution is expressed as PartitionSpecs (from ``core.policy``) on a
single jit program; the only explicit ``shard_map`` is the optional
manual-pod gradient exchange (hierarchical/compressed), with every other
axis left on GSPMD auto sharding.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, PolicyConfig, ShapeConfig
from repro.core import hierarchy, policy as pol
from repro.models import lm, scopes
from repro.models.transformer import ParallelCtx, RunCtx
from repro.optim import adamw, schedule


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------
class TrainState:
    """Plain pytree container: params + optimizer state (+ EF residual)."""

    def __init__(self, params, opt, ef_residual=None):
        self.params = params
        self.opt = opt
        self.ef_residual = ef_residual

    def tree_flatten(self):
        return (self.params, self.opt, self.ef_residual), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def _dt(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def make_run_ctx(cfg: ModelConfig, policy: PolicyConfig,
                 mesh=None) -> RunCtx:
    moe_impl = "sorted"
    if (cfg.moe is not None and policy.ep and mesh is not None
            and policy.tp_axis in getattr(mesh, "shape", {})
            and mesh.shape[policy.tp_axis] > 1
            and cfg.moe.n_experts % mesh.shape[policy.tp_axis] == 0):
        moe_impl = "ep"
    return RunCtx(
        compute_dtype=_dt(policy.compute_dtype),
        attn_impl=policy.attn_impl,
        moe_impl=moe_impl,
        remat=policy.remat,
        pctx=ParallelCtx(mesh=mesh, dp_axes=policy.dp_axes,
                         tp_axis=policy.tp_axis,
                         fsdp_experts=(policy.zero_stage >= 3)),
    )


def init_state(key, cfg: ModelConfig, policy: PolicyConfig,
               optcfg: adamw.AdamWConfig, *, n_pods: int = 1) -> TrainState:
    params = lm.init_lm(key, cfg, dtype=_dt(policy.param_dtype))
    opt = adamw.init(params, optcfg,
                     master_weights=(policy.param_dtype == "bfloat16"))
    ef = None
    if policy.grad_compression == "int8_ef" and n_pods > 1:
        ef = jax.tree.map(
            lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params)
    return TrainState(params, opt, ef)


def state_specs(state: TrainState, cfg: ModelConfig, policy: PolicyConfig,
                mesh_axes: Mapping[str, int]) -> TrainState:
    """PartitionSpecs for a TrainState (params, adam moments, residual)."""
    pspec = pol.param_specs(state.params, cfg, policy, mesh_axes)
    mspec = pol.opt_state_specs(state.params, cfg, policy, mesh_axes)
    opt_spec = adamw.AdamWState(
        step=P(), m=mspec, v=mspec,
        master=(mspec if state.opt.master is not None else None))
    ef_spec = None
    if state.ef_residual is not None:
        ef_spec = jax.tree.map(
            lambda s: P(*(("pod",) + tuple(s))), mspec)
    return TrainState(pspec, opt_spec, ef_spec)


# ---------------------------------------------------------------------------
# loss / grads
# ---------------------------------------------------------------------------
def make_loss_fn(cfg: ModelConfig, policy: PolicyConfig, mesh=None,
                 seq_len: Optional[int] = None) -> Callable:
    """``seq_len`` is accepted for callers that build from a cell's
    shape; the loss does not depend on it."""
    ctx = make_run_ctx(cfg, policy, mesh)
    big_vocab = cfg.padded_vocab >= 32_768

    def loss_fn(params, batch):
        chunk = 0
        if big_vocab:
            S = batch["labels"].shape[1]
            for c in (512, 256, 128, 64, 1):
                if S % c == 0:
                    chunk = c
                    break
        return lm.lm_loss(params, batch, cfg, ctx, xent_chunk=chunk)

    return loss_fn


def _accum_grads(loss_fn, params, batch, n_accum: int):
    """Microbatch gradient accumulation via scan (constant memory)."""
    if n_accum <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, loss, metrics

    def reshape(x):
        return x.reshape((n_accum, x.shape[0] // n_accum) + x.shape[1:])

    micro = jax.tree.map(reshape, batch)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        acc, loss_acc = carry
        (loss, metrics), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
        return (acc, loss_acc + loss), metrics

    (grads, loss_sum), metrics = jax.lax.scan(body, (zeros, 0.0), micro)
    grads = jax.tree.map(lambda g: g / n_accum, grads)
    last_metrics = jax.tree.map(lambda m: m[-1], metrics)
    return grads, loss_sum / n_accum, last_metrics


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, policy: PolicyConfig,
                    optcfg: adamw.AdamWConfig = adamw.AdamWConfig(),
                    schedcfg: Optional[schedule.ScheduleConfig] = None,
                    mesh=None,
                    shape: Optional[ShapeConfig] = None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    Lowers/compiles under any mesh; all sharding comes from in/out specs
    (see ``jit_train_step`` and ``launch.dryrun``).  ``shape`` is
    accepted for callers that build from a cell's shape; the step takes
    its shapes from the batch it is traced with.
    """
    loss_fn = make_loss_fn(cfg, policy, mesh)
    mesh_axes = dict(getattr(mesh, "shape", {})) if mesh is not None else {}
    use_pod_exchange = (
        "pod" in mesh_axes and mesh_axes["pod"] > 1
        and (policy.grad_compression == "int8_ef"))

    @scopes.scoped(scopes.OPTIMIZER)
    def optimizer_update(state: TrainState, grads, metrics, loss):
        lr = None
        if schedcfg is not None:
            lr = schedule.lr_at(state.opt.step, schedcfg)
        params, opt, om = adamw.apply(state.params, grads, state.opt,
                                      optcfg, lr=lr)
        metrics = dict(metrics, **om, loss=loss)
        return params, opt, metrics

    if not use_pod_exchange:
        def train_step(state: TrainState, batch):
            grads, loss, metrics = _accum_grads(
                loss_fn, state.params, batch, policy.grad_accum)
            params, opt, metrics = optimizer_update(
                state, grads, metrics, loss)
            return TrainState(params, opt, state.ef_residual), metrics
        return train_step

    # ---- manual-pod exchange: grads computed per pod, then int8-EF ----
    # inside the manual-pod region the batch is per-pod: dp excludes pod.
    # ep=False: a nested shard_map under a partially-manual mesh trips the
    # jax 0.8 MLIR verifier; the EP layout and the compressed exchange are
    # therefore mutually exclusive for now (documented in DESIGN.md).
    pod_policy = dataclasses.replace(
        policy, dp_axes=tuple(a for a in policy.dp_axes if a != "pod"),
        ep=False)
    pod_loss_fn = make_loss_fn(cfg, pod_policy, mesh)

    def train_step(state: TrainState, batch):

        def pod_body(params, ef, pod_batch):
            grads, loss, metrics = _accum_grads(
                pod_loss_fn, params, pod_batch, policy.grad_accum)
            ef_local = jax.tree.map(lambda r: r[0], ef)
            grads, ef_new = hierarchy.allreduce_int8_ef(
                grads, ef_local, "pod")
            loss = jax.lax.pmean(loss, "pod")
            metrics = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"),
                                   metrics)
            ef_new = jax.tree.map(lambda r: r[None], ef_new)
            return grads, ef_new, loss, metrics

        n_batch = jax.tree.leaves(batch)[0].shape[0]
        bspec = jax.tree.map(
            lambda x: P(*(("pod",) + (None,) * (x.ndim - 1))), batch)
        ef_spec = jax.tree.map(lambda r: P("pod"), state.ef_residual)
        gspec = jax.tree.map(lambda p: P(), state.params)
        grads, ef_new, loss, metrics = jax.shard_map(
            pod_body, mesh=mesh,
            in_specs=(gspec, ef_spec, bspec),
            out_specs=(gspec, ef_spec, P(), jax.tree.map(
                lambda _: P(), {"loss": 0, "xent": 0, "aux": 0})),
            axis_names=frozenset({"pod"}), check_vma=False,
        )(state.params, state.ef_residual, batch)
        params, opt, metrics = optimizer_update(state, grads, metrics, loss)
        return TrainState(params, opt, ef_new), metrics

    return train_step


# ---------------------------------------------------------------------------
# jit wiring (specs in/out) for the step over a mesh of several devices
# ---------------------------------------------------------------------------
def jit_train_step(train_step, state: TrainState, cfg: ModelConfig,
                   policy: PolicyConfig, mesh, example_batch):
    """``train_step`` jitted with the policy's shardings on ``mesh``; the
    state argument is donated."""
    mesh_axes = dict(mesh.shape)
    sspec = state_specs(state, cfg, policy, mesh_axes)
    bspec = pol.batch_specs(example_batch, policy, mesh_axes)
    in_shardings = (TrainState(sspec.params, sspec.opt, sspec.ef_residual),
                    bspec)
    out_shardings = (in_shardings[0], None)
    return jax.jit(train_step,
                   in_shardings=jax.tree.map(
                       lambda s: jax.sharding.NamedSharding(mesh, s)
                       if s is not None else None, in_shardings,
                       is_leaf=lambda x: isinstance(x, P) or x is None),
                   out_shardings=jax.tree.map(
                       lambda s: jax.sharding.NamedSharding(mesh, s)
                       if s is not None else None, out_shardings,
                       is_leaf=lambda x: isinstance(x, P) or x is None),
                   donate_argnums=(0,))


# ---------------------------------------------------------------------------
# run tracking — per-step loss / step_s / tokens-per-s into repro.tracking
# ---------------------------------------------------------------------------
class StepTracker:
    """Adapter from the training loop to the tracking plane.

    Call :meth:`step` once per optimizer step with the step's metrics
    dict; it derives wall-clock ``step_s`` and ``tokens_per_s`` from an
    injectable clock and logs one tracking row per step (plus a system
    sample every ``system_every`` steps).  All methods are no-ops when
    no run is active, so the loop needs no tracking conditionals.
    """

    def __init__(self, tokens_per_step: int, run=None, *,
                 clock=None, system_every: int = 50):
        import time as _time
        from repro import tracking
        self.run = run if run is not None else tracking.current_run()
        self.tokens_per_step = tokens_per_step
        self.clock = clock or _time.time
        self.system_every = max(int(system_every), 1)
        self._last_t: Optional[float] = None
        self._n = 0
        self._loss: Optional[float] = None
        self._tok_s = 0.0

    def step(self, step: int, metrics: Mapping[str, Any]) -> None:
        if self.run is None:
            return
        now = self.clock()
        row: Dict[str, Any] = {
            k: float(v) for k, v in metrics.items()
            if isinstance(v, (int, float)) or hasattr(v, "item")}
        if self._last_t is not None:
            step_s = now - self._last_t
            row["step_s"] = step_s
            row["tokens_per_s"] = (self.tokens_per_step / step_s
                                   if step_s > 0 else 0.0)
            self._tok_s = row["tokens_per_s"]
        self._last_t = now
        self._n += 1
        self._loss = row.get("loss", self._loss)
        self.run.log(row, step=step + 1)
        if self._n % self.system_every == 0:
            self.run.log_system()

    def summary(self) -> Dict[str, Any]:
        """Final-row metrics; also merged into the run summary."""
        out: Dict[str, Any] = {"steps": self._n,
                               "tokens_per_s": self._tok_s}
        if self._loss is not None:
            out["final_loss"] = self._loss
        if self.run is not None:
            self.run.log_summary(out)
        return out
