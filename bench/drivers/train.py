"""Training cells: the program's jitted, donated train step, fed Zipf rows
from the seed, timed over the window; checked against the plain
reference's first steps.

Set-up builds one object, the compiled step with its state, and drives it
through the check steps with the window's own call and feed (rows that
all differ).  From those steps it keeps each step's loss, the first
gradient as the optimizer got it (AdamW's first moment after one step,
divided by 1 - b1) and, after the last check step, the change of every
weight from the seed's weights.  The window then goes on with the same
object.  Once the window has closed and the peak memory is read, the state
is freed and the reference runs the same check steps from the same
weights; the worst leaf's gap decides ``correct``.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, harness, reference, traffic
from bench.harness import Cell


# the reduction of a traced window: no program is singled out
TRACE_ARGS: Dict[str, Any] = {}


class Program:
    """The program's compiled train step for this cell, how to make its
    state from a seed, and its feed."""

    def __init__(self, cell: Cell, devs):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.configs.base import PolicyConfig, ShapeConfig
        from repro.core import policy as pol_mod
        from repro.launch.mesh import make_mesh
        from repro.optim import adamw
        from repro.train import trainer

        self.cell, self.devs = cell, devs
        t, c = cell.traffic, cell.config
        cfg = harness.program_config(c)
        harness.check_layout(cfg, c, cell.model)
        pol = t["policy"]
        policy = PolicyConfig(compute_dtype=pol["compute_dtype"],
                              param_dtype=pol["param_dtype"],
                              remat=pol["remat"],
                              zero_stage=pol["zero_stage"], attn_impl="xla")
        hp = t["optimizer"]
        optcfg = adamw.AdamWConfig(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"],
                                   eps=hp["eps"],
                                   weight_decay=hp["weight_decay"],
                                   grad_clip=hp["grad_clip"])
        shape = ShapeConfig("bench", t["seq"], t["batch"], "train")
        self.pdt = jnp.dtype(pol["param_dtype"])

        def make_state(k):
            params = harness.to_program(cell.model.init(k, c, self.pdt), c)
            return trainer.TrainState(
                params, adamw.init(params, optcfg, master_weights=(
                    pol["param_dtype"] == "bfloat16")), None)

        example = traffic.train_batch(t, 0, 0, c["vocab_size"])
        abstract = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        self.mesh = None
        if t.get("mesh"):
            mesh = make_mesh(t["mesh"], ("data", "model"), devices=devs)
            named = lambda tree: jax.tree.map(
                lambda s: NamedSharding(mesh, s), tree,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            step = trainer.jit_train_step(
                trainer.make_train_step(cfg, policy, optcfg, None,
                                        mesh=mesh, shape=shape),
                abstract, cfg, policy, mesh, example)
            state_sh = named(trainer.state_specs(abstract, cfg, policy,
                                                 dict(mesh.shape)))
            self.batch_sh = named(pol_mod.batch_specs(example, policy,
                                                      dict(mesh.shape)))
            self.mesh = mesh
        else:
            # as launch/train.py: jitted, the state donated
            step = jax.jit(trainer.make_train_step(cfg, policy, optcfg, None,
                                                   shape=shape),
                           donate_argnums=(0,))
            state_sh = jax.tree.map(
                lambda _: jax.sharding.SingleDeviceSharding(devs[0]),
                abstract)
            self.batch_sh = jax.tree.map(
                lambda _: jax.sharding.SingleDeviceSharding(devs[0]),
                example)
        self.init_state = jax.jit(make_state, out_shardings=state_sh)
        batch_abs = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            example, self.batch_sh)
        state_abs = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            abstract, state_sh)
        self.step = step.lower(state_abs, batch_abs).compile()
        self.grad1, self.delta = program_readings(cell, c, hp["b1"])

    def feed(self, seed: int, step: int):
        t, c = self.cell.traffic, self.cell.config
        return jax.device_put(traffic.train_batch(t, seed, step,
                                                  c["vocab_size"]),
                              self.batch_sh)

    def check_steps(self, seed: int):
        """The state from the seed, driven through the check steps with
        the window's own call and feed; returns it with the readings."""
        key = harness.seed_key(seed)
        state = self.init_state(key)
        prog: Dict[str, Any] = {"loss": []}
        for s in range(self.cell.traffic["check_steps"]):
            state, metrics = self.step(state, self.feed(seed, s))
            prog["loss"].append(float(metrics["loss"]))
            if s == 0:
                prog["grad1"] = {k: float(v) for k, v in
                                 self.grad1(state.opt.m).items()}
        prog["delta"] = {k: float(v) for k, v in
                         self.delta(state.params, key).items()}
        return state, prog


def program_readings(cell: Cell, c, b1: float):
    """Jitted readers of the program's state: leaf norms of the first
    gradient (from AdamW's first moment) and of the weights' change."""
    model = cell.model
    pdt = jnp.dtype(cell.traffic["policy"]["param_dtype"])

    def grad1(opt_m):
        flat = harness.from_program(opt_m, c)
        return reference.leaf_norms({k: v / (1.0 - b1)
                                     for k, v in flat.items()})

    def delta(params, key):
        flat = harness.from_program(params, c)
        w0 = model.init(key, c, pdt)
        return reference.leaf_norms({k: flat[k].astype(jnp.float32)
                                     - w0[k].astype(jnp.float32)
                                     for k in flat})

    return jax.jit(grad1), jax.jit(delta)


def gaps(prog: Dict[str, float], ref: Dict[str, float],
         keep=None) -> float:
    """Worst leaf's |prog - ref| over max(ref, median leaf of ref)."""
    names = [k for k in ref if keep is None or keep(k)]
    med = float(np.median([ref[k] for k in names]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in names)


def compare(prog: Dict[str, Any], ref: Dict[str, Any], rule: float
            ) -> Dict[str, float]:
    """The numbers ``correct`` is judged by (see ``bench/limits``).

    Leaves whose reference gradient is under ``rule`` times the median
    leaf's move by round-off alone under Adam; they are left out of the
    change."""
    g = ref["grad1"]
    med = float(np.median(list(g.values())))
    moving = lambda k: g[k] >= rule * med
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["loss"], ref["loss"])),
        "grad_gap": gaps(prog["grad1"], ref["grad1"]),
        "delta_gap": gaps(prog["delta"], ref["delta"], moving),
    }


def reference_readings(cell: Cell, seed: int, devs, mesh, mm_name: str
                       ) -> Dict[str, Any]:
    t, c = cell.traffic, cell.config
    batches = [traffic.train_batch(t, seed, s, c["vocab_size"])
               for s in range(t["check_steps"])]
    shardings = None
    if mesh is not None:
        shardings = _reference_shardings(cell, mesh)
        batches = [jax.device_put(b, shardings[1]) for b in batches]
    else:
        batches = [jax.device_put(b, devs[0]) for b in batches]
    with jax.default_matmul_precision("highest"):
        return reference.train_readings(
            cell.model, c, t["optimizer"], harness.seed_key(seed), batches,
            reference.MM[mm_name], shardings=shardings)


def _reference_shardings(cell: Cell, mesh):
    """The reference's weights split over every chip of the mesh along
    their largest axis that divides evenly, the rows of the batch over the
    data axis: the plain model, laid out only so that it fits."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size
    axes = tuple(mesh.axis_names)

    def spec(shape):
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if shape[i] % n == 0:
                return P(*[axes if j == i else None
                           for j in range(len(shape))])
        return P()

    ps = {k: NamedSharding(mesh, spec(s))
          for k, s in cell.model.shapes(cell.config).items()}
    bs = {k: NamedSharding(mesh, P(axes, None)) for k in ("inputs",
                                                           "labels")}
    return ps, bs


def run(cell: Cell, devs, seed: int, seconds: float, trace_dir,
        t_start: float, counter) -> Dict[str, Any]:
    t, c = cell.traffic, cell.config
    program = Program(cell, devs)
    harness.log("train step compiled")
    state, prog = program.check_steps(seed)
    harness.log("check steps done; window opens")

    # --- the window: the same object, the same call and feed
    tokens_per_step = t["batch"] * t["seq"]
    step = t["check_steps"]
    nxt = program.feed(seed, step)
    jax.block_until_ready((state, nxt))
    counter.counting = True
    with harness.profile(trace_dir):
        t0 = time.monotonic()
        setup_s = t0 - t_start
        with harness.span(trace_dir, "bench:window"):
            prev, n = None, 0
            while True:
                with harness.span(trace_dir, "bench:train_step_call"):
                    state, metrics = program.step(state, nxt)
                step += 1
                n += 1
                with harness.span(trace_dir, "bench:data_feed"):
                    nxt = program.feed(seed, step)
                if prev is not None:
                    with harness.span(trace_dir, "bench:wait_step"):
                        prev["loss"].block_until_ready()
                prev = metrics
                if time.monotonic() - t0 >= seconds:
                    break
            with harness.span(trace_dir, "bench:wait_step"):
                jax.block_until_ready((state, metrics))
            window_s = time.monotonic() - t0
    counter.counting = False
    peak = harness.peak_bytes(devs)
    losses_finite = bool(np.isfinite(float(metrics["loss"])))
    mesh = program.mesh
    del state, metrics, prev, nxt, program
    gc.collect()

    harness.log("window closed")
    ref = reference_readings(cell, seed, devs, mesh, "float32")
    readings = compare(prog, ref, cell.limits.get("leaf_rule", 1e-3))
    harness.log("reference compared")
    return {
        "values": {"train_tokens_per_s": n * tokens_per_step / window_s,
                   "setup_s": setup_s},
        "readings": readings, "ok_extra": losses_finite,
        "attempted": n, "failed": 0 if losses_finite else 1,
        "peak_bytes": peak, "compiles_in_window": counter.n,
        "compiles_named": counter.names,
        "meas": {"kind": "train", "chips": cell.chips,
                 "window_s": window_s, "steps": n,
                 "step_flops": flops.train_step(c, t["batch"], t["seq"])},
        "program": prog, "reference": ref,
    }
