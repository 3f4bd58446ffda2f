"""mamba2-780m's program against the benchmark's plain float32 reference
(``bench/configs/mamba2-780m.py``, the paper's minimal SSD listing) on
seeded random weights, at a tiny size, on four virtual CPU devices: the
ZeRO-3 train step's loss and first gradient over a (data=4, model=1)
mesh, and the SSD core sharded over the data axis, unsharded, and as the
reference computes it.  Each runs in a subprocess, where the device
count can be set before JAX starts."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import os
D_MODEL, VOCAB = {d_model}, {vocab}
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import numpy as np
import jax, jax.numpy as jnp
from bench import harness

jax.config.update("jax_default_matmul_precision", "highest")
c = harness.load_json(harness.BENCH / "configs" / "mamba2-780m.json")
# bench/tests/cells.tiny's sizes (every width divided down), or wider
c.update(d_model=D_MODEL, n_layer=2, vocab_size=VOCAB)
c["mamba2_layer"].update(d_state=16, headdim=16, chunk_size=32)
ref = harness.load_module(harness.BENCH / "configs" / "mamba2-780m.py")
mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(4, 1),
                         ("data", "model"))
"""


def _run(body: str, d_model: int = 64, vocab: int = 500) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    src = textwrap.dedent(PRELUDE).format(
        root=ROOT, d_model=d_model, vocab=vocab) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", src], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_ssd_sharded_and_chunked_match_the_reference():
    """The chunk scan, alone and under ``shard_map`` over the data axis,
    against ``ssd_minimal_discrete``; one group (as mamba2-780m) and two.

    Tolerance 1e-5 normwise: all three compute in float32 at highest
    precision, in different orders (a carried state per chunk against
    the listing's segment sums), over sums of at most 128 terms; measured
    below 1e-6.  The sharded scan runs the same code on each row's chip,
    so it matches the unsharded one to 1e-6."""
    out = _run("""
        from repro.models.ssm import ssd_chunked, ssd_sharded
        m = c["mamba2_layer"]
        Bsz, S, P, N, L = 8, 64, m["headdim"], m["d_state"], m["chunk_size"]
        res = {}
        for H, G in ((4, 1), (4, 2)):
            ks = jax.random.split(jax.random.PRNGKey(H * 10 + G), 5)
            x = jax.random.normal(ks[0], (Bsz, S, H, P))
            dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)) - 2)
            A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                            maxval=2.7))
            Bm = jax.random.normal(ks[3], (Bsz, S, G, N)) * 0.5
            Cm = jax.random.normal(ks[4], (Bsz, S, G, N)) * 0.5
            y, h = ssd_chunked(x, dt, A, Bm, Cm, chunk=L)
            with mesh:
                ys, hs = jax.jit(lambda *a: ssd_sharded(
                    *a, chunk=L, mesh=mesh, dp_axes=("data",),
                    tp_axis="model"))(x, dt, A, Bm, Cm)
            rep = lambda t: jnp.repeat(t, H // G, axis=2)
            want = ref.ssd(x * dt[..., None], A * dt, rep(Bm), rep(Cm), L)
            nrm = lambda a, b: float(jnp.linalg.norm(a - b)
                                     / jnp.linalg.norm(b))
            res[f"G{G}"] = {"chunked": nrm(y, want), "sharded": nrm(ys, want),
                            "sharded_vs_chunked": nrm(ys, y),
                            "state": nrm(hs, h)}
        print(json.dumps(res))
    """)
    for g, r in out.items():
        assert r["chunked"] < 1e-5, (g, r)
        assert r["sharded"] < 1e-5, (g, r)
        assert r["sharded_vs_chunked"] < 1e-6, (g, r)
        assert r["state"] < 1e-6, (g, r)


@pytest.mark.parametrize("groups", [1, 2])
def test_ssd_chunked_gradients_match_the_reference(groups):
    """``jax.grad`` of a seeded scalar of ``ssd_chunked``'s (y, h_final)
    with respect to x, dt, A, B and C against the same scalar of the
    reference: y from ``ssd_minimal_discrete``, h_final as the listing's
    ``decay_states`` over the whole sequence.  The chunk scan recomputes
    each chunk's tiles in its backward; this holds the recompute to the
    plain math.

    Tolerance 1e-5 normwise per input, as for the forward: float32 at
    highest precision on both sides, differing in the order of sums;
    measured under 3e-6.  A backward that scales dt's gradient by 1.001
    fails it."""
    out = _run(f"""
        from repro.models.ssm import ssd_chunked
        m = c["mamba2_layer"]
        Bsz, S, P, N, L = 8, 64, m["headdim"], m["d_state"], m["chunk_size"]
        H, G = 4, {groups}
        ks = jax.random.split(jax.random.PRNGKey(H * 10 + G), 7)
        x = jax.random.normal(ks[0], (Bsz, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)) - 2)
        A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
        Bm = jax.random.normal(ks[3], (Bsz, S, G, N)) * 0.5
        Cm = jax.random.normal(ks[4], (Bsz, S, G, N)) * 0.5
        wy = jax.random.normal(ks[5], (Bsz, S, H, P))
        wh = jax.random.normal(ks[6], (Bsz, H, N, P))

        def program(x, dt, A, Bm, Cm):
            y, h = ssd_chunked(x, dt, A, Bm, Cm, chunk=L)
            return jnp.sum(y * wy) + jnp.sum(h * wh)

        def reference(x, dt, A, Bm, Cm):
            rep = lambda t: jnp.repeat(t, H // G, axis=2)
            y = ref.ssd(x * dt[..., None], A * dt, rep(Bm), rep(Cm), L)
            acum = jnp.cumsum(A * dt, axis=1)                  # (b, l, h)
            h = jnp.einsum("blhn,blh,blhp->bhnp", rep(Bm),
                           jnp.exp(acum[:, -1:] - acum) * dt, x,
                           precision="highest")
            return jnp.sum(y * wy) + jnp.sum(h * wh)

        args = (x, dt, A, Bm, Cm)
        got = jax.jit(jax.grad(program, argnums=range(5)))(*args)
        want = jax.jit(jax.grad(reference, argnums=range(5)))(*args)
        nrm = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        print(json.dumps({{k: nrm(a, b) for k, a, b in
                          zip(("x", "dt", "A", "B", "C"), got, want)}}))
    """)
    assert max(out.values()) < 1e-5, out


# (d_model, vocab, the leaves ZeRO-3 splits over the four chips): at
# cells.tiny's sizes every leaf is under the 64K elements below which the
# policy replicates; twice as wide, the embedding and the projections
# over d_in are split
SIZES = {"tiny": (64, 500, set()),
         "wide": (128, 1000, {"embed", "in_z", "in_x", "out_proj"})}


@pytest.mark.parametrize("size", sorted(SIZES))
def test_zero3_train_step_matches_the_reference(size):
    """One ZeRO-3 train step of the program (``make_train_step`` +
    ``jit_train_step``, the state sharded over data=4 and donated) from
    the reference's seeded weights, on rows of the cell's traffic: its
    loss, and its first gradient as AdamW got it (the first moment over
    1 - b1, after global-norm clipping), leaf by leaf, against
    ``jax.value_and_grad`` of the reference and the same clipping.

    The program computes in float32 here, so the two differ only by the
    order of float32 sums (sharded reductions, the chunk scan against the
    listing, chunked against whole cross entropy): loss to 1e-5
    relative, each gradient leaf to 1e-4 normwise (measured under 1e-5:
    float32 round-off over a few hundred terms per sum).  In bfloat16 the
    chip's ``correct`` compares the same readings at the cell's widths
    (``bench/limits``)."""
    out = _run("""
        from bench import reference, traffic
        from repro.configs.base import PolicyConfig, ShapeConfig
        from repro.optim import adamw
        from repro.train import trainer
        t = harness.load_json(harness.BENCH / "traffic"
                              / "train.zero3-4x1.json")
        t.update(seq=64, batch=8)
        hp = t["optimizer"]
        cfg = harness.program_config(c)
        harness.check_layout(cfg, c, ref)
        policy = PolicyConfig(compute_dtype="float32",
                              param_dtype="float32", remat="block",
                              zero_stage=3, attn_impl="xla")
        optcfg = adamw.AdamWConfig(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"],
                                   eps=hp["eps"],
                                   weight_decay=hp["weight_decay"],
                                   grad_clip=hp["grad_clip"])
        key = harness.seed_key(2**33 + 17)
        w0 = ref.init(key, c, jnp.float32)
        batch = traffic.train_batch(t, 2**33 + 17, 0, c["vocab_size"])

        def ref_loss(p):
            h = ref.hidden(p, batch["inputs"], c, reference.mm_f32)
            return reference.mean_xent(h, p["embed"], batch["labels"],
                                       reference.mm_f32)
        want_loss, g = jax.jit(jax.value_and_grad(ref_loss))(w0)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        want_g = {k: x * jnp.minimum(1.0, hp["grad_clip"] / gn)
                  for k, x in g.items()}

        params = harness.to_program(w0, c)
        state = trainer.TrainState(params, adamw.init(params, optcfg), None)
        step = trainer.jit_train_step(
            trainer.make_train_step(cfg, policy, optcfg, None, mesh=mesh,
                                    shape=ShapeConfig("t", 64, 8, "train")),
            jax.eval_shape(lambda: state), cfg, policy, mesh, batch)
        state, metrics = step(state, batch)
        sharded = [k for k, v in harness.from_program(state.params,
                                                      c).items()
                   if len(v.sharding.device_set) == 4
                   and not v.sharding.is_fully_replicated]
        got_g = {k: v / (1 - hp["b1"])
                 for k, v in harness.from_program(state.opt.m, c).items()}
        nrm = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        print(json.dumps({
            "loss": float(metrics["loss"]), "want_loss": float(want_loss),
            "grad": {k: nrm(got_g[k], want_g[k]) for k in want_g},
            "sharded": sorted(sharded)}))
    """, *SIZES[size][:2])
    assert abs(out["loss"] - out["want_loss"]) <= 1e-5 * out["want_loss"], out
    worst = max(out["grad"].items(), key=lambda kv: kv[1])
    assert worst[1] < 1e-4, (worst, out["grad"])
    assert set(out["sharded"]) == SIZES[size][2], out["sharded"]
