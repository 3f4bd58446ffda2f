"""Each cell's harness, run on the CPU at a tiny size with the chip check
skipped: a sound run comes out correct, and with the timed path broken
underneath (once for each fault the cell can have) it comes out not
correct.  The limits are the cells' own, from ``bench/limits``."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

import run
from bench.tests.cells import devices, tiny
from repro.optim import adamw
from repro.serve import engine
from repro.train import trainer

SEED = 2**33 + 17          # beyond 32 bits, as the driver's seeds are


def _run(name, seconds=1.0):
    cell = tiny(name)
    out, res = run.run_cell(cell, devices(cell), SEED, seconds, False)
    return out, res


def _patch_step(monkeypatch, wrap):
    orig = trainer.make_train_step

    def make(*a, **k):
        return wrap(orig(*a, **k), *a, **k)
    monkeypatch.setattr(trainer, "make_train_step", make)


TRAIN = ["qwen2-0.5b.train.s4k", "mamba2-780m.train.zero3-4x1"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_sound_run_is_correct(name):
    out, res = _run(name)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("name", TRAIN)
def test_train_state_unchanged_is_caught(monkeypatch, name):
    def wrap(real, *a, **k):
        def step(state, batch):
            _, metrics = real(state, batch)
            return state, metrics
        return step
    _patch_step(monkeypatch, wrap)
    out, _ = _run(name)
    assert not out["correct"]
    assert out["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", TRAIN)
def test_train_half_batch_left_out_is_caught(monkeypatch, name):
    def wrap(real, *a, **k):
        def step(state, batch):
            return real(state, jax.tree.map(
                lambda x: x[:x.shape[0] // 2], batch))
        return step
    _patch_step(monkeypatch, wrap)
    out, _ = _run(name)
    assert not out["correct"], out["compared"]


def test_train_exchange_between_chips_left_out_is_caught(monkeypatch):
    """Every chip's gradient stays its own: no reduction over the data
    axis, the update made from what the first chip computed."""
    def wrap(real, cfg, policy, optcfg, schedcfg=None, mesh=None,
             shape=None):
        loss_fn = trainer.make_loss_fn(cfg, policy, None,
                                       seq_len=shape.seq_len)

        def local(params, b):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, b)
            return g, loss

        def step(state, batch):
            g, loss = jax.shard_map(
                local, mesh=mesh, in_specs=(P(), P("data")),
                out_specs=(P(), P()), check_vma=False)(state.params, batch)
            params, opt, om = adamw.apply(state.params, g, state.opt,
                                          optcfg)
            return trainer.TrainState(params, opt, None), dict(om, loss=loss)
        return step
    _patch_step(monkeypatch, wrap)
    out, _ = _run("mamba2-780m.train.zero3-4x1")
    assert not out["correct"], out["compared"]


SERVE = "qwen2-0.5b.serve.chat"


def test_serve_sound_run_is_correct():
    out, res = _run(SERVE, seconds=4.0)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert res["compiles_in_window"] == 0, res["compiles_named"]
    assert set(out["metrics"]) == {"serve_output_tokens_per_s", "ttft_p95_ms",
                                   "itl_p95_ms", "setup_s"}
    # due times, not submit times: every TTFT counts the generator's lag
    assert all(x >= 0 for x in res["meas"]["gen_lag_s"])


def test_serve_token_altered_is_caught(monkeypatch):
    orig = engine.AsyncServeEngine._paged_step_fn

    def altered(self, *a):
        nxt, logits, pages = orig(self, *a)
        return (nxt + 1) % self.cfg.vocab_size, logits, pages
    monkeypatch.setattr(engine.AsyncServeEngine, "_paged_step_fn", altered)
    out, _ = _run(SERVE, seconds=4.0)
    assert not out["correct"], out["compared"]
