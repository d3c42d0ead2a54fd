"""repro.train subsystem: Trainer determinism, telemetry, resumable
checkpoints (bit-equality + config fingerprint), fault-injected recovery
continuity, registry completeness, and MALI-vs-Naive gradient parity on
the full LM loss."""
import dataclasses
import json
import warnings

import jax
import numpy as np
import pytest

from repro.analysis.rules.r004_registry import missing_interface
from repro.analysis.trace_audit import run_train_audit
from repro.configs import smoke_config
from repro.core.ode_block import OdeSettings
from repro.data.synthetic import DataConfig, make_batch
from repro.launch.train import main as train_main
from repro.models import init_lm, lm_loss
from repro.train import (CompressedLoop, ConfigMismatchError, JsonlEmitter,
                         MemoryEmitter, MetricsEmitter, StandardLoop,
                         StdoutEmitter, TRAIN_LOOPS, Trainer, TrainerConfig,
                         TrainLoop, config_fingerprint, get_train_loop,
                         make_emitter, restore_train_state, state_tree)

TINY = dict(steps=6, global_batch=4, seq_len=16, ode_steps=2,
            ckpt_every=2, keep=5, log_every=100, emit="memory")


def tiny_trainer(**kw) -> Trainer:
    return Trainer(TrainerConfig(**{**TINY, **kw}))


@pytest.fixture(scope="module")
def clean_run():
    """One uninterrupted tiny MALI run, shared as the reference trace."""
    t = tiny_trainer()
    final = t.train()
    assert final == TINY["steps"]
    return t


# ---------------------------------------------------------------------------
# Determinism + telemetry
# ---------------------------------------------------------------------------

def test_same_seed_same_trace(clean_run):
    again = tiny_trainer()
    again.train()
    assert again.loss_trace() == clean_run.loss_trace()
    assert all(np.isfinite(v) for v in again.loss_trace())


def test_step_records_account_for_the_odes(clean_run):
    recs = [clean_run.records[s] for s in sorted(clean_run.records)]
    assert [r.step for r in recs] == list(range(TINY["steps"]))
    # fixed-step solves: the feval budget is static, identical every step
    assert recs[0].fevals > 0
    assert len({(r.fevals, r.accepted, r.rejected) for r in recs}) == 1
    assert recs[0].rejected == 0
    row = recs[0].as_row()
    assert set(row) == {"step", "loss", "lr", "grad_norm", "wall_s",
                        "batch_s", "dispatch_s", "wait_s", "readback_s",
                        "compiles", "fevals", "accepted", "rejected",
                        "moe_routed", "moe_dropped"}


def test_memory_emitter_collects_every_step(clean_run):
    assert isinstance(clean_run.emitter, MemoryEmitter)
    assert len(clean_run.emitter.records) == TINY["steps"]
    assert [r.step for r in clean_run.emitter.records] == \
        list(range(TINY["steps"]))


def test_jsonl_emitter_round_trips(tmp_path, clean_run):
    path = str(tmp_path / "metrics.jsonl")
    em = JsonlEmitter(path)
    for rec in clean_run.emitter.records:
        em.emit(rec)
    em.close()
    rows = [json.loads(line) for line in open(path)]
    assert len(rows) == TINY["steps"]
    assert rows[0]["loss"] == pytest.approx(clean_run.loss_trace()[0])


def test_make_emitter_validation():
    assert isinstance(make_emitter("stdout"), StdoutEmitter)
    with pytest.raises(ValueError, match="jsonl"):
        make_emitter("jsonl")          # needs a path
    with pytest.raises(ValueError, match="unknown"):
        make_emitter("bogus")


# ---------------------------------------------------------------------------
# Checkpointing: bit-equality, fingerprint, fault-injected recovery
# ---------------------------------------------------------------------------

def _fingerprint(t: Trainer):
    tc = t.config
    return config_fingerprint(t.cfg, t.opt_cfg, arch=tc.arch, loop=tc.loop,
                              microbatches=tc.microbatches, seed=tc.seed,
                              global_batch=tc.global_batch,
                              seq_len=tc.seq_len)


def test_checkpoint_restores_bit_identical_state(tmp_path):
    t = tiny_trainer(ckpt_dir=str(tmp_path / "run"))
    final = t.train()
    got = restore_train_state(str(tmp_path / "run"), t.state,
                              _fingerprint(t))
    assert got is not None
    step, restored, meta = got
    assert step == final
    assert meta["final"] is True
    live = jax.tree_util.tree_leaves(state_tree(t.state))
    back = jax.tree_util.tree_leaves(state_tree(restored))
    assert len(live) == len(back)
    for a, b in zip(live, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fault_injection_reproduces_clean_loss_trace(tmp_path, clean_run):
    fired = []

    def hook(step):
        if step == 3 and not fired:
            fired.append(step)
            raise RuntimeError("injected node failure")

    t = Trainer(TrainerConfig(**TINY, ckpt_dir=str(tmp_path / "faulty"),
                              max_failures=2), step_hook=hook)
    final = t.train()
    assert final == TINY["steps"]
    assert fired == [3]
    # recomputed post-checkpoint steps overwrite their first attempt, so
    # the recovered trace equals the uninterrupted run's, bit-for-bit
    assert t.loss_trace() == clean_run.loss_trace()


def test_resume_under_different_config_refuses(tmp_path):
    d = str(tmp_path / "run")
    tiny_trainer(ckpt_dir=d).train()
    other = tiny_trainer(ckpt_dir=d, ode_method="naive")
    with pytest.raises(ConfigMismatchError, match="ode"):
        other.train()
    # deliberately NOT one of run_with_recovery's retried exception types
    assert not issubclass(ConfigMismatchError,
                          (RuntimeError, ValueError, OSError))


# ---------------------------------------------------------------------------
# Loop/emitter registries (R004 surface)
# ---------------------------------------------------------------------------

def test_train_loop_registry():
    assert isinstance(get_train_loop("standard"), StandardLoop)
    assert isinstance(get_train_loop("compressed"), CompressedLoop)
    assert set(TRAIN_LOOPS) == {"standard", "compressed"}
    with pytest.raises(ValueError, match="unknown"):
        get_train_loop("bogus")
    for loop in TRAIN_LOOPS.values():
        assert missing_interface(type(loop), TrainLoop) == []
    for emitter_cls in (StdoutEmitter, JsonlEmitter, MemoryEmitter):
        assert missing_interface(emitter_cls, MetricsEmitter) == []


def test_compressed_loop_trains_and_carries_ef():
    t = tiny_trainer(steps=3, loop="compressed")
    assert t.train() == 3
    assert t.state.ef is not None
    assert all(np.isfinite(v) for v in t.loss_trace())


def test_microbatch_accumulation_trains():
    t = tiny_trainer(steps=3, microbatches=2)
    assert t.train() == 3
    assert all(np.isfinite(v) for v in t.loss_trace())


# ---------------------------------------------------------------------------
# Gradient parity + legacy-path hygiene
# ---------------------------------------------------------------------------

def test_mali_matches_naive_gradients_on_lm_loss():
    def grads(method, solver):
        cfg = smoke_config("qwen3-1.7b",
                           OdeSettings(mode="per_block", method=method,
                                       solver=solver, n_steps=2))
        params = init_lm(jax.random.PRNGKey(0), cfg)
        batch = make_batch(cfg, DataConfig(seed=0, global_batch=2,
                                           seq_len=8), 0)
        return jax.grad(lm_loss)(params, cfg, batch)

    g_mali = grads("mali", "alf")
    g_naive = grads("naive", "alf")
    for a, b in zip(jax.tree_util.tree_leaves(g_mali),
                    jax.tree_util.tree_leaves(g_naive)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-5)


def test_train_flow_avoids_legacy_odeint():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        tiny_trainer(steps=2).train()
    legacy = [w for w in caught
              if issubclass(w.category, DeprecationWarning)
              and "odeint" in str(w.message)]
    assert legacy == []


# ---------------------------------------------------------------------------
# CLI + static analysis hooks
# ---------------------------------------------------------------------------

def test_cli_smoke_and_resume(tmp_path, capsys):
    argv = ["--smoke", "--steps", "6", "--global-batch", "4",
            "--seq-len", "16", "--ckpt-dir", str(tmp_path / "cli"),
            "--log-every", "100"]
    train_main(argv)
    assert "final_step=6" in capsys.readouterr().out
    train_main(argv)    # restores the final checkpoint, runs 0 new steps
    assert "final_step=6" in capsys.readouterr().out


def test_run_train_audit_is_clean():
    combos, failures, retrace = run_train_audit()
    assert combos >= 4
    assert failures == []
    assert retrace == {"train:step/mali-smoke": 1}


def test_trainer_config_is_value_hashable():
    a = TrainerConfig(**TINY)
    b = TrainerConfig(**TINY)
    assert a == b and hash(a) == hash(b)
    assert dataclasses.replace(a, ode_method="naive") != a
