"""Bring-up check: MALI training and ODE serving on a TPU, through the
entry points a user calls.

    python chip_smoke.py                # one chip: train + serve phases
    python chip_smoke.py --four-chips   # four chips: data-parallel train
                                        # step and a Sharded ODE solve only

Every phase runs in this one process (a chip belongs to one process at a
time) and must pass; a failed check raises and the script exits non-zero.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only after every phase passed. Without a TPU the script exits
non-zero before any phase runs.

Phases (one chip):

* **train** — ``Trainer`` with ``ode_method="mali"`` and
  ``ode_backend="pallas"`` on ``qwen3-1.7b`` at its published widths, cut
  in depth only (whole layers) to fit one v5e's 16 GB; 5 steps. Checks:
  every loss finite; the compiled step holds Mosaic kernels
  (``tpu_custom_call``); step-0 loss and gradient norm match the
  ``ode_backend="reference"`` model on the same batch, computed in this
  process. Prints step-0 loss, the steady step time (a first chip run,
  not a benchmark) and peak HBM.
* **serve** — ``launch/serve.py``'s ``serve_ode`` through the
  continuous-batching engine: 64 slots, 32-trial chunks, 256 requests at
  once. Checks: every request completed; 8 served end states match
  per-request ``solve()`` within the requests' own tolerance.

Phases (``--four-chips``): the same train step on ``make_host_mesh()``
over four devices (data=4), against step 0 on one device; and
``solve(batching=Sharded("data", PerSample()))`` over 256 rows against the
unsharded ``PerSample()`` solve, values and parameter gradients.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (ALF, MALI, AdaptiveController, PerSample,  # noqa: E402
                        Sharded, solve)
from repro.data.synthetic import DataConfig, make_batch  # noqa: E402
from repro.distributed.sharding import (batch_sharding,  # noqa: E402
                                        batch_shardings)
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import mlp_field, serve_ode  # noqa: E402
from repro.models import init_lm  # noqa: E402
from repro.optim.optimizer import global_norm  # noqa: E402
from repro.train import Trainer, TrainerConfig  # noqa: E402
from repro.train.loop import jitted_train_step, loss_and_grads  # noqa: E402

ARCH = "qwen3-1.7b"
N_PERIODS = 4          # of 28: ~0.82 B params, ~10.8 GB per the AOT compile
GLOBAL_BATCH = 4
SEQ_LEN = 512
STEPS = 5
# Parity of the step-0 numbers between two runs of one model: one bf16
# unit in the last place (2**-8 relative) for the loss; the global grad
# norm sums bf16 products over 0.8 B parameters, so 2**-6.
LOSS_RTOL = 2.0 ** -8
GNORM_RTOL = 2.0 ** -6


def require_tpu() -> None:
    """Exit non-zero unless JAX's default device is a TPU."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {platform!r}")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")
    print(f"  ok: {what}", flush=True)


def depth_cut(arch: str = ARCH, n_periods: int = N_PERIODS):
    """The published config of ``arch`` with whole layers cut away."""
    full = get_config(arch)
    cfg = dataclasses.replace(full, n_periods=n_periods)
    return cfg, f"reduced: n_periods {full.n_periods}→{n_periods}"


def trainer_config(*, global_batch: int, seq_len: int, steps: int,
                   backend: str = "pallas") -> TrainerConfig:
    return TrainerConfig(
        arch=ARCH, smoke=False, ode=True, ode_steps=2, ode_method="mali",
        ode_backend=backend, steps=steps, global_batch=global_batch,
        seq_len=seq_len, max_failures=0, emit="memory", log_every=10**9)


def step0_on_one_device(cfg, tc: TrainerConfig):
    """(loss, grad global norm) of step 0 — the Trainer's initial params
    on its batch 0 — on the default device, with no mesh."""
    cfg = cfg.with_ode(tc.ode_settings()).validate()
    params = init_lm(jax.random.PRNGKey(tc.seed), cfg)
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, DataConfig(seed=tc.seed, global_batch=tc.global_batch,
                        seq_len=tc.seq_len), 0).items()}

    def step0(p, b):
        loss, _, grads = loss_and_grads(p, b, cfg=cfg)
        return loss, global_norm(grads)

    loss, gnorm = jax.jit(step0)(params, batch)
    out = float(loss), float(gnorm)
    del params, batch
    gc.collect()
    return out


def _compiled_step_text(trainer: Trainer, tc: TrainerConfig) -> str:
    """HLO text of the compiled train step the Trainer ran."""
    cfg, mesh, state = trainer.cfg, trainer.mesh, trainer.state
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, DataConfig(seed=tc.seed, global_batch=tc.global_batch,
                        seq_len=tc.seq_len), 0).items()}
    with mesh:
        b_sh = batch_shardings(cfg, mesh, batch)
        batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
        return jitted_train_step.lower(
            state.params, state.opt, None, batch, cfg=cfg,
            opt_cfg=trainer.opt_cfg, microbatches=1, compress=False,
            zero1=mesh.size > 1).compile().as_text()


def run_trainer(cfg, tc: TrainerConfig) -> dict:
    trainer = Trainer(tc, model_config=cfg)
    trainer.train()
    recs = [trainer.records[s] for s in sorted(trainer.records)]
    hlo = _compiled_step_text(trainer, tc)
    out = {"losses": [r.loss for r in recs],
           "grad_norm0": recs[0].grad_norm,
           "step_s": [r.wall_s for r in recs],
           "kernels_in_step": hlo.count("tpu_custom_call"),
           "devices": trainer.mesh.size}
    del trainer
    gc.collect()
    return out


def peak_hbm_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def train_phase(cfg, *, global_batch: int = GLOBAL_BATCH,
                seq_len: int = SEQ_LEN, steps: int = STEPS) -> dict:
    """The MALI + Pallas Trainer run and its reference-backend step 0."""
    ref = step0_on_one_device(cfg, trainer_config(
        global_batch=global_batch, seq_len=seq_len, steps=steps,
        backend="reference"))
    run = run_trainer(cfg, trainer_config(
        global_batch=global_batch, seq_len=seq_len, steps=steps))
    return {**run, "ref_loss0": ref[0], "ref_grad_norm0": ref[1]}


def _sample(requests, n: int):
    stride = max(len(requests) // n, 1)
    return requests[::stride][:n]


def serve_phase(*, slots: int = 64, chunk_steps: int = 32,
                n_requests: int = 256, d_state: int = 32,
                rtol: float = 1e-3, atol: float = 1e-4, n_check: int = 8,
                seed: int = 0) -> dict:
    """``serve_ode`` all at once, and per-request ``solve()`` on a sample:
    the worst ``|served - solve| / (atol + rtol |solve|)`` (<= 1 passes)."""
    report, eng, requests = serve_ode(
        batch=slots, d_state=d_state, chunk_steps=chunk_steps,
        n_requests=n_requests, rtol=rtol, atol=atol, seed=seed)
    cfg = requests[0].config
    ctrl = AdaptiveController(cfg.rtol, cfg.atol, cfg.max_steps)
    ref = jax.jit(lambda z0: solve(eng.f, eng.params, z0, cfg.t0, cfg.t1,
                                   solver=eng.config.solver,
                                   controller=ctrl).ys)
    worst = 0.0
    for req in _sample(requests, n_check):
        want = ref(jax.tree_util.tree_map(jnp.asarray, req.z0))
        for got_leaf, want_leaf in zip(
                jax.tree_util.tree_leaves(eng.results[req.rid]),
                jax.tree_util.tree_leaves(want)):
            want_leaf = np.asarray(want_leaf)
            err = np.abs(np.asarray(got_leaf) - want_leaf)
            worst = max(worst, float(np.max(
                err / (cfg.atol + cfg.rtol * np.abs(want_leaf)))))
    return {"report": report,
            "completed": sum(r.completed for r in eng.records),
            "n_requests": n_requests, "n_checked": min(n_check, n_requests),
            "worst_err_over_tol": worst}


def sharded_solve_phase(*, n_rows: int = 256, d_state: int = 32,
                        seed: int = 0) -> dict:
    """Sharded(data, PerSample) on the host mesh vs unsharded PerSample:
    max relative difference of end states and of parameter gradients."""
    f, params = mlp_field(np.random.default_rng(seed), d_state)
    rng = np.random.default_rng(seed + 1)
    z0 = {"y": jnp.asarray(rng.standard_normal((n_rows, d_state)),
                           jnp.float32),
          "scale": jnp.asarray(np.repeat(10.0 ** rng.uniform(
              0.0, 1.0, (n_rows, 1)), d_state, 1), jnp.float32)}

    def loss_and_ys(p, z, batching):
        ys = solve(f, p, z, 0.0, 1.0, solver=ALF(eta=0.9, backend="pallas"),
                   controller=AdaptiveController(1e-3, 1e-4, 512),
                   gradient=MALI(), batching=batching).ys
        return jnp.sum(ys["y"] ** 2), ys

    def run(batching, z):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_and_ys(p, z, batching), has_aux=True))(params)

    (_, ys_ref), g_ref = run(PerSample(), z0)
    mesh = make_host_mesh()
    with mesh:
        z_sh = jax.device_put(z0, batch_sharding(mesh, "data"))
        (_, ys_sh), g_sh = run(Sharded(axis="data", inner=PerSample()), z_sh)

    def rel(a, b):
        a = np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(a)])
        b = np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(b)])
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

    return {"devices": mesh.size, "rows": n_rows,
            "ys_rel": rel(ys_sh, ys_ref), "grad_rel": rel(g_sh, g_ref)}


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def report_train(run: dict, ref_loss0: float, ref_gnorm0: float,
                 what: str) -> None:
    losses, times = run["losses"], run["step_s"]
    print(f"train: {len(losses)} steps on {run['devices']} device(s); "
          f"losses {losses}")
    print(f"train: step-0 loss {losses[0]!r} vs {what} {ref_loss0!r} "
          f"(rel {_rel(losses[0], ref_loss0):.3e}, tol {LOSS_RTOL:.3e}); "
          f"grad norm {run['grad_norm0']!r} vs {ref_gnorm0!r} "
          f"(rel {_rel(run['grad_norm0'], ref_gnorm0):.3e}, "
          f"tol {GNORM_RTOL:.3e})")
    steady = statistics.median(times[1:]) if len(times) > 1 else times[0]
    print(f"train: first chip run, not a benchmark: step 0 (with compile) "
          f"{times[0]!r} s, steady step {steady!r} s "
          f"(median of steps 1..{len(times) - 1}, block_until_ready)")
    print(f"train: tpu_custom_call in compiled step: "
          f"{run['kernels_in_step']}")
    check(all(np.isfinite(x) for x in losses), "every step's loss is finite")
    if jax.default_backend() == "tpu":
        check(run["kernels_in_step"] > 0,
              "compiled train step holds Mosaic kernels (tpu_custom_call)")
    else:
        check(run["kernels_in_step"] == 0,
              "off the TPU the kernels are interpreted, never compiled")
    check(_rel(losses[0], ref_loss0) <= LOSS_RTOL,
          f"step-0 loss matches {what}")
    check(_rel(run["grad_norm0"], ref_gnorm0) <= GNORM_RTOL,
          f"step-0 grad norm matches {what}")


def one_chip() -> None:
    cfg, reduced = depth_cut()
    print(f"train: {ARCH} d_model {cfg.d_model} heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_head {cfg.d_head} d_ff {cfg.d_ff} vocab "
          f"{cfg.vocab_size} {cfg.param_dtype}; {reduced}; "
          f"global_batch {GLOBAL_BATCH} seq_len {SEQ_LEN}", flush=True)
    t = train_phase(cfg)
    report_train(t, t["ref_loss0"], t["ref_grad_norm0"],
                 'ode_backend="reference"')
    peak = peak_hbm_bytes()
    print(f"train: peak HBM {peak!r} bytes" if peak is not None
          else "train: peak HBM not reported by this backend")

    s = serve_phase()
    print(f"serve: {s['completed']}/{s['n_requests']} completed; "
          f"{s['n_checked']} end states vs solve(): worst "
          f"|served - solve| / (atol + rtol|solve|) = "
          f"{s['worst_err_over_tol']!r}")
    check(s["completed"] == s["n_requests"], "every ODE request completed")
    check(s["worst_err_over_tol"] <= 1.0,
          "served end states match per-request solve() within tolerance")


def four_chips() -> None:
    check(len(jax.devices()) == 4, "four devices present")
    cfg, reduced = depth_cut()
    print(f"train x4: {ARCH} at published widths; {reduced}; "
          f"global_batch {GLOBAL_BATCH} seq_len {SEQ_LEN}", flush=True)
    tc = trainer_config(global_batch=GLOBAL_BATCH, seq_len=SEQ_LEN,
                        steps=STEPS)
    ref = step0_on_one_device(cfg, tc)
    run = run_trainer(cfg, tc)
    check(run["devices"] == 4, "Trainer mesh spans four devices (data=4)")
    report_train(run, ref[0], ref[1], "one device")

    s = sharded_solve_phase()
    print(f"sharded solve: {s['rows']} rows over {s['devices']} devices; "
          f"max rel diff vs PerSample: end states {s['ys_rel']!r}, "
          f"param grads {s['grad_rel']!r}")
    check(s["ys_rel"] <= 1e-5, "Sharded end states match PerSample")
    check(s["grad_rel"] <= 1e-4, "Sharded param grads match PerSample")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path and its comparisons")
    a = ap.parse_args(argv)
    require_tpu()
    compile_cache.enable()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    four_chips() if a.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
