"""Training CLI — a thin front-end over :class:`repro.train.Trainer`.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 50 --ckpt-dir /tmp/run1

The subsystem behind the flags lives in :mod:`repro.train`: native
``solve()``-based continuous-depth steps, registered TrainLoop drivers,
resumable (config-fingerprinted) checkpoints, fault recovery and
structured telemetry. Killing a run and re-launching with the same flags
resumes from the latest checkpoint and reproduces the uninterrupted loss
trace; re-launching with different integrator flags fails fast with
ConfigMismatchError instead of corrupting the run.

``TrainConfig``/``train`` are kept as thin compatibility delegators for
older callers (same field names, same return).
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

from repro.train import Trainer, TrainerConfig

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    """Legacy flat config; ``train(tc)`` maps it onto TrainerConfig."""
    arch: str = "qwen3-1.7b"
    smoke: bool = True
    ode: bool = True
    ode_steps: int = 2
    steps: int = 50
    global_batch: int = 8
    seq_len: int = 64
    microbatches: int = 1
    compress: bool = False
    ckpt_dir: str = ""
    ckpt_every: int = 20
    keep: int = 3
    seed: int = 0
    log_every: int = 10
    production_mesh: bool = False
    multi_pod: bool = False


def _to_trainer_config(tc: TrainConfig) -> TrainerConfig:
    return TrainerConfig(
        arch=tc.arch, smoke=tc.smoke, ode=tc.ode, ode_steps=tc.ode_steps,
        steps=tc.steps, global_batch=tc.global_batch, seq_len=tc.seq_len,
        microbatches=tc.microbatches,
        loop="compressed" if tc.compress else "standard",
        ckpt_dir=tc.ckpt_dir, ckpt_every=tc.ckpt_every, keep=tc.keep,
        seed=tc.seed, log_every=tc.log_every,
        production_mesh=tc.production_mesh, multi_pod=tc.multi_pod)


def train(tc: TrainConfig) -> int:
    """Legacy entry point: run a TrainConfig to completion."""
    return Trainer(_to_trainer_config(tc)).train()


def main(argv=None) -> None:
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ode", default="on", choices=["on", "off"])
    ap.add_argument("--ode-steps", type=int, default=2)
    ap.add_argument("--ode-method", default="mali",
                    choices=["mali", "naive", "aca", "adjoint"])
    ap.add_argument("--ode-backend", default="auto",
                    choices=["auto", "reference", "pallas"])
    ap.add_argument("--ode-batch-axis", default="",
                    help="mesh axis for Sharded() solve batching ('' = off)")
    ap.add_argument("--loop", default="", help="TRAIN_LOOPS key "
                    "(default: standard, or compressed with --compress)")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-jsonl", default="",
                    help="write per-step StepRecord rows to this JSONL file")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="full assigned config (needs a real TPU slice)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    a = ap.parse_args(argv)
    loop = a.loop or ("compressed" if a.compress else "standard")
    cfg = TrainerConfig(
        arch=a.arch, smoke=a.smoke, ode=a.ode == "on",
        ode_steps=a.ode_steps, ode_method=a.ode_method,
        ode_backend=a.ode_backend, ode_batch_axis=a.ode_batch_axis,
        steps=a.steps, global_batch=a.global_batch, seq_len=a.seq_len,
        microbatches=a.microbatches, loop=loop, ckpt_dir=a.ckpt_dir,
        ckpt_every=a.ckpt_every, keep=a.keep, seed=a.seed,
        log_every=a.log_every,
        emit="jsonl" if a.metrics_jsonl else "stdout",
        metrics_path=a.metrics_jsonl,
        production_mesh=a.production_mesh, multi_pod=a.multi_pod)
    final = Trainer(cfg).train()
    print(f"final_step={final}", flush=True)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
