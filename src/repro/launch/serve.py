"""Serving driver: LM prefill/decode AND the continuous-batching ODE loop.

Two serving paths share this driver:

* **LM path** (default) — batched prefill + autoregressive decode::

      PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
          --prompt-len 32 --decode-tokens 16 --batch 4

  Greedy decoding over the synthetic token stream; prints per-phase timings
  and tokens/s. The same prefill/decode step functions are what the dry-run
  lowers at the assigned 32k/500k shapes on the production mesh.

* **ODE path** (``--mode ode``) — the ``repro.serve`` serving loop::

      PYTHONPATH=src python -m repro.launch.serve --mode ode --batch 64 \
          --requests 256 --rate 100 [--ode-engine continuous|static] \
          [--chunk-steps 32] [--seed 0] [--d-state 32] [--t1 1.0] \
          [--rtol 1e-3 --atol 1e-4 --max-steps 512] [--production-mesh]

  Requests (each one initial state of a shared MLP vector field, with its
  own stiffness scale) arrive as a Poisson stream (``--rate``; omit for
  all-at-once) and are served by a :class:`repro.serve.
  ContinuousBatchingEngine` — ``--batch`` slots advanced in
  ``--chunk-steps`` chunked re-dispatch rounds, finished rows backfilled
  from the queue between rounds. ``--ode-engine static`` runs the
  no-backfill static-fleet baseline (the pre-PR-8 one-shot fleet) on the
  same stream for comparison. Prints the :class:`repro.serve.ServeReport`:
  p50/p99 latency, solves/s, f-evals/request, occupancy —
  ``benchmarks/serve_load.py`` tracks the same numbers in CI.

Per-mode ``--batch`` defaults live in ``MODE_DEFAULT_BATCH`` (one place),
and the resolved value is printed in each run's header.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import DEFAULT_ODE, get_config, smoke_config
from repro.core.ode_block import OdeSettings
from repro.distributed.sharding import (cache_shardings, param_shardings,
                                        replicated)
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import init_lm
from repro.models.lm import ServeState, init_serve_state

# One place for the per-mode --batch defaults (main() used to hardcode
# them inline in two spots). For ode, batch == engine slots (fleet width).
MODE_DEFAULT_BATCH = {"lm": 4, "ode": 64}


def serve(arch: str, *, smoke: bool = True, ode: bool = True,
          prompt_len: int = 32, decode_tokens: int = 16, batch: int = 4,
          production_mesh: bool = False, seed: int = 0):
    settings = DEFAULT_ODE if ode else OdeSettings(mode="off")
    cfg = smoke_config(arch, settings) if smoke else get_config(arch, settings)
    mesh = make_production_mesh() if production_mesh else make_host_mesh()
    s_max = prompt_len + decode_tokens
    rng = np.random.default_rng(seed)

    with mesh:
        params = init_lm(jax.random.PRNGKey(seed), cfg)
        params = jax.device_put(params, param_shardings(cfg, mesh, params))
        state = init_serve_state(cfg, batch, s_max)
        st_sh = ServeState(cache_shardings(cfg, mesh, state.cache, batch),
                           replicated(mesh))
        state = jax.device_put(state, st_sh)

        prefill = jax.jit(make_prefill_step(cfg), donate_argnums=(2,))
        decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,))

        if cfg.input_mode == "embeds":
            prompt = {"embeds": jnp.asarray(rng.standard_normal(
                (batch, prompt_len, cfg.d_model)).astype(np.float32))}
        else:
            prompt = {"tokens": jnp.asarray(rng.integers(
                0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32))}

        t0 = time.time()
        logits, state = prefill(params, prompt, state)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        out_tokens = []
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        t0 = time.time()
        for _ in range(decode_tokens):
            if cfg.input_mode == "embeds":
                # stub frontend: feed the token id through a fixed projection
                inp = jnp.tile(tok[..., None].astype(jnp.float32),
                               (1, 1, cfg.d_model)) * 1e-3
            else:
                inp = tok
            logits, state = decode(params, inp, state)
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            out_tokens.append(np.asarray(tok[:, 0]))
        jax.block_until_ready(logits)
        t_decode = time.time() - t0

    toks = np.stack(out_tokens, 1)
    print(f"arch={cfg.name} batch={batch} prompt={prompt_len} "
          f"decode={decode_tokens}")
    print(f"prefill: {t_prefill * 1e3:.1f} ms "
          f"({batch * prompt_len / max(t_prefill, 1e-9):.0f} tok/s)")
    print(f"decode:  {t_decode * 1e3:.1f} ms "
          f"({batch * decode_tokens / max(t_decode, 1e-9):.0f} tok/s)")
    print("sample:", toks[0][:12].tolist())
    return toks


def mlp_field(rng: np.random.Generator, d_state: int):
    """The serving vector field: shared two-layer MLP with per-request
    stiffness in the state (``d scale/dt = 0``). Returns (f, params)."""
    w1 = jnp.asarray(rng.standard_normal((d_state, d_state)) * 0.4,
                     jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((d_state, d_state)) * 0.4,
                     jnp.float32)
    params = {"w1": w1, "w2": w2}
    # f32 products: the TPU's default precision rounds matmul inputs to
    # bf16, a 2^-8 relative error in f that the requests' rtol (1e-3)
    # cannot absorb -- the controller then rejects on noise, runs out of
    # budget and ends away from the f32 solution.
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)

    def f(p, z, t):
        h = jnp.tanh(dot(z["y"], p["w1"]))
        return {"y": z["scale"] * (dot(h, p["w2"]) - z["y"]),
                "scale": jnp.zeros_like(z["scale"])}

    return f, params


def serve_ode(*, batch: int = 64, d_state: int = 32, t1: float = 1.0,
              engine: str = "continuous", chunk_steps: int = 32,
              n_requests: int = 256, rate: float = 0.0, rtol: float = 1e-3,
              atol: float = 1e-4, max_steps: int = 512,
              production_mesh: bool = False, seed: int = 0):
    """Serve a stream of Neural-ODE solve requests through the
    ``repro.serve`` engine stack.

    ``batch`` engine slots advance in ``chunk_steps``-trial dispatch
    rounds; ``engine='continuous'`` backfills retired rows from the queue
    between rounds, ``engine='static'`` runs the no-backfill fleet
    baseline. ``rate`` > 0 makes arrivals Poisson at that rate (requests/s
    of serving-clock time); 0 submits everything at t=0 (closed loop).
    Returns ``(report, engine, requests)``: the run's
    :class:`repro.serve.ServeReport`, the drained engine (served end states
    in ``engine.results`` keyed by request id) and the submitted requests.
    """
    from repro.core import ALF
    from repro.serve import (ENGINES, EngineConfig, Request, RequestConfig,
                             format_report, poisson_arrivals)

    if engine not in ENGINES:
        raise ValueError(f"unknown ode engine {engine!r}; "
                         f"choose from {sorted(ENGINES)}")
    mesh = make_production_mesh() if production_mesh else make_host_mesh()
    rng = np.random.default_rng(seed)
    f, params = mlp_field(rng, d_state)

    config = RequestConfig(t0=0.0, t1=t1, rtol=rtol, atol=atol,
                           max_steps=max_steps)
    if rate > 0.0:
        arrivals = poisson_arrivals(rng, rate, n_requests)
    else:
        arrivals = np.zeros(n_requests)
    requests = []
    for i in range(n_requests):
        z0 = {"y": rng.standard_normal(d_state).astype(np.float32),
              "scale": np.full((d_state,),
                               10.0 ** rng.uniform(0.0, 1.0), np.float32)}
        requests.append(Request(z0=z0, config=config,
                                arrival=float(arrivals[i])))

    print(f"ode serve: engine={engine} batch(slots)={batch} "
          f"chunk_steps={chunk_steps} d={d_state} t1={t1} "
          f"rtol={rtol} atol={atol} max_steps={max_steps} "
          f"requests={n_requests} "
          f"rate={rate if rate > 0 else 'all-at-once'} seed={seed}")

    with mesh:
        eng = ENGINES[engine](
            f, params,
            config=EngineConfig(slots=batch, chunk_steps=chunk_steps,
                                solver=ALF(eta=0.9)),
            vf_id=f"mlp-d{d_state}-seed{seed}")
        eng.submit(requests)
        report = eng.run()
    print(format_report(report))
    return report, eng, requests


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="lm", choices=["lm", "ode"],
                    help="lm: prefill/decode serving; ode: continuous-"
                         "batching ODE serving loop")
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=None,
                    help="lm: requests per step; ode: engine batch slots "
                         f"(defaults: {MODE_DEFAULT_BATCH})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ode", default="on", choices=["on", "off"])
    ap.add_argument("--ode-engine", default="continuous",
                    choices=["continuous", "static"],
                    help="continuous: chunked backfill; static: one-shot "
                         "fleet baseline")
    ap.add_argument("--chunk-steps", type=int, default=32,
                    help="adaptive trials per dispatch round (ode)")
    ap.add_argument("--requests", type=int, default=256,
                    help="number of ODE requests to serve")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in requests/s "
                         "(0 = submit all at t=0)")
    ap.add_argument("--d-state", type=int, default=32,
                    help="ODE state dimension per request")
    ap.add_argument("--t1", type=float, default=1.0,
                    help="integration span end (ode)")
    ap.add_argument("--rtol", type=float, default=1e-3)
    ap.add_argument("--atol", type=float, default=1e-4)
    ap.add_argument("--max-steps", type=int, default=512,
                    help="per-request adaptive trial budget (ode)")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--production-mesh", action="store_true")
    a = ap.parse_args()
    batch = MODE_DEFAULT_BATCH[a.mode] if a.batch is None else a.batch
    if a.mode == "ode":
        serve_ode(batch=batch, d_state=a.d_state, t1=a.t1,
                  engine=a.ode_engine, chunk_steps=a.chunk_steps,
                  n_requests=a.requests, rate=a.rate, rtol=a.rtol,
                  atol=a.atol, max_steps=a.max_steps,
                  production_mesh=a.production_mesh, seed=a.seed)
        return
    serve(a.arch, smoke=a.smoke, ode=a.ode == "on", prompt_len=a.prompt_len,
          decode_tokens=a.decode_tokens, batch=batch,
          production_mesh=a.production_mesh, seed=a.seed)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
