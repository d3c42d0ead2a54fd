"""Serving-layer tests: scheduler, cache, engine parity, CLI smoke.

Everything time-dependent runs on an injected deterministic clock (a fake
timer advancing a fixed step per sample), so no assertion here depends on
wall time. The engine parity tests are the load-bearing ones: the chunked
continuous-batching engine must reproduce ``solve()``'s end states
exactly — chunk boundaries are scan boundaries with identical carry, so
backfilled serving is numerically invisible.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ALF, AdaptiveController, SaveAt, solve
from repro.serve import (ADMISSION_POLICIES, CACHE_POLICIES,
                         SCHEDULING_POLICIES, AdmissionPolicy, AdmitAll,
                         BoundedQueue, CachePolicy, ContinuousBatchingEngine,
                         EngineConfig, FIFO, InterpolantCache, LRU, NoCache,
                         Request, RequestConfig, Scheduler, SchedulingPolicy,
                         ShortestSpanFirst, StaticFleetEngine,
                         decay_dynamics, hot_trajectory_requests,
                         mixed_stiffness_requests, percentile,
                         poisson_arrivals)


def make_timer(step: float = 1e-3):
    """Deterministic clock: advances `step` per sample."""
    state = {"t": 0.0}

    def timer() -> float:
        state["t"] += step
        return state["t"]

    return timer


def _z0(rng, d=4, lam=3.0):
    return {"y": rng.standard_normal(d).astype(np.float32),
            "lam": np.full((d,), lam, dtype=np.float32)}


def _solve_reference(req):
    cfg = req.config
    return solve(decay_dynamics, None,
                 {k: jnp.asarray(v) for k, v in req.z0.items()},
                 cfg.t0, cfg.t1, solver=ALF(eta=0.9),
                 controller=AdaptiveController(cfg.rtol, cfg.atol,
                                               cfg.max_steps))


def small_config():
    return EngineConfig(slots=3, chunk_steps=8, solver=ALF(eta=0.9))


# ---------------------------------------------------------------------------
# RequestConfig
# ---------------------------------------------------------------------------

class TestRequestConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="empty span"):
            RequestConfig(t0=1.0, t1=1.0)
        with pytest.raises(ValueError, match="non-negative"):
            RequestConfig(rtol=-1e-3)
        with pytest.raises(ValueError, match="cannot both be 0"):
            RequestConfig(rtol=0.0, atol=0.0)
        with pytest.raises(ValueError, match="max_steps"):
            RequestConfig(max_steps=0)

    def test_value_hashing(self):
        # The PR 6 contract: fresh equal-valued configs are interchangeable
        # as jit statics and cache-key components.
        a = RequestConfig(t1=np.float32(2.0), rtol=1e-4)
        b = RequestConfig(t1=2.0, rtol=1e-4)
        assert a == b and hash(a) == hash(b)
        assert a != RequestConfig(t1=2.0, rtol=1e-3)
        assert RequestConfig(t0=1.0, t1=0.0).span == -1.0


# ---------------------------------------------------------------------------
# Scheduler (deterministic clock — no wall time anywhere)
# ---------------------------------------------------------------------------

class TestScheduler:
    def _requests(self, arrivals):
        rng = np.random.default_rng(0)
        return [Request(z0=_z0(rng), arrival=t) for t in arrivals]

    def test_release_by_stamp(self):
        s = Scheduler()
        s.schedule(self._requests([0.0, 0.5, 1.0, 2.0]))
        assert s.next_arrival() == 0.0
        assert s.release(now=0.6) == 2
        assert s.depth == 2 and not s.drained
        assert s.next_arrival() == 1.0
        assert s.release(now=0.7) == 0          # nothing new has arrived
        assert s.release(now=5.0) == 2
        taken = s.take(10)
        assert [r.arrival for r in taken] == [0.0, 0.5, 1.0, 2.0]  # FIFO
        assert s.drained

    def test_bounded_queue_rejects(self):
        s = Scheduler(admission=BoundedQueue(max_depth=2))
        s.schedule(self._requests([0.0, 0.1, 0.2, 0.3]))
        s.release(now=1.0)
        assert s.depth == 2
        assert s.n_rejected == 2
        assert [r.arrival for r in s.rejected] == [0.2, 0.3]
        # draining the queue re-opens admission for later arrivals
        s.take(2)
        s.schedule(self._requests([1.5]))
        s.release(now=2.0)
        assert s.depth == 1 and s.n_rejected == 2
        assert AdmitAll().admit(10_000, None)

    def test_shortest_span_first(self):
        rng = np.random.default_rng(0)
        spans = [3.0, 1.0, 2.0]
        reqs = [Request(z0=_z0(rng), config=RequestConfig(t1=t1))
                for t1 in spans]
        s = Scheduler(policy=ShortestSpanFirst())
        s.schedule(reqs)
        s.release(now=0.0)
        out = s.take(2)
        assert [r.config.t1 for r in out] == [1.0, 2.0]
        assert [r.config.t1 for r in s.take(5)] == [3.0]
        # FIFO control on the same spans
        assert isinstance(FIFO().select([], 4), list)

    def test_take_pred_splits_lanes(self):
        rng = np.random.default_rng(0)
        dense = Request(z0=_z0(rng), config=RequestConfig(dense=True))
        plain = Request(z0=_z0(rng))
        s = Scheduler()
        s.schedule([dense, plain])
        s.release(now=0.0)
        out = s.take(5, pred=lambda r: r.wants_dense)
        assert out == [dense]
        assert s.take(5) == [plain]

    def test_registries(self):
        assert set(ADMISSION_POLICIES) == {"admit_all", "bounded"}
        assert set(SCHEDULING_POLICIES) == {"fifo", "shortest_span"}
        assert set(CACHE_POLICIES) == {"lru", "none"}


# ---------------------------------------------------------------------------
# Interpolant cache
# ---------------------------------------------------------------------------

class TestInterpolantCache:
    def test_key_is_content_hash(self):
        rng = np.random.default_rng(1)
        z0 = _z0(rng)
        cfg = RequestConfig(dense=True)
        k = InterpolantCache.key("vf", cfg, z0)
        assert k == InterpolantCache.key(
            "vf", RequestConfig(dense=True),
            {kk: vv.copy() for kk, vv in z0.items()})
        assert k != InterpolantCache.key("vf2", cfg, z0)
        assert k != InterpolantCache.key(
            "vf", RequestConfig(dense=True, rtol=1e-5), z0)
        other = {kk: vv.copy() for kk, vv in z0.items()}
        other["y"][0] += 1.0
        assert k != InterpolantCache.key("vf", cfg, other)

    def test_hit_miss_counters(self):
        c = InterpolantCache(LRU(max_entries=4))
        assert c.get("a") is None
        c.put("a", "va")
        assert c.get("a") == "va"
        assert (c.hits, c.misses, c.hit_rate) == (1, 1, 0.5)
        assert "a" in c and len(c) == 1

    def test_lru_eviction(self):
        c = InterpolantCache(LRU(max_entries=2))
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1          # refresh "a": now "b" is oldest
        c.put("c", 3)
        assert c.evictions == 1
        assert c.get("b") is None       # "b" was evicted, not "a"
        assert c.get("a") == 1 and c.get("c") == 3

    def test_no_cache_policy(self):
        c = InterpolantCache(NoCache())
        c.put("a", 1)
        assert len(c) == 0 and c.get("a") is None
        with pytest.raises(ValueError, match="max_entries"):
            LRU(max_entries=0)


# ---------------------------------------------------------------------------
# Engine: backfilled chunked serving == stacked individual solves
# ---------------------------------------------------------------------------

class TestEngineParity:
    def _mixed_requests(self):
        rng = np.random.default_rng(7)
        reqs = mixed_stiffness_requests(rng, 7, rate=1_000.0, d_state=4,
                                        lam_decades=(0.0, 1.3),
                                        max_steps=256)
        # one reverse-time request rides the same fleet
        reqs.append(Request(z0=_z0(rng, lam=2.0),
                            config=RequestConfig(t0=1.0, t1=0.0,
                                                 max_steps=256),
                            arrival=0.002))
        return reqs

    def test_backfill_equals_stacked_solves(self):
        reqs = self._mixed_requests()
        eng = ContinuousBatchingEngine(decay_dynamics, None,
                                       config=small_config(),
                                       timer=make_timer())
        eng.submit(reqs)
        report = eng.run()
        assert report.n_requests == len(reqs)
        assert report.n_completed == len(reqs)
        for req in reqs:
            ref = _solve_reference(req)
            got = eng.results[req.rid]["y"]
            np.testing.assert_allclose(got, np.asarray(ref.ys["y"]),
                                       atol=1e-6, rtol=1e-6)
        # f-eval accounting matches solve()'s Stats exactly
        ref0 = _solve_reference(reqs[0])
        rec0 = next(r for r in eng.records if r.rid == reqs[0].rid)
        assert rec0.n_fevals == int(ref0.stats.n_fevals)
        assert rec0.n_accepted == int(ref0.stats.n_accepted)

    def test_deterministic_under_fake_clock(self):
        def trace(seed_step):
            eng = ContinuousBatchingEngine(decay_dynamics, None,
                                           config=small_config(),
                                           timer=make_timer(seed_step))
            eng.submit(self._mixed_requests())
            eng.run()
            return [(r.arrival, r.completion, r.n_fevals, r.completed)
                    for r in sorted(eng.records, key=lambda r: r.arrival)]

        assert trace(1e-3) == trace(1e-3)

    def test_budget_exhaustion_marks_incomplete(self):
        rng = np.random.default_rng(3)
        req = Request(z0=_z0(rng, lam=50.0),
                      config=RequestConfig(max_steps=3))
        eng = ContinuousBatchingEngine(decay_dynamics, None,
                                       config=small_config(),
                                       timer=make_timer())
        eng.submit([req])
        report = eng.run()
        rec = eng.records[0]
        assert not rec.completed and rec.n_fevals == 3 + 1  # trials + v0
        assert report.n_completed == 0
        assert req.rid in eng.results   # truncated end state still returned

    def test_static_fleet_completes_together(self):
        reqs = self._mixed_requests()
        eng = StaticFleetEngine(decay_dynamics, None, config=small_config(),
                                timer=make_timer())
        eng.submit(reqs)
        report = eng.run()
        assert report.n_completed == len(reqs)
        for req in reqs:
            ref = _solve_reference(req)
            np.testing.assert_allclose(eng.results[req.rid]["y"],
                                       np.asarray(ref.ys["y"]),
                                       atol=1e-6, rtol=1e-6)
        # one-shot fleet semantics: batch members share a completion stamp
        stamps = {r.completion for r in eng.records}
        assert len(stamps) <= int(np.ceil(len(reqs)
                                          / eng.config.slots)) + 1

    def test_engine_config_validation(self):
        with pytest.raises(ValueError, match="slots"):
            EngineConfig(slots=0)
        with pytest.raises(ValueError, match="error estimate"):
            from repro.core import Rk4
            EngineConfig(solver=Rk4())

    def test_mismatched_state_shape_rejected(self):
        rng = np.random.default_rng(0)
        eng = ContinuousBatchingEngine(decay_dynamics, None,
                                       config=small_config(),
                                       timer=make_timer())
        eng.submit([Request(z0=_z0(rng, d=4))])
        eng.scheduler.release(0.0)
        eng._backfill()
        with pytest.raises(ValueError, match="structure/shapes"):
            eng._insert(1, Request(z0=_z0(rng, d=8)))


# ---------------------------------------------------------------------------
# Dense lane + interpolant cache through the engine
# ---------------------------------------------------------------------------

class TestDenseLane:
    def test_hot_trajectory_hits_cost_zero_fevals(self):
        rng = np.random.default_rng(5)
        reqs = hot_trajectory_requests(rng, n_repeats=3, d_state=4,
                                       lam=4.0)
        cache = InterpolantCache(LRU(max_entries=8))
        eng = ContinuousBatchingEngine(decay_dynamics, None,
                                       config=small_config(), cache=cache,
                                       vf_id="decay", timer=make_timer())
        eng.submit(reqs)
        report = eng.run()
        assert (cache.hits, cache.misses) == (3, 1)
        assert report.cache_hit_rate == pytest.approx(0.75)
        hit_recs = [r for r in eng.records if r.cache_hit]
        assert len(hit_recs) == 3
        assert all(r.n_fevals == 0 for r in hit_recs)   # the acceptance bar
        miss = next(r for r in eng.records if not r.cache_hit)
        assert miss.n_fevals > 0

    def test_eval_matches_direct_dense_solve(self):
        rng = np.random.default_rng(6)
        req = hot_trajectory_requests(rng, n_repeats=0, d_state=4,
                                      lam=4.0)[0]
        eng = ContinuousBatchingEngine(decay_dynamics, None,
                                       config=small_config(),
                                       timer=make_timer())
        eng.submit([req])
        eng.run()
        cfg = req.config
        ref = solve(decay_dynamics, None,
                    {k: jnp.asarray(v) for k, v in req.z0.items()},
                    cfg.t0, cfg.t1, solver=ALF(eta=0.9),
                    controller=AdaptiveController(cfg.rtol, cfg.atol,
                                                  cfg.max_steps),
                    saveat=SaveAt(dense=True))
        want = ref.evaluate(jnp.asarray(req.eval_ts))
        np.testing.assert_allclose(eng.results[req.rid]["y"],
                                   np.asarray(want["y"]),
                                   atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# Load generation + metrics
# ---------------------------------------------------------------------------

class TestLoadgenMetrics:
    def test_poisson_arrivals(self):
        rng = np.random.default_rng(0)
        ts = poisson_arrivals(rng, rate=100.0, n=500)
        assert len(ts) == 500 and np.all(np.diff(ts) > 0)
        assert np.mean(np.diff(ts)) == pytest.approx(0.01, rel=0.2)
        with pytest.raises(ValueError, match="rate"):
            poisson_arrivals(rng, rate=0.0, n=1)

    def test_percentile(self):
        xs = [1.0, 2.0, 3.0, 4.0]
        assert percentile(xs, 50.0) == pytest.approx(2.5)
        assert percentile(xs, 100.0) == 4.0
        assert np.isnan(percentile([], 50.0))
        with pytest.raises(ValueError):
            percentile(xs, 101.0)


# ---------------------------------------------------------------------------
# Static-analysis contracts on the serve layer
# ---------------------------------------------------------------------------

class TestServeAnalysisContracts:
    def test_policies_implement_full_interface(self):
        from repro.analysis.rules.r004_registry import missing_interface
        for cls, base in [(AdmitAll, AdmissionPolicy),
                          (BoundedQueue, AdmissionPolicy),
                          (FIFO, SchedulingPolicy),
                          (ShortestSpanFirst, SchedulingPolicy),
                          (LRU, CachePolicy), (NoCache, CachePolicy)]:
            assert missing_interface(cls, base) == []

        class Incomplete(AdmissionPolicy):
            name = "incomplete"

        assert missing_interface(Incomplete, AdmissionPolicy) == ["admit"]

    def test_serve_trace_audit_clean(self):
        # Device-free: chunk_transition is spec-preserving and one trace
        # serves every round across fresh equal-valued configs.
        from repro.analysis.trace_audit import run_serve_audit
        combos, failures, retrace = run_serve_audit()
        assert combos >= 5
        assert failures == []
        assert all(n == 1 for n in retrace.values()), retrace


# ---------------------------------------------------------------------------
# CLI smoke: launch/serve.py --mode ode through the new engine
# ---------------------------------------------------------------------------

class TestServeCLI:
    def test_mode_default_batch_single_source(self):
        from repro.launch.serve import MODE_DEFAULT_BATCH
        assert MODE_DEFAULT_BATCH == {"lm": 4, "ode": 64}

    def test_ode_mode_smoke(self, monkeypatch, capsys):
        from repro.launch import serve as serve_mod
        monkeypatch.setattr("sys.argv", [
            "serve", "--mode", "ode", "--batch", "2", "--requests", "5",
            "--d-state", "4", "--chunk-steps", "8", "--rate", "500",
            "--seed", "3", "--t1", "0.5", "--rtol", "1e-3", "--atol",
            "1e-4", "--max-steps", "128"])
        serve_mod.main()
        out = capsys.readouterr().out
        # run header prints the resolved batch + forwarded CLI knobs
        assert "batch(slots)=2" in out
        assert "t1=0.5" in out and "seed=3" in out
        assert "engine=continuous" in out
        assert "serve[continuous]" in out      # the ServeReport
        assert "5 completed" in out

    def test_ode_mode_static_engine(self, monkeypatch, capsys):
        from repro.launch import serve as serve_mod
        monkeypatch.setattr("sys.argv", [
            "serve", "--mode", "ode", "--ode-engine", "static", "--batch",
            "2", "--requests", "4", "--d-state", "4", "--chunk-steps",
            "8", "--max-steps", "128"])
        serve_mod.main()
        out = capsys.readouterr().out
        assert "engine=static" in out and "serve[static]" in out

    def test_ode_field_matmuls_are_f32_on_every_backend(self):
        # the TPU's default precision would round f's matmul inputs to
        # bf16, an error the requests' rtol cannot absorb
        import jax
        from repro.launch.serve import mlp_field
        f, params = mlp_field(np.random.default_rng(0), 4)
        z = {"y": jnp.ones((2, 4)), "scale": jnp.ones((2, 4))}
        eqns = jax.make_jaxpr(lambda p, z: f(p, z, 0.0))(params, z).eqns
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert len(dots) == 2
        for e in dots:
            assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
