"""A cell that takes four chips, on four virtual CPU devices: the program
runs on exactly the cell's chips, and the reference spread over them
computes what it computes on one."""
import os
import subprocess
import sys
import textwrap

from bench import spec


def _on_four_devices(code: str) -> str:
    root = os.path.dirname(spec.BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout


def test_the_program_runs_on_exactly_the_cell_s_chips():
    """The Trainer's mesh holds the cell's devices and no others; over every
    device it is the program's own host mesh; a whole run on one and on
    four of four devices is correct with its state only on them."""
    out = _on_four_devices("""
        import time
        import jax
        from bench import cell
        from bench.faults import Unchanged
        from bench.tests.test_bench_modules import LIMITS
        from bench.tests.test_bench_reference import tiny_config, tiny_traffic
        from repro.launch.mesh import make_host_mesh

        class Spy(Unchanged):
            seen = set()

            def step(self, params, opt_state, carry, batch, **kw):
                for x in jax.tree_util.tree_leaves((params, opt_state)):
                    Spy.seen |= set(x.devices())
                return self.inner.step(params, opt_state, carry, batch, **kw)

        devs = jax.devices()
        assert len(devs) == 4
        assert cell.host_mesh(devs) == make_host_mesh()
        cfile, traffic = tiny_config("stablelm-1.6b"), tiny_traffic(True)
        traffic["global_batch"] = 4
        for n in (1, 4):
            t = cell.build_trainer(cfile, traffic, 5, devs[:n])
            assert list(t.mesh.devices.flat) == devs[:n], t.mesh
            Spy.seen = set()
            res = cell.run(cfile, traffic, LIMITS, 2 ** 31 + 13, 0.0,
                           t_start=time.perf_counter(), devices=devs[:n],
                           fault=Spy)
            assert res["correct"], res["checks"]
            assert res["ctx"]["chips"] == n
            assert Spy.seen == set(devs[:n]), Spy.seen
            print("ok", n)
        """)
    assert out.split() == ["ok", "1", "ok", "4"]


def test_the_reference_spread_over_four_chips_is_bitwise_one_chip_s():
    out = _on_four_devices("""
        import jax
        import numpy as np
        from bench.reference import lm
        from bench.reference.placement import Placement
        from bench.tests.test_bench_reference import tiny_traffic

        devs = jax.devices()
        job = lm.job(tiny_traffic(True))
        rng = np.random.default_rng(7)
        batches = [(rng.integers(0, 256, (2, 64), dtype=np.int32),
                    rng.integers(0, 256, (2, 64), dtype=np.int32))
                   for _ in range(2)]
        for tie in (False, True):
            m = lm.Sizes(d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
                         d_ff=128, vocab_size=256, n_layers=5, qk_norm=True,
                         rope_theta=1e4, tie_embeddings=tie,
                         param_dtype="float32")
            one = lm.run(2 ** 32 + 3, m, job, batches)
            four = lm.run(2 ** 32 + 3, m, job, batches, devices=devs)
            assert one == four, (one, four)
            p = lm.init_params(3, m, Placement(devs, 5))
            for i, lp in enumerate(p["layers"]):
                for x in lp.values():
                    assert x.devices() == {devs[i * 4 // 5]}, (i, x.devices())
            assert p["embed"].devices() == {devs[0]}
            for k in ("final_norm", "head"):
                assert k not in p or p[k].devices() == {devs[3]}
            assert Placement(devs[:1], 5).layer(4) is None
            print("ok", tie)
        """)
    assert out.split() == ["ok", "False", "ok", "True"]
