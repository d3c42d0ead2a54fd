"""AOT compiles of the MALI path's Pallas kernels, and of the training
path's chunked attention, for a described TPU v5e.

Nothing runs: each kernel is lowered through ``ops.py``'s pallas path and
compiled by the TPU compiler for a v5e chip that is described, not
attached, at the qwen3-1.7b residual-stream size (4 x 512 x 2048). Mosaic
refuses here what interpret mode accepts (misaligned blocks, VMEM
overuse), and the compiled program must hold the kernel
(``tpu_custom_call``), never the interpreter's plain HLO.

The topology is described inside a module fixture (only one process may
load the TPU library, so never at import), and the persistent compile
cache is off around these compiles: a TPU entry written here cannot be
read back without a chip.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import smoke_config
from repro.kernels.alf_step import ops
from repro.models import attention as A

SHAPE = (4, 512, 2048)   # qwen3-1.7b: global_batch 4 x seq_len 512 x d_model
ETA = 0.9

# name -> (function of n state-shaped arrays and h, n)
KERNELS = {
    "alf_midpoint": (lambda z, v, h: ops.alf_midpoint(
        z, v, h, use_pallas=True), 2),
    "alf_update": (lambda k1, v, u1, h: ops.alf_update(
        k1, v, u1, h, eta=ETA, use_pallas=True), 3),
    "alf_inverse": (lambda zo, vo, u1, h: ops.alf_inverse(
        zo, vo, u1, h, eta=ETA, use_pallas=True), 3),
    "alf_bwd_pre": (lambda z, v, az, av, h: ops.alf_bwd_pre(
        z, v, az, av, h, eta=ETA, use_pallas=True), 4),
    "alf_bwd_post": (lambda k1, vo, u1, az, av, dk1, h: ops.alf_bwd_post(
        k1, vo, u1, az, av, dk1, h, eta=ETA, use_pallas=True), 6),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, n = KERNELS[name]
    x = jax.ShapeDtypeStruct(SHAPE, dtype, sharding=one_chip)
    h = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(*([x] * n), h).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_keeps_its_name_for_v5e(one_chip, name):
    """The compiled program names the Mosaic call after its kernel, so a
    profile shows it under that name."""
    fn, n = KERNELS[name]
    x = jax.ShapeDtypeStruct(SHAPE, jnp.float32, sharding=one_chip)
    h = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = jax.jit(fn).lower(*([x] * n), h).compile().as_text()
    calls = re.findall(
        r'%([\w.\-]+) = .*custom_call_target="tpu_custom_call"', text)
    assert calls and all(re.fullmatch(rf"{name}(\.\d+)?", c) for c in calls)


def test_flash_attention_grad_compiles_for_v5e(one_chip):
    """The gradient of the training path's chunked attention at S = 4,096
    (its live-tile loops run over traced bounds) compiles for the chip."""
    cfg = smoke_config("qwen3-1.7b")
    b, s, h, kv, dh = 1, 4096, 2, 1, 64
    pos = jnp.arange(s, dtype=jnp.int32)

    def loss(q, k, v):
        out = A._sdpa_chunked_flash(cfg, q, k, v, pos, pos, 0)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((b, s, h, dh), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, dh), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile()
    assert "while" in compiled.as_text()
