"""Tests pinning the §Perf optimizations to their reference semantics:
flash-bwd attention == AD-through-scan attention, sort-based MoE dispatch ==
cumsum dispatch, sharding hints are no-ops without a mesh."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.distributed.sharding import hint
from repro.models import attention as A


@pytest.mark.parametrize("window", [0, 100])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_flash_bwd_matches_ad_reference(window, softcap):
    cfg = dataclasses.replace(smoke_config("gemma2-2b"),
                              attn_softcap=softcap)
    b, s, h, kv, dh = 2, 512, 4, 2, 16
    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, s, h, dh))
    k = jax.random.normal(kk, (b, s, kv, dh))
    v = jax.random.normal(kvk, (b, s, kv, dh))
    pos = jnp.arange(s, dtype=jnp.int32)

    def f_flash(q, k, v):
        return (A._sdpa_chunked_flash(cfg, q, k, v, pos, pos, window,
                                      block_q=128, block_kv=128) ** 2).sum()

    def f_ref(q, k, v):
        return (A._sdpa_chunked(cfg, q, k, v, pos, pos, window,
                                block_q=128, block_kv=128) ** 2).sum()

    np.testing.assert_allclose(float(f_flash(q, k, v)),
                               float(f_ref(q, k, v)), rtol=1e-4)
    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def _rel(got, want):
    """Norm-wise relative gap ||got - want|| / ||want||."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# (s, heads, kv_heads, block_q, block_kv, window, softcap)
LIVE_TILE_CASES = {
    "causal": (512, 4, 4, 64, 128, 0, 0.0),
    "window-inside-one-kv-block": (512, 4, 4, 64, 128, 40, 0.0),
    "window-spans-two-kv-blocks": (512, 4, 4, 64, 128, 200, 0.0),
    "padded-q-and-kv": (300, 4, 4, 64, 128, 0, 0.0),
    "gqa": (512, 4, 2, 64, 128, 0, 0.0),
    "softcap": (512, 4, 2, 64, 128, 0, 30.0),
    # a q-block wider than window + kv-block: its last rows meet masked
    # tiles of the live range before their first live one (alpha = 0)
    "masked-tile-before-first-live": (256, 4, 2, 128, 32, 40, 0.0),
}


@pytest.mark.parametrize("case", sorted(LIVE_TILE_CASES))
def test_flash_live_tiles_match_full_sweep(case):
    """The flash path, which visits only live tiles, equals the chunked
    path's AD through every tile: output and dq/dk/dv within 1e-6."""
    s, h, kv, bq, bk, window, cap = LIVE_TILE_CASES[case]
    cfg = dataclasses.replace(smoke_config("gemma2-2b"), attn_softcap=cap)
    b, dh = 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, kv, dh))
    v = jax.random.normal(ks[2], (b, s, kv, dh))
    w = jax.random.normal(ks[3], (b, s, h, dh))
    pos = jnp.arange(s, dtype=jnp.int32)

    def attn(fn):
        return lambda q, k, v: fn(cfg, q, k, v, pos, pos, window,
                                  block_q=bq, block_kv=bk)

    live, full = attn(A._sdpa_chunked_flash), attn(A._sdpa_chunked)
    assert _rel(live(q, k, v), full(q, k, v)) <= 1e-6
    grads = [jax.grad(lambda *a, f=f: (f(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v) for f in (live, full)]
    for got, want in zip(*grads):
        assert _rel(got, want) <= 1e-6


def _tiled_pos(s, bq, bk):
    """The padded, tiled positions the chunked paths give the flash core."""
    x = jnp.zeros((1, s, 1, 1))
    pos = jnp.arange(s, dtype=jnp.int32)
    tiled = A._chunk_arrays(None, x, x, x, pos, pos, bq, bk)
    return tiled[3], tiled[4]


@pytest.mark.parametrize("s, bq, bk, window, lo, hi", [
    # the training cells' shape: 20 of 32 tiles
    (4096, 512, 1024, 0, [0] * 8, [0, 0, 1, 1, 2, 2, 3, 3]),
    # q-block i sees kv-blocks i-2..i (256(i-2)+255 > 256i-300)
    (1024, 256, 256, 300, [0, 0, 0, 1], [0, 1, 2, 3]),
    # 300 rows: the last q-block holds rows 256..299 and padding
    (300, 64, 128, 0, [0] * 5, [0, 0, 1, 1, 2]),
], ids=["s4096-causal", "window", "padded"])
def test_live_kv_range(s, bq, bk, window, lo, hi):
    qpos, kpos = _tiled_pos(s, bq, bk)
    got_lo, got_hi = A._live_kv_range(qpos, kpos, window)
    np.testing.assert_array_equal(np.asarray(got_lo), lo)
    np.testing.assert_array_equal(np.asarray(got_hi), hi)
    real = np.asarray(qpos.max(axis=1)) >= 0
    assert np.all(np.asarray(got_lo)[real] <= np.asarray(got_hi)[real])
    if s == 4096:
        assert int((got_hi - got_lo + 1).sum()) == 20
        assert qpos.shape[0] * kpos.shape[0] == 32


def test_flash_vs_direct_small():
    """Chunked (flash) path == direct softmax attention."""
    cfg = smoke_config("qwen3-1.7b")
    b, s, h, dh = 1, 256, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))
    pos = jnp.arange(s, dtype=jnp.int32)
    got = A._sdpa_chunked_flash(cfg, q, k, v, pos, pos, 0,
                                block_q=64, block_kv=64)
    bias = A._mask_bias(pos, pos, 0)
    want = A._sdpa_direct(cfg, q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_moe_sort_dispatch_matches_cumsum():
    """Rank-within-expert positions: sort-based == one-hot-cumsum oracle."""
    n, k, e = 128, 3, 16
    rng = np.random.default_rng(7)
    gate_idx = jnp.asarray(rng.integers(0, e, (n, k)), jnp.int32)
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32).reshape(n * k, e)
    pos_old = (((jnp.cumsum(onehot, 0) - onehot) * onehot).sum(-1)
               ).astype(jnp.int32)
    eidx = gate_idx.reshape(-1)
    order = jnp.argsort(eidx, stable=True)
    sorted_e = eidx[order]
    gs = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=eidx.dtype),
                          side="left")
    pos_sorted = jnp.arange(n * k, dtype=jnp.int32) \
        - gs[sorted_e].astype(jnp.int32)
    pos_new = jnp.zeros((n * k,), jnp.int32).at[order].set(pos_sorted)
    np.testing.assert_array_equal(np.asarray(pos_old), np.asarray(pos_new))


def test_hint_is_noop_without_mesh():
    x = jnp.ones((8, 4))
    y = hint(x, "batch", "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_hint_under_trivial_mesh():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with mesh:
        x = jnp.ones((8, 4))
        y = hint(x, "batch", "model")  # size-1 axes -> no constraint applied
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
