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


@pytest.mark.parametrize("rows,held,n_held_ids", [
    (384, 16, 16),    # every expert held, the budget not binding
    (40, 16, 6),      # six experts held of sixteen, the budget binding
    (200, 8, 3),      # three held of eight: most groups empty
], ids=["dropless", "budget", "empty-groups"])
def test_moe_sort_dispatch_matches_cumsum(rows, held, n_held_ids):
    """Rows grouped by expert: the sort-based dispatch keeps the budget's
    highest-affinity held assignments and puts each at its group's start
    plus its rank among that expert's kept assignments in (token, choice)
    order (the one-hot-cumsum oracle); the group sizes count them; empty
    rows sit in the last group."""
    from repro.models.moe import dispatch
    n, k = 128, 3
    rng = np.random.default_rng(7)
    ids = rng.integers(0, held, (n, k))
    ids[ids >= n_held_ids] += held          # the rest are not held here
    vals = jnp.asarray(rng.random((n, k)), jnp.float32)
    local = jnp.asarray(ids.reshape(-1), jnp.int32)
    is_held = (local >= 0) & (local < held)
    pick, keep, sizes = (np.asarray(a) for a in dispatch(
        vals, local, is_held, rows, held))
    aff = np.asarray(vals).reshape(-1)
    held_ids = np.flatnonzero(np.asarray(is_held))
    kept = np.sort(held_ids[np.lexsort((held_ids, -aff[held_ids]))][:rows])
    onehot = jax.nn.one_hot(local[kept], held, dtype=jnp.float32)
    rank = np.asarray((((jnp.cumsum(onehot, 0) - onehot) * onehot).sum(-1)
                       ).astype(jnp.int32))
    counts = np.bincount(np.asarray(local)[kept], minlength=held)
    start = np.cumsum(counts) - counts
    want = np.full(rows, -1)
    want[start[np.asarray(local)[kept]] + rank] = kept
    assert np.array_equal(np.where(keep, pick, -1), want)
    group = np.repeat(np.arange(held), sizes)
    assert np.array_equal(group[keep], np.asarray(local)[pick[keep]])
    assert (group[~keep] == held - 1).all() and not keep[len(kept):].any()
    assert sizes.sum() == rows
    assert np.array_equal(sizes - np.eye(held, dtype=int)[-1]
                          * (rows - len(kept)), counts)


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
