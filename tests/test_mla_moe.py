"""DeepSeek-V2's mechanisms in the program: YaRN rotary against its
published formula, the attention core at value heads narrower than q/k,
the dense attention's lowered program unchanged, the MoE layer's budget
and gates against small oracles, its counters, and the serve path's
refusal of MLA."""
import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.configs.base import LayerSpec
from repro.core.ode_block import OdeSettings
from repro.models import attention as A
from repro.models import init_lm
from repro.models.common import (rope_frequencies, yarn_correction_range,
                                 yarn_mscale)
from repro.models.mla import mla_softmax_scale
from repro.models.moe import apply_moe, budget, init_moe, route_counts
from repro.models.transformer import init_layer_cache, layer_serve
from repro.train.loop import loss_and_grads


def test_yarn_matches_the_published_formula():
    """DeepSeek-V2-Lite's YaRN (dim 64, theta 1e4, factor 40 over 4,096
    positions, beta 32/1): correction range 10..23, the blend of
    extrapolated and interpolated frequencies by a linear ramp, mscale
    ratio 1 and softmax scale 192^-1/2 * 1.26080^2."""
    cfg = get_config("deepseek-v2-lite")
    assert yarn_correction_range(64, 1e4, 4096, 32.0, 1.0) == (10, 23)
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1, rel=1e-12)
    assert m * m == pytest.approx(1.58963, abs=5e-6)
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    got = np.asarray(rope_frequencies(64, 1e4, (40.0, 4096, 32.0, 1.0)))
    i = np.arange(32)
    extra = 1.0 / 1e4 ** (2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert got[10] == pytest.approx(extra[10], rel=2e-6)      # ramp starts
    assert got[23] == pytest.approx(extra[23] / 40, rel=2e-6)  # and ends
    np.testing.assert_array_equal(rope_frequencies(64, 1e4),
                                  rope_frequencies(64, 1e4, None))


def _qkv(b, s, h, dqk, dv, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, dqk)),
            jax.random.normal(ks[1], (b, s, h, dqk)),
            jax.random.normal(ks[2], (b, s, h, dv)))


@pytest.mark.parametrize("path", ["flash", "chunked"])
def test_attention_core_at_narrower_value_heads(path):
    """The chunked paths at v width 16 against q/k width 24 and a scale
    of their own, output and gradients, against the direct einsum."""
    cfg = smoke_config("qwen3-1.7b")
    b, s, h = 1, 200, 2
    q, k, v = _qkv(b, s, h, 24, 16)
    pos = jnp.arange(s, dtype=jnp.int32)
    scale = 0.37
    fn = A._sdpa_chunked_flash if path == "flash" else A._sdpa_chunked

    def chunked(q, k, v):
        return fn(cfg, q, k, v, pos, pos, 0, block_q=64, block_kv=32,
                  scale=scale)

    def direct(q, k, v):
        return A._sdpa_direct(cfg, q, k, v, A._mask_bias(pos, pos, 0),
                              scale)

    got, want = chunked(q, k, v), direct(q, k, v)
    assert got.shape == (b, s, h, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(5), got.shape)
    g_got = jax.grad(lambda *a: (chunked(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: (direct(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    for a, w in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


# sha256 of the lowered gradient of attention_train at the dense cells'
# widths (2 x 4,096 tokens in bfloat16), as the parent of the value-width
# change lowered it: the change leaves their program as it was
DENSE_ATTENTION_HLO = {
    "stablelm-1.6b":
        "d911311afc7607a8d4e172c9d44b599ac1a616277fdb3807ab5f6e72d733a694",
    "qwen3-1.7b":
        "11dfce39b4bc2ad7ca4b9ecc69aa3035084368442a15be67d9adcc55e3b63b1b",
}


@pytest.mark.parametrize("arch", sorted(DENSE_ATTENTION_HLO))
def test_dense_attention_program_unchanged(arch):
    cfg = get_config(arch)
    p = jax.eval_shape(lambda: A.init_attention(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((2, 4096, cfg.d_model), jnp.bfloat16)

    def loss(p, x):
        return A.attention_train(p, cfg, LayerSpec(), x).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        DENSE_ATTENTION_HLO[arch]


def _moe_cfg(**kw):
    base = dataclasses.replace(
        smoke_config("deepseek-v2-lite"), moe_experts=8, moe_top_k=2,
        moe_shared_experts=0, moe_d_ff=8, d_model=16)
    return dataclasses.replace(base, **kw).validate()


def _expert(params, j, x):
    h = x @ params["w_gate"][j]
    return (h * jax.nn.sigmoid(h) * (x @ params["w_up"][j])) \
        @ params["w_down"][j]


def _oracle(params, cfg, x, rows):
    """The held experts' part, token by token: the ``rows`` held
    assignments of highest routing probability (ties in (token, choice)
    order), each its expert's output times its gate."""
    xt = np.asarray(x.reshape(-1, x.shape[-1]), np.float64)
    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(xt, jnp.float32) @ params["router"], axis=-1))
    k, first, held = cfg.moe_top_k, cfg.moe_first_expert, cfg.n_experts_held
    idx = np.argsort(-probs, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(probs, idx, 1)
    gates = vals / vals.sum(1, keepdims=True) if cfg.moe_norm_topk else vals
    cand = [(-vals[t, c], t * k + c) for t in range(len(xt)) for c in range(k)
            if first <= idx[t, c] < first + held]
    kept = {a for _, a in sorted(cand)[:rows]}
    out = np.zeros_like(xt)
    for a in kept:
        t, c = divmod(a, k)
        out[t] += gates[t, c] * np.asarray(
            _expert(params, idx[t, c] - first, jnp.asarray(xt[t],
                                                           jnp.float32)))
    return out, len(cand), len(kept)


@pytest.mark.parametrize("case", ["binding", "not-binding", "ties"])
def test_moe_keeps_the_highest_affinity_budget(case):
    cfg = _moe_cfg(moe_experts_held=4, moe_first_expert=2,
                   moe_capacity_factor=0.6 if case != "not-binding" else 2.0)
    params = init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 16))
    if case == "ties":        # repeated tokens: equal affinities
        x = jnp.tile(x[:, :4], (1, 6, 1))
    rows = budget(48, cfg, cfg.moe_capacity_factor)
    want, routed, kept = _oracle(params, cfg, x, rows)
    got = apply_moe(params, cfg, x).reshape(-1, 16)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    counts = route_counts(params, cfg, x)
    assert int(counts.routed) == routed
    assert int(counts.dropped) == routed - kept
    assert (routed > rows) == (case != "not-binding")


@pytest.mark.parametrize("norm", [False, True], ids=["raw", "renormalised"])
def test_moe_gate_values(norm):
    """With ``moe_norm_topk`` off the gates are the softmax probabilities
    themselves; on, they sum to one over the top k."""
    cfg = _moe_cfg(moe_norm_topk=norm, moe_capacity_factor=1.0)
    params = init_moe(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 16))
    want, routed, kept = _oracle(params, cfg, x, 16)
    assert routed == kept == 16
    got = apply_moe(params, cfg, x).reshape(-1, 16)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    other, _, _ = _oracle(
        params, dataclasses.replace(cfg, moe_norm_topk=not norm), x, 16)
    assert not np.allclose(np.asarray(got), other, rtol=1e-3, atol=1e-4)


def test_train_step_counts_the_routing():
    """Every MoE branch routes its entry state once: holding every expert,
    each of them routes all N k assignments and drops none."""
    cfg = smoke_config("deepseek-v2-lite", OdeSettings(mode="off"))
    assert cfg.n_experts_held == cfg.moe_experts
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                            jnp.int32) for k in ("tokens", "labels")}
    _, stats, _ = loss_and_grads(params, batch, cfg=cfg)
    n_moe = sum(l.mlp == "moe" for l in cfg.layers())
    assert int(stats.moe_routed) == n_moe * 2 * 16 * cfg.moe_top_k
    assert int(stats.moe_dropped) == 0


def test_serve_path_refuses_mla():
    cfg = smoke_config("deepseek-v2-lite")
    spec = cfg.prelude[0]
    assert spec.mixer == "mla"
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        init_layer_cache(cfg, spec, 1, 8)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        layer_serve({}, cfg, spec, jnp.zeros((1, 1, cfg.d_model)), None,
                    jnp.int32(0), "decode")
