"""Plain float32 reference of DeepSeek-V2-Lite's continuous-depth training
step, for one chip's share of an expert-parallel deployment.

It follows the DeepSeek-V2 paper (arXiv:2405.04434) and the published
``config.json`` and modeling code of DeepSeek-V2-Lite: pre-norm decoder,
RMSNorm (eps 1e-6), multi-head latent attention without a query latent
(queries ``x W_q``; keys and values from a ``kv_lora_rank``-wide latent
``c_kv = RMSNorm(x W_kva[:r])`` through ``W_kvb``; one ``qk_rope_head_dim``
rotary key ``x W_kva[r:]`` shared by every head) with YaRN rotary and its
softmax scale ``(nope + rope)^-1/2 mscale(factor, mscale_all_dim)^2``; a
dense SwiGLU first layer; then DeepSeekMoE layers: a float32 softmax router
over all ``n_router_experts``, greedy top-k, gate values renormalised only
with ``norm_topk_prob``, SwiGLU routed experts and shared experts of
``n_shared_experts`` times the expert width; an untied head; next-token
cross-entropy. Each residual branch ``x + g(norm(x))`` is the ODE
``dz/dt = g(norm(z))`` on ``[0, t1]``, solved by ``lm.branch`` (ALF unrolled
by hand) and differentiated directly; AdamW is ``lm.py``'s.

The share (the configuration's deployment): the chip holds
``n_routed_experts`` experts from ``first_held_expert`` on, and computes
their part of each MoE layer for the tokens routed to them; what the absent
experts would add is left out, as in the program. Held assignments above
the device budget ``B = ceil(N k held / E * device_capacity_factor)`` for
the ``N`` tokens of a step drop, the lowest routing probability first,
ties broken by (token, choice) order (DeepSeek-V2 §3.2.3). The routed part
is the dense product of every held expert with every token, weighted by a
combine matrix that is zero off the kept assignments: no sort, gather or
grouped product.

Departures from the published model, all in the configuration's
``assumed``: the rotary columns are rotated half against half where the
checkpoint stores them interleaved (immaterial with random weights); no
auxiliary balance loss.

Everything is ``jax.numpy`` in float32 under ``highest`` precision, computed
block by block like ``lm.py``, with one difference: the budget ranks the
assignments of every token of the step, so a layer runs over the whole
batch at once (``lm.py`` runs one sequence at a time). Attention still runs
one query block at a time under ``jax.checkpoint``, and one layer's
vector-Jacobian product at a time. The weights are drawn from the seed by
the program's ``jax.random`` calls in its order; the router stays float32.
``matmul="float8_e4m3fn"`` rounds both operands of every matrix product to
float8, as in ``lm.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import branch_units, evals_per_branch
from bench.reference import lm
from bench.reference.lm import (  # noqa: F401  (REFERENCE_API)
    _adamw_leaf, _embed_vjp, _flat, _on, _round, _stored, _tree_sub,
    _unflat, _trunc, _embed_draw, global_norm, head_vjp, job, leaf_norms,
    lr_at, rmsnorm)
from bench.reference.placement import Placement, put

HIGHEST = lm.HIGHEST
ATTN_BLOCK = lm.ATTN_BLOCK


class Sizes(NamedTuple):
    d_model: int
    n_heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    d_ff: int                 # the dense layers' SwiGLU width
    expert_d_ff: int
    n_experts: int            # the router's outputs
    n_held: int               # experts held on this chip
    first_held: int
    top_k: int
    n_shared: int
    norm_topk: bool
    capacity: float           # the device budget's factor
    n_dense: int              # leading dense layers
    vocab_size: int
    n_layers: int
    rope_theta: float
    yarn: Tuple[float, ...]   # factor, original length, beta_fast,
    #                           beta_slow, mscale, mscale_all_dim
    param_dtype: str


def sizes(published: Dict) -> Sizes:
    """Model sizes from the configuration's published (config.json) keys,
    plus the share's: ``n_router_experts``, ``first_held_expert`` and
    ``device_capacity_factor``."""
    c = published
    rs = c["rope_scaling"]
    if (c.get("q_lora_rank") is not None or c.get("attention_bias")
            or c.get("hidden_act") != "silu"
            or c.get("scoring_func") != "softmax"
            or c.get("topk_method") != "greedy" or c.get("n_group") != 1
            or c.get("routed_scaling_factor") != 1
            or c.get("moe_layer_freq") != 1 or rs.get("type") != "yarn"
            or c.get("tie_word_embeddings")):
        raise ValueError("the reference has no query latent, no bias, SiLU, "
                         "greedy softmax routing in one group, a MoE layer "
                         "after each dense one, YaRN and an untied head")
    return Sizes(
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        kv_rank=c["kv_lora_rank"], nope_dim=c["qk_nope_head_dim"],
        rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], expert_d_ff=c["moe_intermediate_size"],
        n_experts=c["n_router_experts"], n_held=c["n_routed_experts"],
        first_held=c["first_held_expert"], top_k=c["num_experts_per_tok"],
        n_shared=c["n_shared_experts"], norm_topk=bool(c["norm_topk_prob"]),
        capacity=float(c["device_capacity_factor"]),
        n_dense=c["first_k_dense_replace"], vocab_size=c["vocab_size"],
        n_layers=c["num_hidden_layers"], rope_theta=float(c["rope_theta"]),
        yarn=(float(rs["factor"]), int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]),
              float(rs["mscale"]), float(rs["mscale_all_dim"])),
        param_dtype=c.get("torch_dtype", "float32"))


# --------------------------------------------------------------------------
# the program's model as this block sees it
# --------------------------------------------------------------------------

def program_fields(published: Dict) -> Dict:
    """The fields of the program's ``ModelConfig`` that the published keys
    fix, with the values they must have."""
    m = sizes(published)
    factor, original, fast, slow, mscale, mscale_all = m.yarn
    return {
        "d_model": m.d_model, "n_heads": m.n_heads, "n_kv_heads": m.n_heads,
        "mla_kv_rank": m.kv_rank, "mla_nope_dim": m.nope_dim,
        "mla_rope_dim": m.rope_dim, "mla_v_dim": m.v_dim,
        "prelude_d_ff": m.d_ff, "moe_d_ff": m.expert_d_ff,
        "moe_experts": m.n_experts, "moe_experts_held": m.n_held,
        "moe_first_expert": m.first_held, "moe_top_k": m.top_k,
        "moe_shared_experts": m.n_shared, "moe_norm_topk": m.norm_topk,
        "moe_capacity_factor": m.capacity, "vocab_size": m.vocab_size,
        "n_layers": m.n_layers, "rope_theta": m.rope_theta,
        "rope_yarn_factor": factor, "rope_yarn_original_len": original,
        "rope_yarn_beta_fast": fast, "rope_yarn_beta_slow": slow,
        "rope_yarn_mscale": mscale, "rope_yarn_mscale_all_dim": mscale_all,
        "tie_embeddings": False, "param_dtype": m.param_dtype}


_MLA_LEAF = {
    ("mixer_norm", "scale"): "attn_norm", ("mixer", "wq"): "wq",
    ("mixer", "wkv_a"): "wkv_a", ("mixer", "kv_norm", "scale"): "kv_norm",
    ("mixer", "wkv_b"): "wkv_b", ("mixer", "wo"): "wo",
    ("mlp_norm", "scale"): "mlp_norm"}
_DENSE_LEAF = {**_MLA_LEAF, **{
    ("mlp", "w_gate"): "w_gate", ("mlp", "w_up"): "w_up",
    ("mlp", "w_down"): "w_down"}}
_MOE_LEAF = {**_MLA_LEAF, **{
    ("mlp", "router"): "router", ("mlp", "w_gate"): "experts_gate",
    ("mlp", "w_up"): "experts_up", ("mlp", "w_down"): "experts_down",
    ("mlp", "shared", "w_gate"): "shared_gate",
    ("mlp", "shared", "w_up"): "shared_up",
    ("mlp", "shared", "w_down"): "shared_down"}}


def layer_leaf(group: str, index: int, path: Tuple[str, ...]
               ) -> Optional[str]:
    """The reference's name of the program's leaf ``path``: the prelude's
    layer is the dense one, the period's ``sub0`` a MoE layer."""
    if group == "prelude":
        return _DENSE_LEAF.get(path)
    return _MOE_LEAF.get(path) if index == 0 else None


top_leaf = lm.top_leaf


def mla_weights(m) -> int:
    h, dqk = m.n_heads, m.nope_dim + m.rope_dim
    return (m.d_model * h * dqk + m.d_model * (m.kv_rank + m.rope_dim)
            + m.kv_rank * h * (m.nope_dim + m.v_dim) + h * m.v_dim * m.d_model)


def budget(n_tokens: int, m) -> int:
    """Rows of held-expert assignments one MoE call computes."""
    rows = math.ceil(n_tokens * m.top_k * m.n_held / m.n_experts
                     * m.capacity)
    return max(1, min(rows, n_tokens * m.top_k))


def required_flops_per_token(m, job, seq_len: int) -> float:
    """FLOPs per token that one training step requires: each layer's MLA
    and MLP branch as ``bench/flops.py`` counts a branch of its weights;
    the attention core adds, per f-eval and token, ``H (dqk + dv) S``
    forward (``QK^T`` over 192-wide and ``PV`` over 128-wide heads, each
    over the causal half of the keys), three times that with the backward;
    a MoE branch's weights are its router, its shared experts and its held
    experts at the budget's rows a token (``k held / E`` times the factor,
    at most ``k``); the head adds ``6 D V``; the embedding lookup is not
    counted."""
    units = branch_units(job.ode, job.n_steps)
    evals = evals_per_branch(job.ode, job.n_steps)
    d, f = m.d_model, m.expert_d_ff
    core = 3 * m.n_heads * (m.nope_dim + m.rope_dim + m.v_dim) * seq_len \
        * evals
    rows = min(m.top_k * m.n_held / m.n_experts * m.capacity, m.top_k)
    moe = d * m.n_experts + 3 * d * f * (m.n_shared + rows)
    dense = 3 * d * m.d_ff
    per_layer = [units * (mla_weights(m) + (dense if i < m.n_dense else moe))
                 + core for i in range(m.n_layers)]
    return sum(per_layer) + 6.0 * d * m.vocab_size


# --------------------------------------------------------------------------
# initialisation (seeded; the program's draws in its order)
# --------------------------------------------------------------------------

def _keys(seed: int, m):
    """The embedding's, the head's and each layer's key, as ``init_lm``
    and ``init_blocks`` split them: the dense layers from the prelude's
    keys, each MoE layer from its period's key split once."""
    ke, kb, kh = jax.random.split(jax.random.PRNGKey(seed), 3)
    keys = jax.random.split(kb, m.n_dense + 1)
    periods = jax.random.split(keys[-1], m.n_layers - m.n_dense)
    return ke, kh, ([keys[i] for i in range(m.n_dense)]
                    + [jax.random.split(k, 1)[0] for k in periods])


def init_layer(key, m, dense: bool = False):
    """One layer's weights from its key, in float32: a MoE layer, or with
    ``dense`` the leading dense one."""
    d, h, r = m.d_model, m.n_heads, m.kv_rank
    dn, dr, dv = m.nope_dim, m.rope_dim, m.v_dim
    k1, k2 = jax.random.split(key)
    kq, ka, kb, ko = jax.random.split(k1, 4)
    r_ = functools.partial(_round, dtype=m.param_dtype)
    lp = {"attn_norm": jnp.ones((d,), jnp.float32),
          "wq": r_(_trunc(kq, (d, h * (dn + dr)), d)),
          "wkv_a": r_(_trunc(ka, (d, r + dr), d)),
          "kv_norm": jnp.ones((r,), jnp.float32),
          "wkv_b": r_(_trunc(kb, (r, h * (dn + dv)), r)),
          "wo": r_(_trunc(ko, (h * dv, d), h * dv)),
          "mlp_norm": jnp.ones((d,), jnp.float32)}
    if dense:
        kg, ku, kd = jax.random.split(k2, 3)
        lp.update(w_gate=r_(_trunc(kg, (d, m.d_ff), d)),
                  w_up=r_(_trunc(ku, (d, m.d_ff), d)),
                  w_down=r_(_trunc(kd, (m.d_ff, d), m.d_ff)))
        return lp
    e, f, fs = m.n_held, m.expert_d_ff, m.expert_d_ff * m.n_shared
    kr, kg, ku, kd, ks = jax.random.split(k2, 5)
    sg, su, sd = jax.random.split(ks, 3)
    lp.update(router=_trunc(kr, (d, m.n_experts), d),
              experts_gate=r_(_trunc(kg, (e, d, f), d)),
              experts_up=r_(_trunc(ku, (e, d, f), d)),
              experts_down=r_(_trunc(kd, (e, f, d), f)),
              shared_gate=r_(_trunc(sg, (d, fs), d)),
              shared_up=r_(_trunc(su, (d, fs), d)),
              shared_down=r_(_trunc(sd, (fs, d), fs)))
    return lp


def _init_on(device, init, key, *args, **kw):
    with _on(device):
        return init(put(key, device), *args, **kw)


def init_params(seed: int, m, place: Optional[Placement] = None) -> Dict:
    """The model's initial weights, in float32, from ``seed``; each part on
    its chip where ``place`` spreads them."""
    place = place or Placement(None, m.n_layers)
    ke, kh, layer_keys = _keys(seed, m)
    params = {"embed": _init_on(place.first, _init_embed, ke,
                                (m.vocab_size, m.d_model), m.param_dtype)}
    with _on(place.last):
        params["final_norm"] = jnp.ones((m.d_model,), jnp.float32)
    params["layers"] = [_init_on(place.layer(i), init_layer, layer_keys[i], m,
                                 dense=i < m.n_dense)
                        for i in range(m.n_layers)]
    params["head"] = _init_on(place.last, _init_embed, kh,
                              (m.d_model, m.vocab_size), m.param_dtype)
    return params


def _init_embed(key, shape, dtype):
    return _round(_embed_draw(key, shape), dtype)


# --------------------------------------------------------------------------
# the model, a layer over the whole batch
# --------------------------------------------------------------------------

_ein = lm._ein


def _yarn(m, s: int):
    """cos and sin [S, rope/2] of YaRN rotary at positions 0..S-1, by the
    published formula (HF ``DeepseekV2YarnRotaryEmbedding``) in float64."""
    factor, original, fast, slow, mscale, mscale_all = m.yarn
    dim, base = m.rope_dim, m.rope_theta

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    inv_freq = inter * ramp + extra * (1.0 - ramp)
    scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32))
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(m) -> float:
    mult = yarn_mscale(m.yarn[0], m.yarn[5])
    return (m.nope_dim + m.rope_dim) ** -0.5 * mult * mult


def _rotate(x, cos, sin):
    """x [..., S, H, d] rotated half against half."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s_ = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], -1)


def mla(lp, x, m, mm):
    """Multi-head latent attention of x [..., S, D]."""
    *lead, s, d = x.shape
    h, r, dn, dr, dv = (m.n_heads, m.kv_rank, m.nope_dim, m.rope_dim,
                        m.v_dim)
    q = _ein("...d,de->...e", x, lp["wq"], mm).reshape(*lead, s, h, dn + dr)
    kva = _ein("...d,de->...e", x, lp["wkv_a"], mm)
    c_kv = rmsnorm(kva[..., :r], lp["kv_norm"])
    kv = _ein("...r,re->...e", c_kv, lp["wkv_b"], mm).reshape(
        *lead, s, h, dn + dv)
    cos, sin = _yarn(m, s)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos, sin)], -1)
    k_pe = _rotate(kva[..., None, r:], cos, sin)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (*lead, s, h, dr))], -1)
    v = kv[..., dn:]
    blk = min(ATTN_BLOCK, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    scale = softmax_scale(m)
    k_pos = jnp.arange(s)

    @jax.checkpoint
    def block(qb, start):
        scores = _ein("...qhd,...khd->...hqk", qb, k, mm) * scale
        causal = k_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _ein("...hqk,...khd->...qhd", p, v, mm)

    qb = jnp.moveaxis(q.reshape(*lead, s // blk, blk, h, dn + dr),
                      len(lead), 0)
    out = jax.lax.map(lambda a: block(*a), (qb, jnp.arange(s // blk) * blk))
    out = jnp.moveaxis(out, 0, len(lead)).reshape(*lead, s, h * dv)
    return _ein("...e,ed->...d", out, lp["wo"], mm)


def swiglu(x, w_gate, w_up, w_down, mm, weight=None):
    g = _ein("...d,df->...f", x, w_gate, mm)
    u = _ein("...d,df->...f", x, w_up, mm)
    a = jax.nn.silu(g) * u
    if weight is not None:
        a = a * weight
    return _ein("...f,fd->...d", a, w_down, mm)


def combine(lp, xt, m, mm):
    """The combine matrix [N, held] of N tokens: each kept assignment's gate
    value at its held expert, zero elsewhere."""
    n = xt.shape[0]
    probs = jax.nn.softmax(_ein("nd,de->ne", xt, lp["router"], mm), axis=-1)
    vals, idx = jax.lax.top_k(probs, m.top_k)
    gates = vals / jnp.sum(vals, -1, keepdims=True) if m.norm_topk else vals
    local = idx - m.first_held
    held = (local >= 0) & (local < m.n_held)
    # rank of each assignment by affinity, held first, ties in (token,
    # choice) order; the budget keeps the first B
    order = jnp.argsort(-jnp.where(held, vals, -1.0).reshape(-1),
                        stable=True)
    rank = jnp.zeros(n * m.top_k, jnp.int32).at[order].set(
        jnp.arange(n * m.top_k, dtype=jnp.int32)).reshape(n, m.top_k)
    keep = held & (rank < budget(n, m))
    tok = jnp.broadcast_to(jnp.arange(n)[:, None], idx.shape)
    return jnp.zeros((n, m.n_held), jnp.float32).at[
        tok, jnp.where(keep, local, 0)].add(jnp.where(keep, gates, 0.0))


def moe(lp, x, m, mm):
    """The held experts' part of a DeepSeekMoE layer over every token of
    x [..., D], plus the shared experts."""
    d, e, f = m.d_model, m.n_held, m.expert_d_ff
    xt = x.reshape(-1, d)
    weight = jnp.repeat(combine(lp, xt, m, mm), f, axis=1)   # [N, held*F]
    routed = swiglu(
        xt, jnp.moveaxis(lp["experts_gate"], 0, 1).reshape(d, e * f),
        jnp.moveaxis(lp["experts_up"], 0, 1).reshape(d, e * f),
        lp["experts_down"].reshape(e * f, d), mm, weight)
    shared = swiglu(xt, lp["shared_gate"], lp["shared_up"],
                    lp["shared_down"], mm)
    return (routed + shared).reshape(x.shape)


def layer(lp, x, m, job, mm):
    lp = {k: (a if k == "router" else _stored(a, m.param_dtype))
          for k, a in lp.items()}

    def f_attn(z):
        return mla(lp, rmsnorm(z, lp["attn_norm"]), m, mm)

    def f_mlp(z):
        zn = rmsnorm(z, lp["mlp_norm"])
        if "router" in lp:
            return moe(lp, zn, m, mm)
        return swiglu(zn, lp["w_gate"], lp["w_up"], lp["w_down"], mm)

    return lm.branch(jax.checkpoint(f_mlp),
                     lm.branch(jax.checkpoint(f_attn), x, job), job)


# --------------------------------------------------------------------------
# jitted blocks
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "job", "mm"))
def _layer_fwd(lp, x, m, job, mm):
    with jax.default_matmul_precision("highest"):
        return layer(lp, x, m, job, mm)


@functools.partial(jax.jit, static_argnames=("m", "job", "mm"),
                   donate_argnums=(0,))
def layer_vjp(g_acc, lp, x, gy, m, job, mm):
    """Adds the batch's parameter cotangent to ``g_acc``; returns it and
    the input cotangent. x: [..., S, D], every sequence of the step."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(lambda p, xx: layer(p, xx, m, job, mm), lp, x)
        g_lp, g_x = pull(gy)
    return jax.tree_util.tree_map(jnp.add, g_acc, g_lp), g_x


# --------------------------------------------------------------------------
# one step: loss and gradients, layer by layer
# --------------------------------------------------------------------------

def loss_and_grads(params: Dict, tokens: np.ndarray, labels: np.ndarray,
                   m, job, mm: Optional[str] = None,
                   place: Optional[Placement] = None):
    """Mean next-token CE over every token of the batch and its gradient:
    each layer over the whole batch, the head one sequence at a time."""
    place = place or Placement(None, m.n_layers)
    b, s = tokens.shape
    scale = jnp.float32(1.0 / (b * s))
    layers = params["layers"]
    with _on(place.last):
        g_head = jnp.zeros(params["head"].shape, jnp.float32)
        g_fn = jnp.zeros_like(params["final_norm"])
    g_layers = []
    for i, lp in enumerate(layers):
        with _on(place.layer(i)):
            g_layers.append(jax.tree_util.tree_map(jnp.zeros_like, lp))
    tok = jnp.asarray(tokens)
    xs = [_round(params["embed"][tok], m.param_dtype)]
    for i, lp in enumerate(layers):
        xs[i] = put(xs[i], place.layer(i))
        xs.append(_layer_fwd(lp, xs[i], m, job, mm))
    nll_total, gys = 0.0, []
    top = put(xs[-1], place.last)
    for r in range(b):
        nll, gf, g_head, gy = head_vjp(
            g_head, params["final_norm"], params["head"], top[r],
            jnp.asarray(labels[r]), scale, mm, m.param_dtype)
        nll_total += float(nll)
        g_fn = g_fn + gf
        gys.append(gy)
    gy = jnp.stack(gys)
    del top, gys
    for i in range(len(layers) - 1, -1, -1):
        g_layers[i], gy = layer_vjp(g_layers[i], layers[i], xs[i],
                                    put(gy, place.layer(i)), m, job, mm)
        xs[i] = None
    with _on(place.first):
        g_embed = jnp.zeros(params["embed"].shape, jnp.float32)
    g_embed = _embed_vjp(g_embed, tok.reshape(-1),
                         put(gy, place.first).reshape(b * s, -1))
    grads = {"embed": g_embed, "final_norm": g_fn, "head": g_head,
             "layers": g_layers}
    return nll_total / (b * s), grads


def run(seed: int, m, job, batches: Sequence, mm: Optional[str] = None,
        devices: Optional[Sequence] = None) -> Dict:
    """The first ``len(batches)`` training steps from the seeded weights,
    as ``lm.run``: each step's loss, the norm of every leaf of the first
    clipped gradient, and of every leaf's change after the last step."""
    opt = job.optimizer
    place = Placement(devices, m.n_layers)
    params = init_params(seed, m, place)
    history, scales, losses = [], [], []
    first = None
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(params, tokens, labels, m, job, mm,
                                     place)
        losses.append(loss)
        gnorm = global_norm(grads)
        sc = min(1.0, opt.clip_norm / max(gnorm, 1e-12))
        if first is None:
            first = {k: v * sc for k, v in leaf_norms(grads).items()}
        history.append(_flat(grads))
        scales.append(jnp.float32(sc))
        del grads
        lr = jnp.float32(lr_at(opt, t))
        flat = _flat(params)
        new = [_adamw_leaf(p, [h[i] for h in history], scales, lr,
                           b1=opt.b1, b2=opt.b2, eps=opt.eps,
                           wd=opt.weight_decay)
               for i, p in enumerate(flat)]
        params = _unflat(params, new)
        del flat, new
    del history
    change = leaf_norms(_tree_sub(params, init_params(seed, m, place)))
    return {"loss": losses, "grad": first, "change": change}
