"""Plain float32 reference of the continuous-depth LM training step.

It follows the published description of the models the benchmark runs
(pre-norm decoder, RMSNorm, rotary attention with optional qk-norm and
grouped KV heads, SwiGLU MLP, untied or tied head, next-token
cross-entropy) with each residual branch ``x + g(norm(x))`` turned into
the ODE ``dz/dt = g(norm(z))`` on ``[0, t1]``, solved by a fixed-grid
asynchronous leapfrog (ALF) unrolled by hand and differentiated directly.
The optimizer is AdamW with global-norm clipping, linear warm-up and
cosine decay.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. The step is computed block by
block so that it fits one chip: one sequence (batch row) at a time, one
layer's vector-Jacobian product at a time (its forward recomputed inside),
attention over query blocks and the loss over row chunks, each under
``jax.checkpoint``. The weights are drawn from the seed by the same
``jax.random`` calls, in the same order and op by op, as the model's
documented initialisation, and rounded to the parameter dtype the
configuration states.

``matmul="float8_e4m3fn"`` rounds both operands of every matrix product to
float8 with a per-tensor scale: the lower-precision control that the
comparison has to reject.

Given a cell's chips, the step spreads the layers over them
(``bench/reference/placement.py``): the same ops in the same order, each
layer's weights, gradients and input on its own chip.

This is the reference module of every configuration that names none: it
also holds what the harness needs to know of this block (``bench/spec.py``
states the interface): the program fields the published keys fix, the
names of the program's parameter leaves, and the FLOPs a step requires.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import branch_units, evals_per_branch
from bench.reference.placement import Placement, put

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6
LOSS_CHUNK = 512
ATTN_BLOCK = 512


class Sizes(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    n_layers: int
    qk_norm: bool
    rope_theta: float
    tie_embeddings: bool
    param_dtype: str


class Opt(NamedTuple):
    peak_lr: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: float


class Job(NamedTuple):
    ode: bool
    n_steps: int
    t1: float
    eta: float
    optimizer: Opt


def sizes(published: Dict) -> Sizes:
    """Model sizes from a configuration's published (config.json) keys."""
    c = published
    if (c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("use_qkv_bias")
            or c.get("attention_bias")):
        raise ValueError("the reference has full rotary and no bias")
    heads = c["num_attention_heads"]
    return Sizes(
        d_model=c["hidden_size"], n_heads=heads,
        n_kv_heads=c.get("num_key_value_heads", heads),
        d_head=c.get("head_dim") or c["hidden_size"] // heads,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        n_layers=c["num_hidden_layers"], qk_norm=bool(c.get("qk_norm")),
        rope_theta=float(c.get("rope_theta", 10000.0)),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        param_dtype=c.get("torch_dtype", "float32"))


def job(traffic: Dict) -> Job:
    """Integrator and optimizer settings from a traffic (training job) file."""
    ode = traffic["ode"]
    return Job(ode=bool(ode["on"]), n_steps=int(ode.get("n_steps", 0)),
               t1=float(ode.get("t1", 1.0)), eta=float(ode.get("eta", 1.0)),
               optimizer=Opt(**traffic["optimizer"]))


# --------------------------------------------------------------------------
# the program's model as this block sees it
# --------------------------------------------------------------------------

# published config.json key -> the repo ModelConfig field it must equal
_PUBLISHED = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "param_dtype"}

# a layer's parameter path in the program -> the reference's leaf name
_LAYER_LEAF = {
    ("mixer_norm", "scale"): "attn_norm", ("mixer", "wq"): "wq",
    ("mixer", "wk"): "wk", ("mixer", "wv"): "wv", ("mixer", "wo"): "wo",
    ("mixer", "q_norm", "scale"): "q_norm",
    ("mixer", "k_norm", "scale"): "k_norm",
    ("mlp_norm", "scale"): "mlp_norm", ("mlp", "w_gate"): "w_gate",
    ("mlp", "w_up"): "w_up", ("mlp", "w_down"): "w_down"}
_TOP_LEAF = {("embed",): "embed", ("head",): "head",
             ("final_norm", "scale"): "final_norm"}


def program_fields(published: Dict) -> Dict:
    """The fields of the program's ``ModelConfig`` that a configuration's
    published keys fix, with the values they must have."""
    m = sizes(published)
    want = {f: published[k] for k, f in _PUBLISHED.items()
            if k in published}
    want.update(d_head=m.d_head, qk_norm=m.qk_norm, n_layers=m.n_layers)
    return want


def layer_leaf(group: str, index: int, path: Tuple[str, ...]
               ) -> Optional[str]:
    """The reference's name of the program's leaf ``path`` inside layer
    ``index`` of ``group``: one decoder layer, the period's ``sub0``."""
    return _LAYER_LEAF.get(path) if (group, index) == ("period", 0) else None


def top_leaf(path: Tuple[str, ...]) -> Optional[str]:
    """The reference's name of a program leaf outside the layers."""
    return _TOP_LEAF.get(path)


def branch_weights(m) -> Dict[str, int]:
    """Matmul weights of one layer's attention and MLP branch."""
    attn = m.d_model * m.d_head * (2 * m.n_heads + 2 * m.n_kv_heads)
    mlp = 3 * m.d_model * m.d_ff
    return {"attn": attn, "mlp": mlp}


def required_flops_per_token(m, job, seq_len: int) -> float:
    """FLOPs per token that one training step requires: each layer's
    attention and MLP branch as ``bench/flops.py`` counts a branch;
    attention scores add, per f-eval and token, ``2 H d_head S`` forward
    (``QK^T`` and ``PV`` over the causal half of the keys), three times
    that with the backward; the head adds ``6 D V``; the embedding lookup
    is not counted."""
    w = branch_weights(m)
    units = branch_units(job.ode, job.n_steps)
    evals = evals_per_branch(job.ode, job.n_steps)
    scores = 3 * 2 * m.n_heads * m.d_head * seq_len * evals
    per_layer = units * (w["attn"] + w["mlp"]) + scores
    return m.n_layers * per_layer + 6.0 * m.d_model * m.vocab_size


# --------------------------------------------------------------------------
# initialisation (seeded; same draws as the model's documented init)
# --------------------------------------------------------------------------

def _trunc(key, shape, fan_in):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * fan_in ** -0.5)


def _embed_draw(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.02


def _round(x, dtype):
    """float32 ``x`` rounded to ``dtype`` (nearest, ties to even). A
    ``reduce_precision``, not a pair of converts, which the compiler may
    drop as excess precision inside a jitted function."""
    info = jnp.finfo(jnp.dtype(dtype))
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _stored(x, dtype):
    """The weights as the model holds them: the float32 master rounded to
    the parameter dtype; the gradient passes to the master unchanged."""
    return x + jax.lax.stop_gradient(_round(x, dtype) - x)


def _keys(seed: int, n_layers: int):
    key = jax.random.PRNGKey(seed)
    ke, kb, kh = jax.random.split(key, 3)
    layer_root = jax.random.split(kb, 2)[-1]
    layer_keys = jax.random.split(layer_root, n_layers)
    return ke, kh, layer_keys


def init_layer(key, m):
    """One layer's weights from its key, in float32."""
    d, h, kv, dh, ff = (m.d_model, m.n_heads, m.n_kv_heads, m.d_head, m.d_ff)
    k1, k2 = jax.random.split(jax.random.split(key, 1)[0])
    kq, kk, kvv, ko = jax.random.split(k1, 4)
    kg, ku, kd = jax.random.split(k2, 3)
    r = functools.partial(_round, dtype=m.param_dtype)
    lp = {"attn_norm": jnp.ones((d,), jnp.float32),
          "wq": r(_trunc(kq, (d, h * dh), d)),
          "wk": r(_trunc(kk, (d, kv * dh), d)),
          "wv": r(_trunc(kvv, (d, kv * dh), d)),
          "wo": r(_trunc(ko, (h * dh, d), h * dh)),
          "mlp_norm": jnp.ones((d,), jnp.float32),
          "w_gate": r(_trunc(kg, (d, ff), d)),
          "w_up": r(_trunc(ku, (d, ff), d)),
          "w_down": r(_trunc(kd, (ff, d), ff))}
    if m.qk_norm:
        lp["q_norm"] = jnp.ones((dh,), jnp.float32)
        lp["k_norm"] = jnp.ones((dh,), jnp.float32)
    return lp


def _init_embed(key, shape, dtype):
    return _round(_embed_draw(key, shape), dtype)


def _on(device):
    """Arrays made in this context without inputs go to ``device``."""
    return (contextlib.nullcontext() if device is None
            else jax.default_device(device))


def _init_on(device, init, key, *args):
    with _on(device):
        return init(put(key, device), *args)


def init_params(seed: int, m, place: Optional[Placement] = None) -> Dict:
    """The model's initial weights, in float32, from ``seed``; each part on
    its chip where ``place`` spreads them."""
    place = place or Placement(None, m.n_layers)
    ke, kh, layer_keys = _keys(seed, m.n_layers)
    params = {"embed": _init_on(place.first, _init_embed, ke,
                                (m.vocab_size, m.d_model), m.param_dtype)}
    with _on(place.last):
        params["final_norm"] = jnp.ones((m.d_model,), jnp.float32)
    params["layers"] = [_init_on(place.layer(i), init_layer, layer_keys[i], m)
                        for i in range(m.n_layers)]
    if not m.tie_embeddings:
        params["head"] = _init_on(place.last, _init_embed, kh,
                                  (m.d_model, m.vocab_size), m.param_dtype)
    return params


# --------------------------------------------------------------------------
# the model, one sequence at a time
# --------------------------------------------------------------------------

def _quant(x, dtype):
    """Round to ``dtype`` with a per-tensor scale onto its largest value."""
    big = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / big
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _ein(spec, a, b, mm):
    if mm is not None:
        a, b = _quant(a, mm), _quant(b, mm)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * scale


def rope(x, theta):
    """Rotary embedding of x [S, H, dh] (halves rotated against each other)."""
    s, _, dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(lp, x, m, mm):
    s = x.shape[0]
    h, kv, dh = m.n_heads, m.n_kv_heads, m.d_head
    q = _ein("sd,de->se", x, lp["wq"], mm).reshape(s, h, dh)
    k = _ein("sd,de->se", x, lp["wk"], mm).reshape(s, kv, dh)
    v = _ein("sd,de->se", x, lp["wv"], mm).reshape(s, kv, dh)
    if m.qk_norm:
        q, k = rmsnorm(q, lp["q_norm"]), rmsnorm(k, lp["k_norm"])
    q, k = rope(q, m.rope_theta), rope(k, m.rope_theta)
    k = jnp.repeat(k, h // kv, axis=1)       # query head i reads kv head i//g
    v = jnp.repeat(v, h // kv, axis=1)
    blk = min(ATTN_BLOCK, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    k_pos = jnp.arange(s)

    @jax.checkpoint
    def block(qb, start):
        scores = _ein("qhd,khd->hqk", qb, k, mm) * dh ** -0.5
        q_pos = start + jnp.arange(blk)
        causal = k_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return _ein("hqk,khd->qhd", p, v, mm)

    qb = q.reshape(s // blk, blk, h, dh)
    starts = jnp.arange(s // blk) * blk
    out = jax.lax.map(lambda a: block(*a), (qb, starts)).reshape(s, h * dh)
    return _ein("se,ed->sd", out, lp["wo"], mm)


def mlp(lp, x, m, mm):
    gate = _ein("sd,df->sf", x, lp["w_gate"], mm)
    up = _ein("sd,df->sf", x, lp["w_up"], mm)
    return _ein("sf,fd->sd", jax.nn.silu(gate) * up, lp["w_down"], mm)


def branch(g, x, job):
    """``x + g(x)`` discretely, or the ODE dz/dt = g(z) by fixed-grid ALF."""
    if not job.ode:
        return x + g(x)
    h = job.t1 / job.n_steps
    z, v = x, g(x)                       # v0 = f(z0)
    for _ in range(job.n_steps):
        k1 = z + v * (h / 2)
        u = g(k1)
        v = v + 2.0 * job.eta * (u - v)
        z = k1 + v * (h / 2)
    return z


def layer(lp, x, m, job, mm):
    lp = jax.tree_util.tree_map(lambda a: _stored(a, m.param_dtype), lp)

    def f_attn(z):
        return attention(lp, rmsnorm(z, lp["attn_norm"]), m, mm)

    def f_mlp(z):
        return mlp(lp, rmsnorm(z, lp["mlp_norm"]), m, mm)

    return branch(jax.checkpoint(f_mlp), branch(jax.checkpoint(f_attn), x,
                                                job), job)


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
    """Adds this row's parameter cotangent to ``g_acc``; returns it and the
    input cotangent."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(lambda p, xx: layer(p, xx, m, job, mm), lp, x)
        g_lp, g_x = pull(gy)
    return jax.tree_util.tree_map(jnp.add, g_acc, g_lp), g_x


@functools.partial(jax.jit, static_argnames=("mm", "wdt"),
                   donate_argnums=(0,))
def head_vjp(g_head_acc, fn_scale, head, x, labels, scale, mm, wdt):
    """Chunked next-token CE of one row: (nll sum, d final_norm, d head
    accumulated, d x). Cotangents carry the mean's ``scale``."""
    s, d = x.shape
    chunk = min(LOSS_CHUNK, s)
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of {chunk}")
    n = s // chunk

    def chunk_nll(fs, hd, xc, lc):
        fs, hd = _stored(fs, wdt), _stored(hd, wdt)
        logits = _ein("sd,dv->sv", rmsnorm(xc, fs), hd, mm)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - tgt)

    def body(carry, inp):
        nll, g_fs, g_hd = carry
        xc, lc = inp
        val, (a, b, c) = jax.value_and_grad(chunk_nll, argnums=(0, 1, 2))(
            fn_scale, head, xc, lc)
        return (nll + val, g_fs + a * scale, g_hd + b * scale), c * scale

    with jax.default_matmul_precision("highest"):
        (nll, g_fs, g_head), g_x = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.zeros_like(fn_scale), g_head_acc),
            (x.reshape(n, chunk, d), labels.reshape(n, chunk)))
    return nll, g_fs, g_head, g_x.reshape(s, d)


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_vjp(g_embed, tokens, g_x):
    return g_embed.at[tokens].add(g_x)


@jax.jit
def _sq(x):
    return jnp.sum(jnp.square(x))


def _norms_by_layer(tree_list: List[Dict], prefix="L"):
    out = {}
    for i, lp in enumerate(tree_list):
        for k, a in lp.items():
            out[f"{prefix}{i}.{k}"] = float(jnp.sqrt(_sq(a)))
    return out


def leaf_norms(params: Dict) -> Dict[str, float]:
    """Euclidean norm of every leaf, layer leaves one per layer."""
    out = {k: float(jnp.sqrt(_sq(params[k])))
           for k in ("embed", "head", "final_norm") if k in params}
    out.update(_norms_by_layer(params["layers"]))
    return out


def _flat(params: Dict) -> List:
    top = [params[k] for k in ("embed", "head", "final_norm") if k in params]
    return top + [lp[k] for lp in params["layers"] for k in sorted(lp)]


# --------------------------------------------------------------------------
# one step: loss and gradients, block by block
# --------------------------------------------------------------------------

def loss_and_grads(params: Dict, tokens: np.ndarray, labels: np.ndarray,
                   m, job, mm: Optional[str] = None,
                   place: Optional[Placement] = None):
    """Mean next-token CE over every token of the batch and its gradient,
    one row at a time and one layer at a time; each layer's input and
    gradient on its chip where ``place`` spreads the layers."""
    place = place or Placement(None, m.n_layers)
    b, s = tokens.shape
    scale = jnp.float32(1.0 / (b * s))
    head = (put(params["embed"].T, place.last) if m.tie_embeddings
            else params["head"])
    layers = params["layers"]
    nll_total = 0.0
    with _on(place.last):
        g_head = jnp.zeros(head.shape, jnp.float32)
        g_fn = jnp.zeros_like(params["final_norm"])
    g_layers = []
    for i, lp in enumerate(layers):
        with _on(place.layer(i)):
            g_layers.append(jax.tree_util.tree_map(jnp.zeros_like, lp))
    with _on(place.first):
        g_embed = jnp.zeros(params["embed"].shape, jnp.float32)
    for r in range(b):
        tok = jnp.asarray(tokens[r])
        xs = [_round(params["embed"][tok], m.param_dtype)]
        for i, lp in enumerate(layers):
            xs[i] = put(xs[i], place.layer(i))
            xs.append(_layer_fwd(lp, xs[i], m, job, mm))
        nll, gf, g_head, gy = head_vjp(
            g_head, params["final_norm"], head, put(xs[-1], place.last),
            jnp.asarray(labels[r]), scale, mm, m.param_dtype)
        nll_total += float(nll)
        g_fn = g_fn + gf
        for i in range(len(layers) - 1, -1, -1):
            g_layers[i], gy = layer_vjp(g_layers[i], layers[i], xs[i],
                                        put(gy, place.layer(i)), m, job, mm)
        del xs
        g_embed = _embed_vjp(g_embed, tok, put(gy, place.first))
    grads = {"embed": g_embed, "final_norm": g_fn, "layers": g_layers}
    if m.tie_embeddings:
        grads["embed"] = grads["embed"] + put(g_head.T, place.first)
    else:
        grads["head"] = g_head
    return nll_total / (b * s), grads


# --------------------------------------------------------------------------
# AdamW
# --------------------------------------------------------------------------

def lr_at(opt, step: int) -> float:
    """Learning rate of optimizer step ``step`` (1-based)."""
    warm = min(step / max(opt.warmup_steps, 1), 1.0)
    t = min(max((step - opt.warmup_steps)
                / max(opt.total_steps - opt.warmup_steps, 1), 0.0), 1.0)
    decay = opt.min_lr_ratio + (1 - opt.min_lr_ratio) * 0.5 * (
        1 + np.cos(np.pi * t))
    return opt.peak_lr * warm * decay


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"),
                   donate_argnums=(0,))
def _adamw_leaf(p, gs, ss, lr, b1, b2, eps, wd):
    """One AdamW update of leaf ``p`` at step len(gs), with the moments
    rebuilt from the clipped gradient history ``ss[i] * gs[i]``."""
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    for g, sc in zip(gs, ss):
        gc = g * sc
        m = b1 * m + (1 - b1) * gc
        v = b2 * v + (1 - b2) * gc * gc
    t = len(gs)
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)


def global_norm(grads: Dict) -> float:
    return float(np.sqrt(sum(float(_sq(a)) for a in _flat(grads))))


def run(seed: int, m, job, batches: Sequence, mm: Optional[str] = None,
        devices: Optional[Sequence] = None) -> Dict:
    """The first ``len(batches)`` training steps from the seeded weights,
    spread over ``devices`` (the cell's chips) where there are several.

    Returns each step's loss, the norm of every leaf of the first clipped
    gradient, and the norm of every leaf's change after the last step.
    """
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
    init = init_params(seed, m, place)
    change = leaf_norms(_tree_sub(params, init))
    return {"loss": losses, "grad": first, "change": change}


def _unflat(like: Dict, leaves: List) -> Dict:
    it = iter(leaves)
    out = {k: next(it) for k in ("embed", "head", "final_norm") if k in like}
    out["layers"] = [{k: next(it) for k in sorted(lp)}
                     for lp in like["layers"]]
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _sub(a, b):
    return a - b


def _tree_sub(a: Dict, b: Dict) -> Dict:
    return _unflat(a, [_sub(x, y) for x, y in zip(_flat(a), _flat(b))])
