"""Sharding rules: parameters, optimizer state, inputs, KV caches.

Mesh axes: ('pod', 'data', 'model') multi-pod, ('data', 'model') single-pod.
'pod' x 'data' is pure data parallelism; 'model' is tensor/expert parallel.

Strategies (ModelConfig.sharding):
  * 'dp'      — pure data parallel: params replicated, batch sharded over
    every mesh axis (incl. 'model') when divisible. Right for the <3B archs
    on a 256-chip pod: TP would make them collective-bound (measured in
    EXPERIMENTS.md §Perf).
  * 'tp'      — 1D: weights sharded over 'model' only (small archs).
  * 'fsdp_tp' — 2D: the same 'model' sharding plus the complementary big dim
    over 'data' (FSDP-style; GSPMD inserts the per-layer all-gathers).
    Required for the >8B archs: e.g. grok-1 bf16 params = 628 GB -> 2.45
    GB/chip at 16x16.

Every rule is divisibility-guarded: a dim is sharded only if the axis size
divides it, else that dim stays replicated (e.g. grok's 8 experts on a
16-way model axis fall back to d_ff-sharding).

Optimizer state inherits the param sharding leaf-for-leaf (ZeRO-1: the f32
master/m/v live fully sharded; nothing is replicated that isn't replicated
in the params).

xLSTM params are replicated (125M: DP-only is the right config — noted in
DESIGN.md); its activations shard on batch.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

Pytree = Any


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _maybe(dim: int, axis: Optional[str], mesh: Mesh) -> Optional[str]:
    """Shard `dim` over `axis` only if divisible."""
    if axis is None or axis not in mesh.axis_names:
        return None
    return axis if dim % _axis_size(mesh, axis) == 0 else None


def _leaf_spec(cfg: ModelConfig, mesh: Mesh, path: Tuple[str, ...],
               shape: Tuple[int, ...]) -> P:
    if cfg.sharding == "dp":
        return P()
    fsdp = cfg.sharding == "fsdp_tp"
    data = "data" if fsdp else None
    name = path[-1]

    # xLSTM mixers: replicate (see module docstring)
    if name in ("w_i", "w_f", "f_bias", "r_in", "out_norm") or \
            (name in ("w_up", "w_q", "w_k", "w_v", "w_down", "w_in", "bias")
             and _in_lstm_path(cfg, path)):
        return P()

    if len(shape) <= 1:
        return P()  # norms, biases, scalars

    if name == "embed":
        return P(_maybe(shape[0], data, mesh), _maybe(shape[1], "model", mesh))
    if name == "head":
        return P(_maybe(shape[0], data, mesh), _maybe(shape[1], "model", mesh))

    # attention
    if name == "wq":
        # shard fused (H*dh) only when it splits on whole heads
        ok = cfg.n_heads % _axis_size(mesh, "model") == 0
        return P(_maybe(shape[0], data, mesh),
                 _maybe(shape[1], "model", mesh) if ok else None)
    if name in ("wk", "wv"):
        # K/V: intra-head splits (kv_heads < model axis) force a psum into
        # EVERY attention tile (contraction over a sharded d_head); the
        # projections are tiny — replicate them and keep K/V activations
        # whole instead (measured on qwen3 prefill_32k; §Perf)
        ok = cfg.n_kv_heads % _axis_size(mesh, "model") == 0
        return P(_maybe(shape[0], data, mesh),
                 _maybe(shape[1], "model", mesh) if ok else None)
    if name == "wo":
        return P(_maybe(shape[0], "model", mesh), _maybe(shape[1], data, mesh))

    # dense mlp
    if name in ("w_gate", "w_up") and len(shape) == 2:
        return P(_maybe(shape[0], data, mesh), _maybe(shape[1], "model", mesh))
    if name == "w_down" and len(shape) == 2:
        return P(_maybe(shape[0], "model", mesh), _maybe(shape[1], data, mesh))

    # moe experts [E, D, F] / [E, F, D]
    if name in ("w_gate", "w_up") and len(shape) == 3:
        ep = _maybe(shape[0], "model", mesh)
        if ep:
            return P(ep, _maybe(shape[1], data, mesh), None)
        return P(None, _maybe(shape[1], data, mesh),
                 _maybe(shape[2], "model", mesh))
    if name == "w_down" and len(shape) == 3:
        ep = _maybe(shape[0], "model", mesh)
        if ep:
            return P(ep, None, _maybe(shape[2], data, mesh))
        return P(None, _maybe(shape[1], "model", mesh),
                 _maybe(shape[2], data, mesh))
    if name == "router":
        return P()

    # mamba
    if name == "in_proj":
        return P(_maybe(shape[0], data, mesh), _maybe(shape[1], "model", mesh))
    if name == "conv_w":
        return P(None, _maybe(shape[1], "model", mesh))
    if name == "x_proj":
        return P(_maybe(shape[0], "model", mesh), None)
    if name == "dt_proj":
        return P(None, _maybe(shape[1], "model", mesh))
    if name == "A_log":
        return P(_maybe(shape[0], "model", mesh), None)
    if name == "out_proj":
        return P(_maybe(shape[0], "model", mesh), _maybe(shape[1], data, mesh))

    return P()


def _in_lstm_path(cfg: ModelConfig, path: Tuple[str, ...]) -> bool:
    """True if this param belongs to an mLSTM/sLSTM mixer (pattern-level:
    any layer spec in the config uses those mixers and the path is a mixer)."""
    if "mixer" not in path:
        return False
    return any(spec.mixer in ("mlstm", "slstm")
               for spec in cfg.prelude + cfg.period)


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
    return tuple(names)


def param_shardings(cfg: ModelConfig, mesh: Mesh, params_like: Pytree) -> Pytree:
    """NamedSharding tree matching ``params_like`` (arrays or ShapeDtype)."""

    def one(path, leaf):
        names = _path_names(path)
        shape = tuple(leaf.shape)
        # scanned-period params carry a leading n_periods dim: apply the
        # rule to the per-layer shape, replicate the stack dim
        if "period" in names:
            spec = P(None, *_leaf_spec(cfg, mesh, names, shape[1:]))
        else:
            spec = _leaf_spec(cfg, mesh, names, shape)
        if len(spec) > len(shape):
            spec = P(*spec[:len(shape)])
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_like)


def batch_shardings(cfg: ModelConfig, mesh: Mesh, batch_like: Pytree) -> Pytree:
    # pure-DP archs also spread the batch over the (otherwise idle) model
    # axis when it divides
    candidates = []
    if cfg.sharding == "dp":
        candidates.append(dp_axes(mesh) + ("model",))
    candidates.append(dp_axes(mesh))

    def one(leaf):
        nbatch = leaf.shape[0]
        lead = None
        for axes in candidates:
            total = 1
            for a in axes:
                total *= _axis_size(mesh, a)
            if total > 1 and nbatch % total == 0:
                lead = axes
                break
        return NamedSharding(mesh, P(lead, *([None] * (leaf.ndim - 1))))

    return jax.tree_util.tree_map(one, batch_like)


def cache_shardings(cfg: ModelConfig, mesh: Mesh, cache_like: Pytree,
                    batch: int) -> Pytree:
    """KV/SSM cache: batch over DP when it divides; otherwise (long-context,
    batch=1) shard the KV *sequence* dim over 'data' (flash-decoding style
    split-KV) and heads over 'model'."""
    dp = dp_axes(mesh)
    if cfg.sharding == "dp":
        full = dp + ("model",)
        total = 1
        for a in full:
            total *= _axis_size(mesh, a)
        if batch % max(total, 1) == 0:
            dp = full
    dp_total = 1
    for a in dp:
        dp_total *= _axis_size(mesh, a)
    batch_on_dp = batch % max(dp_total, 1) == 0 and dp_total > 1

    def one(path, leaf):
        names = _path_names(path)
        shape = tuple(leaf.shape)
        # period-stacked caches have a leading n_periods dim
        lead = ("period" in names)
        core = shape[1:] if lead else shape
        # KV cache leaves are the 'k'/'v' fields: [slots, B, S, K, dh].
        # Everything else (Mamba conv/ssm, LSTM c/n/m/h) is per-token-free
        # recurrent state — no sequence dim to split.
        is_kv = bool(names) and names[-1] in ("k", "v") and len(core) == 5

        def fits(dim_size, axis):
            sz = _axis_size(mesh, axis)
            return sz > 1 and dim_size % sz == 0

        spec: list = [None] * len(core)
        if len(core) >= 2 and batch_on_dp:
            spec[1] = dp
        elif is_kv and "data" in mesh.axis_names and fits(core[2], "data"):
            # long-context batch=1: split the KV sequence over 'data'
            # (flash-decoding style split-KV)
            spec[2] = "data"
        if "model" in mesh.axis_names:
            # shard the widest model-side dim that divides, scanning from
            # the heads dim outward (KV: [.., K, dh]; mLSTM: [.., H, dk, dv]);
            # for KV the sequence dim (2) is reserved for 'data' split-KV
            for d in range(3 if is_kv else 2, len(core)):
                if spec[d] is None and fits(core[d], "model"):
                    spec[d] = "model"
                    break
        p = P(*([None] + spec if lead else spec))
        return NamedSharding(mesh, p)

    return jax.tree_util.tree_map_with_path(one, cache_like)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the innermost active ``with mesh:`` context, or None.

    This is how mesh-aware library code (``solve(batching=Sharded(...))``,
    the activation :func:`hint`) discovers the production/host mesh without
    threading it through every call signature.
    """
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Leading-axis batch sharding for a fleet of ODE states: place the
    batch dim on ``axis``, replicate everything else (the device layout
    ``solve(batching=Sharded(axis))`` computes over — pre-placing inputs
    with this avoids a resharding transfer on entry)."""
    return NamedSharding(mesh, P(axis))


def model_axis_size() -> int:
    """Size of the ambient mesh's 'model' axis (1 when no mesh)."""
    import os
    if os.environ.get("REPRO_NO_HINTS"):
        return 1
    mesh = ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


def hint(x, *dims: Optional[str]):
    """Activation-sharding hint usable INSIDE model code.

    ``dims`` name the wanted axis per tensor dim: 'batch' (-> every dp axis),
    'model', or None. A no-op when no mesh context is active (unit tests /
    single-host examples) or when an axis doesn't divide. GSPMD propagates
    most shardings fine; the explicit hints pin the cases where propagation
    picks a catastrophic layout (measured: mamba's scan replicated the batch
    dim across 'data' — 16x redundant memory/compute; EXPERIMENTS.md §Perf
    jamba iteration 1).
    """
    import os
    if os.environ.get("REPRO_NO_HINTS"):
        return x
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.thread_resources.env.physical_mesh
    if mesh.empty or mesh.size == 1:
        return x
    if jax.sharding.get_abstract_mesh().manual_axes:
        return x   # inside a shard_map body (Sharded ODE): device-local
    spec = []
    for dim, want in zip(x.shape, dims):
        if want == "batch":
            axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
            total = 1
            for a in axes:
                total *= mesh.shape[a]
            spec.append(axes if axes and dim % max(total, 1) == 0 else None)
        elif want == "model" and "model" in mesh.axis_names:
            spec.append("model" if dim % mesh.shape["model"] == 0 else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def zero1_sharding(mesh: Mesh, leaf) -> NamedSharding:
    """ZeRO-1 spec for optimizer-state leaves of REPLICATED params: shard the
    largest divisible dim over ('data','model') (fallback 'data', then
    replicate). Params stay replicated; GSPMD turns the grad all-reduce into
    reduce-scatter + (post-update) all-gather."""
    shape = tuple(leaf.shape)
    size = 1
    for d in shape:
        size *= d
    if not shape or size < (1 << 16):
        return NamedSharding(mesh, P())
    for axes in ((("data", "model"),), (("data",),), (("model",),)):
        axes = axes[0]
        if not all(a in mesh.axis_names for a in axes):
            continue
        total = 1
        for a in axes:
            total *= _axis_size(mesh, a)
        # largest dim divisible by the axis product
        best = -1
        for i, d in enumerate(sorted(range(len(shape)),
                                     key=lambda i: -shape[i])):
            if shape[d] % total == 0:
                best = d
                break
        if best >= 0:
            spec = [None] * len(shape)
            spec[best] = axes
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def opt_state_shardings(cfg: ModelConfig, mesh: Mesh, p_sh: Pytree,
                        params_like: Pytree):
    """Optimizer-state shardings: inherit the param sharding where the param
    is itself sharded; apply ZeRO-1 to leaves whose param is replicated."""
    def one(sh, leaf):
        if any(ax is not None for ax in sh.spec):
            return sh
        return zero1_sharding(mesh, leaf)

    return jax.tree_util.tree_map(one, p_sh, params_like)
