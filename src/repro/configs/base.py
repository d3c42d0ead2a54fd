"""Config system: architecture, layer-pattern, shape-cell and run configs.

Layer patterns are expressed as (prelude, period, n_periods): the prelude
layers are unrolled, the period is repeated ``n_periods`` times under a
single ``lax.scan`` with stacked parameters — HLO size stays O(period)
regardless of depth, which both matches production practice and keeps the
512-fake-device AOT compiles tractable. All layers inside one period may be
heterogeneous (Jamba's mamba/attn interleave, Gemma-2's local/global
alternation); layers across periods must repeat exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

from repro.core.ode_block import OdeSettings


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One sub-layer of a period."""
    mixer: str = "attn"           # 'attn' | 'mla' | 'mamba' | 'mlstm' | 'slstm'
    mlp: str = "dense"            # 'dense' | 'moe' | 'none'
    attn_kind: str = "global"     # 'global' | 'local'  (gemma2 alternation)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # depth pattern
    prelude: Tuple[LayerSpec, ...]
    period: Tuple[LayerSpec, ...]
    n_periods: int
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_shared_experts: int = 0
    moe_d_ff: int = 0              # per-(routed)-expert hidden dim
    # device budget: rows of held-expert assignments a MoE layer computes,
    # as a multiple of its share N * top_k * held / experts (training, and
    # serving with eval); over it the lowest-affinity assignments drop
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: float = 2.0
    moe_norm_topk: bool = True     # renormalise the top-k gate values
    # the expert share of one chip of an expert-parallel group: experts
    # [moe_first_expert, moe_first_expert + moe_experts_held) of the
    # router's moe_experts; 0 holds all of them
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    # dense-FFN override for prelude layers (DeepSeek layer-0 dense)
    prelude_d_ff: int = 0
    # attention details
    qk_norm: bool = False
    attn_softcap: float = 0.0      # gemma2: 50.0
    final_softcap: float = 0.0     # gemma2: 30.0
    sliding_window: int = 0        # local-attn window (gemma2: 4096)
    rope_theta: float = 10000.0
    # YaRN rotary (factor 0: plain RoPE): frequencies blended between
    # interpolated and extrapolated over the correction range that
    # beta_fast/beta_slow give at the original length
    rope_yarn_factor: float = 0.0
    rope_yarn_original_len: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    # multi-head latent attention (mixer 'mla'; no query latent): K/V from
    # a kv_rank-wide latent, q/k heads nope_dim + rope_dim wide (the rotary
    # key shared by all heads), value heads v_dim wide
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # ssm (mamba) details
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    # xlstm details
    lstm_proj_factor: float = 2.0
    # embedding / head
    tie_embeddings: bool = False
    input_mode: str = "tokens"     # 'tokens' | 'embeds' (vlm stub frontend)
    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # long-seq attention backward: 'flash' (FA2-style custom_vjp, O(S*d)
    # residuals) or 'autodiff' (AD through the scan; stacks O(S^2) tiles —
    # kept as the reference/baseline path; EXPERIMENTS.md §Perf)
    attn_bwd: str = "flash"
    # the paper's technique
    ode: OdeSettings = dataclasses.field(default_factory=OdeSettings)
    # sharding strategy: 'tp' (model-axis only) or 'fsdp_tp' (2D over
    # (data, model) — required for the >8B archs on a 16x16 pod)
    sharding: str = "tp"
    # sub-quadratic? (controls long_500k eligibility)
    subquadratic: bool = False

    @property
    def n_experts_held(self) -> int:
        return self.moe_experts_held or self.moe_experts

    @property
    def n_layers(self) -> int:
        return len(self.prelude) + len(self.period) * self.n_periods

    def layers(self) -> Tuple[LayerSpec, ...]:
        return self.prelude + self.period * self.n_periods

    def with_ode(self, ode: OdeSettings) -> "ModelConfig":
        return dataclasses.replace(self, ode=ode)

    def validate(self) -> "ModelConfig":
        if self.period and self.n_periods <= 0:
            raise ValueError("n_periods must be positive when period non-empty")
        specs = self.prelude + self.period
        has_moe = any(l.mlp == "moe" for l in specs)
        if has_moe and (self.moe_experts <= 0 or self.moe_top_k <= 0):
            raise ValueError(f"{self.name}: moe layers need moe_experts/top_k")
        if has_moe and not (0 <= self.moe_first_expert and self.moe_first_expert
                            + self.n_experts_held <= self.moe_experts):
            raise ValueError(f"{self.name}: held experts outside the router's")
        if any(l.mixer == "mla" for l in specs) and min(
                self.mla_kv_rank, self.mla_nope_dim, self.mla_rope_dim,
                self.mla_v_dim) <= 0:
            raise ValueError(f"{self.name}: mla layers need mla_* sizes")
        self.ode.validate()
        return self


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    """One assigned (input-shape) cell."""
    name: str                      # train_4k / prefill_32k / decode_32k / long_500k
    seq_len: int
    global_batch: int
    kind: str                      # 'train' | 'prefill' | 'decode'


SHAPE_CELLS = (
    ShapeCell("train_4k", 4096, 256, "train"),
    ShapeCell("prefill_32k", 32768, 32, "prefill"),
    ShapeCell("decode_32k", 32768, 128, "decode"),
    ShapeCell("long_500k", 524288, 1, "decode"),
)


def get_shape_cell(name: str) -> ShapeCell:
    for c in SHAPE_CELLS:
        if c.name == name:
            return c
    raise ValueError(f"unknown shape cell {name!r}")


def cell_applicable(cfg: ModelConfig, cell: ShapeCell) -> Tuple[bool, str]:
    """Assignment rule: long_500k only for sub-quadratic archs."""
    if cell.name == "long_500k" and not cfg.subquadratic:
        return False, ("skip: long_500k requires sub-quadratic attention; "
                       f"{cfg.name} has full/global attention layers")
    return True, ""


def uniform_pattern(n_layers: int, spec: LayerSpec) -> dict:
    """Homogeneous depth: scan all layers as 1-layer periods."""
    return dict(prelude=(), period=(spec,), n_periods=n_layers)
