"""Architecture / shape configuration dataclasses.

Every assigned architecture gets one module in this package holding an
``ArchConfig`` with the exact published numbers (source cited in the
module docstring). ``smoke_config`` derives a reduced same-family config
for CPU smoke tests; the full configs are only ever lowered via the
dry-run (ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden size
    every: int = 1               # layer i hosts MoE iff (i % every) == moe_offset
    moe_offset: int = 0
    dense_residual: bool = False  # arctic: dense FFN running in parallel with MoE
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # the chip's share of an expert-parallel deployment: the router
    # keeps all n_experts outputs, and this layer holds and computes
    # experts held_offset .. held_offset + held - 1 (n_held 0 = all)
    n_held: int = 0
    held_offset: int = 0
    shared_d_ff: int = 0         # granite: one shared SwiGLU expert

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk: int = 256
    conv_dim: int = 4            # depthwise causal conv width

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    # --- attention details ---
    qkv_bias: bool = False
    use_rope: bool = True        # jamba: no positional encoding (mamba provides order)
    rope_theta: float = 10_000.0
    attn_softcap: float = 0.0    # gemma2 attention-logit softcap
    final_softcap: float = 0.0   # gemma2 final-logit softcap
    sliding_window: int = 0      # window for 'local' layers; 0 = full attention
    layer_pattern: Tuple[str, ...] = ()   # e.g. ('local','global'); () = all 'global'
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    post_norms: bool = False     # gemma2 post-attention/post-ffn extra norms
    act: str = "silu"            # 'silu' (SwiGLU) | 'gelu' (GeGLU)
    # granite scalar multipliers: x = embed * embedding_multiplier; each
    # residual branch is scaled by residual_multiplier; logits are
    # divided by logits_scaling; attention scores are scaled by
    # attention_multiplier (0 = the usual 1/sqrt(head_dim))
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float = 0.0
    # --- moe ---
    moe: Optional[MoEConfig] = None
    # --- ssm / hybrid ---
    ssm: Optional[SSMConfig] = None
    attn_every: int = 0          # hybrid: one attention layer per period of this many
    attn_offset: int = 0         # index of the attention layer within the period
    # --- encoder-decoder ---
    n_enc_layers: int = 0
    # --- modality frontend stub ---
    frontend: str = ""           # '' | 'audio' | 'vision'
    prefix_len: int = 0          # frames/patches prepended by the stub
    source: str = ""             # citation string

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.n_heads, 1))
        if self.layer_pattern:
            assert self.n_layers % len(self.layer_pattern) == 0, self.name
        if self.attn_every:
            assert self.n_layers % self.attn_every == 0, self.name

    # --- structural helpers -------------------------------------------
    @property
    def period(self) -> int:
        """Length of the repeating layer super-block (for lax.scan)."""
        p = 1
        if self.layer_pattern:
            p = math.lcm(p, len(self.layer_pattern))
        if self.attn_every:
            p = math.lcm(p, self.attn_every)
        if self.moe is not None and self.moe.every > 1:
            p = math.lcm(p, self.moe.every)
        return p

    def layer_kind(self, i: int) -> str:
        """'attn' | 'mamba' for mixer at layer i."""
        if self.family == "ssm":
            return "mamba"
        if self.attn_every:
            return "attn" if (i % self.attn_every) == self.attn_offset else "mamba"
        return "attn"

    def attn_kind(self, i: int) -> str:
        """'global' | 'local' attention flavour at layer i."""
        if self.layer_pattern:
            return self.layer_pattern[i % len(self.layer_pattern)]
        return "global"

    def is_moe_layer(self, i: int) -> bool:
        m = self.moe
        return m is not None and (i % m.every) == m.moe_offset

    @property
    def n_attn_layers(self) -> int:
        return sum(1 for i in range(self.n_layers) if self.layer_kind(i) == "attn")

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch can run 500k-token contexts (SSM/hybrid)."""
        return self.family in ("ssm", "hybrid")

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs decode (seamless is enc-dec)

    # --- parameter counting (for 6ND roofline terms) -------------------
    def _mixer_params(self, kind: str) -> int:
        d = self.d_model
        if kind == "mamba":
            s = self.ssm
            di = s.d_inner(d)
            nh = s.n_heads(d)
            # in_proj: d -> 2*di + 2*n_groups*d_state + nh  (x, z, B, C, dt)
            in_p = d * (2 * di + 2 * s.d_state + nh)
            conv = (di + 2 * s.d_state) * s.conv_dim
            out_p = di * d
            extra = nh * 2 + di  # A_log, dt_bias, norm
            return in_p + conv + out_p + extra
        # attention
        q = d * self.n_heads * self.head_dim
        kv = 2 * d * self.n_kv_heads * self.head_dim
        o = self.n_heads * self.head_dim * d
        b = (self.n_heads + 2 * self.n_kv_heads) * self.head_dim if self.qkv_bias else 0
        return q + kv + o + b

    def _ffn_params(self, i: int) -> Tuple[int, int]:
        """(total, active) FFN params at layer i."""
        d = self.d_model
        dense = 3 * d * self.d_ff if self.d_ff else 0
        if self.is_moe_layer(i):
            m = self.moe
            expert = 3 * d * m.d_ff
            total = m.n_experts * expert + d * m.n_experts  # + router
            active = m.top_k * expert + d * m.n_experts
            if m.dense_residual:
                total += dense
                active += dense
            return total, active
        return dense, dense

    def count_params(self) -> Tuple[int, int]:
        """(total, active) parameter counts, embeddings included once."""
        d = self.d_model
        total = active = self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d      # lm head
            active += self.vocab_size * d
        n_dec = self.n_layers
        for i in range(n_dec):
            mix = self._mixer_params(self.layer_kind(i))
            ff_t, ff_a = self._ffn_params(i)
            norms = 2 * d * (2 if self.post_norms else 1)
            total += mix + ff_t + norms
            active += mix + ff_a + norms
        for _ in range(self.n_enc_layers):   # encoder: full attn + dense ffn
            mix = self._mixer_params("attn")
            total += mix + 3 * d * self.d_ff + 2 * d
            active += mix + 3 * d * self.d_ff + 2 * d
        if self.n_enc_layers:                # decoder cross-attention
            for _ in range(n_dec):
                mix = self._mixer_params("attn")
                total += mix + d
                active += mix + d
        return total, active


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str            # 'train' | 'prefill' | 'decode'
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


def shape_applicable(arch: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch, shape) is a valid dry-run cell, with reason if not."""
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return False, "full-attention arch: 500k context is quadratic-infeasible (DESIGN.md §5)"
    return True, ""


# ----------------------------------------------------------------------
def smoke_config(cfg: ArchConfig) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests (one super-block period,
    tiny widths, few experts) — preserves every structural feature."""
    period = cfg.period
    n_layers = period * (2 if period <= 4 else 1)
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=min(cfg.moe.top_k, 2), d_ff=64)
    ssm = None
    if cfg.ssm is not None:
        ssm = dataclasses.replace(cfg.ssm, d_state=16, head_dim=16, chunk=32)
    n_heads = 4
    n_kv = min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else n_heads
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=64,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=512,
        sliding_window=min(cfg.sliding_window, 16) if cfg.sliding_window else 0,
        moe=moe,
        ssm=ssm,
        n_enc_layers=2 if cfg.n_enc_layers else 0,
        prefix_len=8 if cfg.prefix_len else 0,
    )
