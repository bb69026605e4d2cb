"""ArchConfig: the 'application' a CIR packages.

One file per assigned architecture lives next to this module; each exports
``CONFIG`` built from the exact public numbers.  ``reduced()`` derives the
small same-family config used by CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class ArchConfig:
    arch_id: str
    family: str                     # dense-lm | moe-lm | ssm-lm | hybrid-lm | audio-lm | vlm-lm
    num_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 0               # 0 -> d_model // n_heads

    # --- attention flavour
    attention: str = "gqa"          # gqa | mla | none
    sliding_window: int = 0         # 0 = full; gemma2 local layers use 4096
    alt_local_global: bool = False  # gemma2: alternate local/global layers
    attn_softcap: float = 0.0       # gemma2 logit soft-capping
    final_softcap: float = 0.0
    qkv_bias: bool = False          # qwen-family
    post_norms: bool = False        # gemma2: post-attn/post-ffn norms
    use_rope: bool = True           # musicgen: sinusoidal absolute instead
    rope_theta: float = 10000.0
    partial_rotary: float = 1.0     # phi4-mini: 0.75
    mrope_sections: Tuple[int, ...] = ()  # qwen2-vl M-RoPE

    # --- MLA (deepseek)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # --- FFN flavour
    ffn: str = "swiglu"             # swiglu | geglu | gelu
    norm: str = "rms"               # rms | ln
    tie_embeddings: bool = False

    # --- MoE
    num_experts: int = 0
    top_k: int = 0
    shared_experts: int = 0
    moe_ff: int = 0                 # expert hidden dim (if != d_ff)
    first_dense_layers: int = 0     # deepseek: first k layers dense
    moe_every: int = 1              # jamba: MoE every other layer
    router_scale: bool = False      # deepseek sigmoid-routing w/ bias

    # --- SSM / RWKV
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    rwkv_head_size: int = 64
    attn_period: int = 0            # jamba: one attention layer per period
    attn_offset: int = 0

    # --- heads / extras
    mtp: bool = False               # deepseek multi-token prediction head
    frontend: str = ""              # "audio-frames" | "vision-patches" | ""
    codebooks: int = 0              # musicgen
    dtype: str = "bfloat16"
    # activations and the residual stream; "" keeps ``dtype``.  float32
    # activations multiply at float32 precision; the weights stay ``dtype``.
    act_dtype: str = ""
    max_seq: int = 8192

    # --- declared direct dependencies (pre-builder may extend/filter)
    extra_deps: Tuple[Tuple[str, str, str], ...] = ()
    notes: str = ""

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads:
            self.head_dim = self.d_model // self.n_heads
        if self.moe_ff == 0:
            self.moe_ff = self.d_ff

    # ------------------------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the long_500k shape."""
        return self.family in ("ssm-lm", "hybrid-lm") or (
            self.alt_local_global and self.sliding_window > 0)

    def param_count(self) -> int:
        """Analytic parameter count (used for image-size + MODEL_FLOPS)."""
        d, L, V = self.d_model, self.num_layers, self.vocab
        emb = V * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        n_attn_layers = L
        n_ssm_layers = 0
        if self.attn_period:
            n_attn_layers = L // self.attn_period
            n_ssm_layers = L - n_attn_layers
        if self.family == "ssm-lm":
            n_attn_layers, n_ssm_layers = 0, L

        if self.attention == "mla":
            q = d * self.q_lora_rank + self.q_lora_rank * self.n_heads * (
                self.qk_nope_dim + self.qk_rope_dim)
            kv = d * (self.kv_lora_rank + self.qk_rope_dim) + \
                self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
            o = self.n_heads * self.v_head_dim * d
            attn = q + kv + o
        elif self.attention == "gqa":
            attn = d * self.n_heads * self.head_dim \
                + 2 * d * self.n_kv * self.head_dim \
                + self.n_heads * self.head_dim * d
        else:
            attn = 0

        if self.family == "ssm-lm":        # rwkv6
            inner = d
            tm = 6 * d * 32 * 2 + d * inner * 4 + inner * d   # lora mixes + wkv proj
            cm = d * self.d_ff + self.d_ff * d
            per = tm + cm
            return emb + L * per

        gated = self.ffn in ("swiglu", "geglu")
        dense_ffn = d * self.d_ff * (3 if gated else 2)
        if self.is_moe:
            moe_ffn = self.num_experts * d * self.moe_ff * (3 if gated else 2)
            moe_ffn += self.shared_experts * d * self.moe_ff * (3 if gated else 2)
            moe_ffn += d * self.num_experts   # router
        else:
            moe_ffn = 0

        mamba = 0
        if n_ssm_layers:
            din = d * self.ssm_expand
            mamba = (d * din * 2            # in_proj (x, z)
                     + din * self.ssm_conv  # conv
                     + din * (self.ssm_state * 2 + 1)  # B,C,dt proj (x->)
                     + din                  # A? (din*state) actually
                     + din * self.ssm_state # A_log
                     + din * d)             # out_proj

        total = emb
        for i in range(L):
            is_attn = (self.attn_period == 0) or (i % self.attn_period == self.attn_offset)
            if self.family == "hybrid-lm":
                blk = attn if is_attn else mamba
            else:
                blk = attn
            if self.is_moe:
                use_moe = (i % self.moe_every == (self.moe_every - 1)) if self.moe_every > 1 \
                    else (i >= self.first_dense_layers)
                blk += moe_ffn if use_moe else dense_ffn
            else:
                blk += dense_ffn
            total += blk
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: routed top-k + shared only)."""
        if not self.is_moe:
            return self.param_count()
        full = dataclasses.replace(
            self, num_experts=self.top_k, shared_experts=self.shared_experts)
        return full.param_count()

    # ------------------------------------------------------------------
    def reduced(self) -> "ArchConfig":
        """Small same-family config for CPU smoke tests."""
        r = dataclasses.replace(
            self,
            num_layers=max(2, min(4, self.num_layers // 10 or 2)),
            d_model=128, n_heads=4, n_kv=min(self.n_kv, 2) or 2,
            head_dim=32, d_ff=256, vocab=512,
            q_lora_rank=32 if self.q_lora_rank else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_dim=16 if self.qk_nope_dim else 0,
            qk_rope_dim=16 if self.qk_rope_dim else 0,
            v_head_dim=32 if self.v_head_dim else 0,
            num_experts=min(self.num_experts, 4),
            top_k=min(self.top_k, 2),
            moe_ff=128 if self.is_moe else 0,
            first_dense_layers=min(self.first_dense_layers, 1),
            sliding_window=64 if self.sliding_window else 0,
            ssm_state=8, rwkv_head_size=32,
            attn_period=min(self.attn_period, 4) if self.attn_period else 0,
            mrope_sections=(4, 6, 6) if self.mrope_sections else (),
            max_seq=256,
            dtype="float32",
        )
        if self.attn_period:
            r = dataclasses.replace(r, num_layers=max(r.num_layers, r.attn_period))
        return r

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        return d

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "ArchConfig":
        d = dict(d)
        for k in ("mrope_sections",):
            if k in d:
                d[k] = tuple(d[k])
        if "extra_deps" in d:
            d["extra_deps"] = tuple(tuple(x) for x in d["extra_deps"])
        return ArchConfig(**d)


FAMILY_MODEL_COMPONENT = {
    "dense-lm": "decoder-dense",
    "moe-lm": "decoder-moe",
    "ssm-lm": "decoder-rwkv",
    "hybrid-lm": "decoder-hybrid",
    "audio-lm": "decoder-audio",
    "vlm-lm": "decoder-vlm",
}
