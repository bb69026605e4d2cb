"""codeqwen1.5-7b [dense] — qwen1.5 arch (GQA, qkv-bias, SwiGLU).

32L d_model=4096 32H (GQA kv=4, 8 query heads per kv head) d_ff=13440
vocab=92416, untied head: 7.25 B parameters [hf:Qwen/CodeQwen1.5-7B; hf].
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    arch_id="codeqwen1.5-7b",
    family="dense-lm",
    num_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv=4,
    head_dim=128,
    d_ff=13440,
    vocab=92416,
    attention="gqa",
    qkv_bias=True,
    ffn="swiglu",
    norm="rms",
    rope_theta=1000000.0,
    dtype="bfloat16",
)
