"""rwkv6-1.6b 'Finch' [ssm] — attention-free, data-dependent decay.

24L d_model=2048 d_ff=7168 vocab=65536, head_size 64 → 32 heads
[arXiv:2404.05892; unverified].
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    arch_id="rwkv6-1.6b",
    family="ssm-lm",
    num_layers=24,
    d_model=2048,
    n_heads=32,
    n_kv=32,
    head_dim=64,
    d_ff=7168,
    vocab=65536,
    attention="none",
    ffn="relu2",
    norm="ln",
    rwkv_head_size=64,
    dtype="bfloat16",
    # bfloat16 activations put the served logits about one standard
    # deviation from a float32 forward pass of the same bfloat16 weights:
    # every rounding in the 24 layers grows through the time mix's
    # products (docs/architecture.md, "RWKV-6 precision")
    act_dtype="float32",
    notes="WKV6 chunked scan; O(1) decode state (no KV cache).",
)
