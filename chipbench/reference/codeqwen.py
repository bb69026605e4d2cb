"""Plain float32 reference of CodeQwen1.5-7B (Qwen1.5, ``Qwen2ForCausalLM``):
the dense GQA stack of ``dense_gqa`` with q, k and v biases.

The forward pass, the head and the FLOP counts are ``dense_gqa``'s: biases
add to q, k and v before the rotary embedding, 32 query heads read 4 kv
heads (query head i reads kv head i // 8), and the head is untied.  Only the
weights differ.  ``dense_gqa`` draws the biases as zeros, and with zero
biases a program that drops them, or adds a kv head's bias to the wrong
heads, serves the same tokens.  Here they are drawn with the "small" init,
N(0, 0.02^2) (``base._draw``; 0.02 is the published config's
``initializer_range``, the embeddings' scale).  Larger biases swamp the
token-dependent part of the residual stream: a bias is the same at every
position, and attention over a long prompt averages the values' own part
away.  With "bonus" (N(0, 0.25)) and prompts of 1024-2048 tokens the greedy
tokens became nearly input-independent, so the float8 control served the
reference's own tokens on one seed of three and no limit could fail it
(PERF.md, section 6).  At 0.02 a program that leaves the biases out still
fails the check: on the CPU at reduced widths it reads well above the
rehearsal's limit (``tests/test_codeqwen_reference.py``).
"""
from __future__ import annotations

from typing import Dict

from chipbench.reference import dense_gqa
from chipbench.reference.dense_gqa import (  # noqa: F401
    decode_flops, head, hidden, prefill_flops)


def param_tree(m: Dict) -> Dict:
    tree = dense_gqa.param_tree(m)
    attn = tree["blocks"]["attn"]
    for name in ("bq", "bk", "bv"):
        shape, _, lead = attn[name]
        attn[name] = (shape, "small", lead)
    return tree
