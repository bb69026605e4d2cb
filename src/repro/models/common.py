"""Param-spec trees, norms, position embeddings, shared model utilities.

Parameters are nested dicts of arrays.  Modules declare nested dicts of
``P`` specs (shape + *logical axes* + init); ``init_tree`` materializes them
and ``axes_tree`` mirrors the structure with logical-axis tuples, so the
sharding plan can map every leaf without drift.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class P:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed | small
    scale: float = 1.0
    dtype: Optional[str] = None   # None -> the tree-level dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


SpecTree = Dict[str, Any]   # nested dict of P


def _leaf_init(key, p: P, dtype) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    fan_in = p.shape[0] if p.shape else 1
    if p.init == "embed":
        std = 0.02
    elif p.init == "small":
        std = 0.02
    else:
        std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, p.shape, jnp.float32) * std * p.scale
            ).astype(dtype)


def init_tree(key: jax.Array, specs: SpecTree, dtype=jnp.float32) -> Dict:
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    keys = jax.random.split(key, len(leaves))
    vals = [_leaf_init(k, p, jnp.dtype(p.dtype) if p.dtype else dtype)
            for k, p in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def eval_shape_tree(specs: SpecTree, dtype=jnp.float32) -> Dict:
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(
            p.shape, jnp.dtype(p.dtype) if p.dtype else dtype),
        specs, is_leaf=lambda x: isinstance(x, P))


def axes_tree(specs: SpecTree) -> Dict:
    return jax.tree.map(lambda p: p.axes, specs,
                        is_leaf=lambda x: isinstance(x, P))


def stacked(specs: SpecTree, n: int) -> SpecTree:
    """Prefix every leaf with a scanned 'layer' dimension."""
    return jax.tree.map(
        lambda p: P((n,) + p.shape, ("layer",) + p.axes, p.init, p.scale,
                    p.dtype),
        specs, is_leaf=lambda x: isinstance(x, P))


def count_params(specs: SpecTree) -> int:
    tot = 0
    for p in jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        n = 1
        for s in p.shape:
            n *= s
        tot += n
    return tot


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-6, plus_one: bool = False):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w) if plus_one else w
    return (y * scale).astype(dt)


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w + b).astype(dt)


def norm_spec(cfg, d: Optional[int] = None) -> SpecTree:
    d = d or cfg.d_model
    if cfg.norm == "rms":
        return {"w": P((d,), ("embed",),
                       "zeros" if cfg.arch_id.startswith("gemma") else "ones")}
    return {"w": P((d,), ("embed",), "ones"),
            "b": P((d,), ("embed",), "zeros")}


def apply_norm(params, x, cfg, rms=rms_norm):
    """``rms`` is the selected RMSNorm kernel (``Variants.rms_norm``)."""
    if cfg.norm == "rms":
        return rms(x, params["w"], plus_one=cfg.arch_id.startswith("gemma"))
    return layer_norm(x, params["w"], params["b"])


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE / partial / M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))


def apply_rope(x, positions, theta: float = 10000.0,
               partial: float = 1.0,
               mrope_sections: Tuple[int, ...] = ()):
    """x: (..., seq, heads, head_dim); positions: (batch, seq) int or
    (3, batch, seq) for M-RoPE."""
    hd = x.shape[-1]
    rot = int(hd * partial)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = jnp.asarray(rope_freqs(rot, theta), jnp.float32)   # (rot/2,)

    if mrope_sections:
        # Qwen2-VL M-RoPE: frequency slots split across (t, h, w) sections.
        assert positions.ndim == 3, "M-RoPE needs (3, batch, seq) positions"
        secs = list(mrope_sections)
        assert sum(secs) == rot // 2, (secs, rot)
        pos_parts = []
        start = 0
        for i, s in enumerate(secs):
            pos_parts.append(
                positions[i][..., None].astype(jnp.float32) * freqs[start:start + s])
            start += s
        ang = jnp.concatenate(pos_parts, axis=-1)      # (b, s, rot/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs   # (b, s, rot/2)

    cos = jnp.cos(ang)[..., None, :]   # (b, s, 1, rot/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    y = jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
    return jnp.concatenate([y, x_pass], axis=-1) if x_pass.shape[-1] else y


def sinusoidal_pos(positions, dim: int) -> jax.Array:
    """MusicGen-style absolute sinusoidal embeddings; positions (b, s)."""
    half = dim // 2
    freqs = jnp.asarray(rope_freqs(2 * half, 10000.0), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embed_spec(cfg) -> SpecTree:
    sp: SpecTree = {"tok": P((cfg.vocab, cfg.d_model), ("vocab", "embed"), "embed")}
    return sp


def head_spec(cfg) -> SpecTree:
    if cfg.tie_embeddings:
        return {}
    return {"w": P((cfg.d_model, cfg.vocab), ("embed", "vocab"), "normal")}


def embed_tokens(params, tokens, cfg):
    e = params["tok"][tokens]          # (b, s, d)
    if cfg.act_dtype:
        e = e.astype(cfg.act_dtype)
    if cfg.arch_id.startswith("gemma"):
        e = e * jnp.asarray(math.sqrt(cfg.d_model), e.dtype)
    return e


def matmul_precision(cfg):
    """The matmul precision of ``cfg``'s activations: float32 ones at
    float32 (the TPU's default rounds each operand to bfloat16)."""
    if cfg.act_dtype == "float32":
        return jax.default_matmul_precision("float32")
    return contextlib.nullcontext()


def lm_logits(head_params, embed_params, x, cfg):
    if cfg.tie_embeddings:
        w = embed_params["tok"].T
    else:
        w = head_params["w"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


def cross_entropy(logits, labels, mask=None):
    """logits f32 (b, s, v); labels int (b, s)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)
