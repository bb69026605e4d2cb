"""Pallas TPU fused RMSNorm.

Bandwidth-bound fusion: one HBM read of x, one write of y — versus the
unfused square/mean/rsqrt/mul chain that XLA may materialize in between.
Rows are tiled (block_rows, d); the weight block is broadcast to every row
block via a constant index_map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, plus_one: bool):
    x = x_ref[...].astype(jnp.float32)                 # (br, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    scale = (1.0 + w) if plus_one else w
    o_ref[...] = (y * scale[None, :]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("eps", "plus_one", "block_rows", "interpret"))
def rmsnorm_pallas(x, w, *, eps: float = 1e-6, plus_one: bool = False,
                   block_rows: int = 256, interpret: bool):
    """x: (..., d); w: (d,)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n = x2.shape[0] // br

    kernel = functools.partial(_rmsnorm_kernel, eps=eps, plus_one=plus_one)
    y = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(x2, w)
    if pad:
        y = y[:rows]
    return y.reshape(orig_shape)
