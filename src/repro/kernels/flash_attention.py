"""Pallas TPU flash attention (GQA, causal, sliding-window, logit softcap).

Design (TPU-native, not a CUDA port):
  * grid = (batch, q_heads, nq, nk) — the kv dimension is innermost, so the
    online-softmax state (m, l, acc) lives in VMEM scratch and persists
    across the kv loop; the output block is written once, on the last kv
    step (the canonical TPU flash pattern).
  * BlockSpec tiles: q/out (1, 1, block_q, d), k/v (1, 1, block_k, d) — the
    working set is 2·bq·d + 2·bk·d + bq·bk floats, sized to fit VMEM with
    MXU-aligned (multiples of 128) matmul dims.
  * GQA is handled by the k/v index_map (query head → kv head, ih // g):
    no K/V replication in HBM, the MXU sees one query head per step.
  * Causal + sliding-window blocks that are fully masked are *skipped*
    (pl.when), so the kernel does ~half the matmuls of the dense version
    and a window kernel touches only O(window/block_k) kv blocks per row.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  block_q: int, block_k: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level mask culling: skip kv blocks that cannot contribute
    q_lo = iq * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    live = jnp.bool_(True)
    if causal:
        live &= q_hi >= k_lo                 # some query sees this kv block
    if window:
        live &= q_lo - k_hi < window         # block not entirely out-of-window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0, :, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap",
                     "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool):
    """q: (b, hq, sq, d); k/v: (b, hkv, skv, d).  Returns (b, hq, sq, d)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]            # MLA: v head dim may differ from qk head dim
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    nq, nk = sq // bq, skv // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max m
            pltpu.VMEM((bq,), jnp.float32),       # running denom l
            pltpu.VMEM((bq, dv), jnp.float32),    # output accumulator
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
