"""Pallas TPU kernels for the WKV6 recurrence (RWKV6 'Finch').

Two kernels, one ``name="wkv6"``; ``kernels.ops.pallas_wkv6`` picks by the
input's length:

  * one token (``wkv6_step_pallas``, the decode): the recurrence stepped
    once, on the VPU.  A grid step takes one batch row and all its heads,
    reads their state from HBM once and writes it once; the state query
    r·S is a multiply and a sublane sum, in float32.
  * two or more tokens (``wkv6_pallas``, the prefill): the chunked form
    below.

The chunked kernel is a TPU adaptation of the (GPU, element-parallel)
official kernel: instead of one thread per channel running the recurrence
serially, the sequence is split into chunks of L tokens.  Within a chunk the
work is (L, K)/(L, V) matmuls on the MXU; across chunks only the (K, V)
state is carried — it lives in VMEM scratch and persists over the
sequential chunk grid dimension.

    y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T         with 0 < w < 1

The decay comes in as lw = log w (≤ 0) in float32, as the model computes it
(lw = -exp(decay + dd)): the kernel works in log w throughout.

Every exponential computed here has exponent ≤ 0 (decays multiply), so the
chunked form is overflow-safe in f32 regardless of sequence length or decay
strength.

Everything inside the chunked kernel is a 2-D op the TPU compiler lowers:
  * the exclusive cumsum of the log-decays is a strictly-lower-triangular
    (L, L) matmul, the chunk's total decay a sublane ``sum``;
  * the intra-chunk decay exp(sw_t - sw_j - lw_j) depends on (t, j, channel),
    so it cannot be factored into an r side and a k side without exponents
    > 0.  It is accumulated one channel at a time as an (L, L) outer
    product: a ``fori_loop`` over K reads row c of the transposed k and
    inclusive-cumsum tiles (VMEM scratch, dynamic sublane index) and column c
    of r and sw (a one-hot lane reduction).

Chunked grid = (batch, heads, n_chunks); chunk dim is innermost/sequential.
Blocks: r/k/lw (1, 1, L, K), v (1, 1, L, V), u (1, 1, K) per head,
state (1, 1, K, V); scratch: state (K, V) f32 + two (K, L) f32 tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    """(M, K) @ (K, N) in f32."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _mm_nt(a, b):
    """(M, K) @ (N, K)^T in f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _mm_tn(a, b):
    """(L, M)^T @ (L, N) in f32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                 y_ref, sout_ref, S_scr, kT_scr, swlT_scr, *,
                 L: int, K: int, nchunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        S_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)           # (L, K)
    k = k_ref[0, 0].astype(jnp.float32)           # (L, K)
    v = v_ref[0, 0].astype(jnp.float32)           # (L, V)
    lw = lw_ref[0, 0].astype(jnp.float32)         # (L, K) log-decay (≤ 0)
    u = u_ref[0].astype(jnp.float32)              # (1, K)
    S = S_scr[...]                                 # (K, V)

    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = tj < ti                               # strictly causal (j < t)
    sw = _mm(causal.astype(jnp.float32), lw)       # exclusive cumsum (L, K)
    swl = sw + lw                                  # inclusive cumsum
    sw_end = jnp.sum(lw, axis=0, keepdims=True)    # total chunk decay (1, K)

    # transposed (K, L) tiles so channel c is a sublane row
    eye_k = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
             ).astype(jnp.float32)
    kT_scr[...] = _mm_nt(eye_k, k)
    swlT_scr[...] = _mm_nt(eye_k, swl)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    # intra-chunk: A[t, j] = Σ_c r[t,c] k[j,c] exp(sw[t,c] - swl[j,c]), j < t
    def channel(c, A):
        pick = lane == c
        r_c = jnp.sum(jnp.where(pick, r, 0.0), axis=1, keepdims=True)
        sw_c = jnp.sum(jnp.where(pick, sw, 0.0), axis=1, keepdims=True)
        k_c = kT_scr[pl.ds(c, 1), :]               # (1, L)
        swl_c = swlT_scr[pl.ds(c, 1), :]           # (1, L)
        decay = jnp.exp(jnp.minimum(sw_c - swl_c, 0.0))
        return A + jnp.where(causal, r_c * k_c * decay, 0.0)

    A = jax.lax.fori_loop(0, K, channel, jnp.zeros((L, L), jnp.float32))
    y = _mm(A, v)
    # current-token bonus: diag(u)
    y += jnp.sum(r * u * k, axis=1, keepdims=True) * v
    # inter-chunk: query the carried state
    y += _mm(r * jnp.exp(sw), S)
    # state update: S' = diag(e^{sw_end}) S + Σ_j (k_j e^{sw_end-swl_j}) v_j^T
    k2 = k * jnp.exp(sw_end - swl)
    decay_col = jnp.sum(eye_k * jnp.exp(sw_end), axis=1, keepdims=True)
    S_new = decay_col * S + _mm_tn(k2, v)

    S_scr[...] = S_new
    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)

    @pl.when(ic == nchunks - 1)
    def _final():
        sout_ref[0, 0, :, :] = S_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, lw, u, state=None, *, chunk: int = 64,
                interpret: bool):
    """r,k: (b, h, s, K); lw: (b, h, s, K) log-decay in float32; v: (b, h,
    s, V); u: (h, K); ``s`` a multiple of ``chunk``
    (``kernels.ops.pallas_wkv6`` pads).
    Returns (y (b, h, s, V), final_state (b, h, K, V) f32)."""
    b, h, s, K = r.shape
    V = v.shape[-1]
    L = chunk
    if s % L:
        raise ValueError(f"sequence length {s} is not a multiple of the "
                         f"chunk {L}")
    n = s // L
    if state is None:
        state = jnp.zeros((b, h, K, V), jnp.float32)
    lw = lw.astype(jnp.float32)
    u3 = u.reshape(h, 1, K)

    kernel = functools.partial(_wkv6_kernel, L=L, K=K, nchunks=n)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(b, h, n),
        in_specs=[
            pl.BlockSpec((1, 1, L, K), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, L, K), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, L, V), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, L, K), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, K), lambda ib, ih, ic: (ih, 0, 0)),
            pl.BlockSpec((1, 1, K, V), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, V), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, K, V), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, V), v.dtype),
            jax.ShapeDtypeStruct((b, h, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32),
                        pltpu.VMEM((K, L), jnp.float32),
                        pltpu.VMEM((K, L), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="wkv6",
        interpret=interpret,
    )(r, k, v, lw, u3, state)
    return y, s_out


def _wkv6_step_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                      y_ref, sout_ref, *, K: int):
    r = r_ref[0].astype(jnp.float32)               # (H, 1, K)
    k = k_ref[0].astype(jnp.float32)               # (H, 1, K)
    v = v_ref[0].astype(jnp.float32)               # (H, 1, V)
    lw = lw_ref[0]                                 # (H, 1, K) log-decay
    u = u_ref[...].astype(jnp.float32)             # (H, 1, K)
    S = s0_ref[0]                                  # (H, K, V) f32

    # a channel row (H, 1, K) as a sublane column (H, K, 1): one-hot lane sum
    eye = (jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
           ).astype(jnp.float32)

    def column(x):
        return jnp.sum(eye * x, axis=2, keepdims=True)

    r_col, k_col = column(r), column(k)
    # y = r·S + (Σ_c r_c u_c k_c) v, the state query a sublane sum
    y = jnp.sum(r_col * S, axis=1, keepdims=True)
    y += jnp.sum(r * u * k, axis=2, keepdims=True) * v
    # S' = diag(w) S + kᵀ v
    sout_ref[0] = jnp.exp(column(lw)) * S + k_col * v
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6_step_pallas(r, k, v, lw, u, state, *, interpret: bool):
    """One token of the recurrence: r, k, lw (b, h, 1, K) (lw the log-decay
    in float32), v (b, h, 1, V), u (h, K), state (b, h, K, V) f32.  Each
    grid step takes one batch row and all its heads, reading the state
    once and writing it once.
    Returns (y (b, h, 1, V), new state (b, h, K, V) f32)."""
    b, h, s, K = r.shape
    V = v.shape[-1]
    if s != 1:
        raise ValueError(f"the step kernel takes one token, not {s}")
    u3 = u.reshape(h, 1, K)
    row = lambda ib: (ib, 0, 0, 0)
    y, s_out = pl.pallas_call(
        functools.partial(_wkv6_step_kernel, K=K),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, 1, K), row),
            pl.BlockSpec((1, h, 1, K), row),
            pl.BlockSpec((1, h, 1, V), row),
            pl.BlockSpec((1, h, 1, K), row),
            pl.BlockSpec((h, 1, K), lambda ib: (0, 0, 0)),
            pl.BlockSpec((1, h, K, V), row),
        ],
        out_specs=[
            pl.BlockSpec((1, h, 1, V), row),
            pl.BlockSpec((1, h, K, V), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, 1, V), v.dtype),
            jax.ShapeDtypeStruct((b, h, K, V), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="wkv6",
        interpret=interpret,
    )(r, k, v, lw.astype(jnp.float32), u3, state)
    return y, s_out
