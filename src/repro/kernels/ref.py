"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *correctness* definitions: small, obvious, unblocked.  The
kernel tests sweep shapes/dtypes and assert_allclose kernel-vs-ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# Attention (GQA, causal, optional sliding window + logit softcap)
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, *, scale: float, causal: bool = True,
                  window: int = 0, softcap: float = 0.0):
    """q: (b, hq, sq, d); k/v: (b, hkv, skv, d) with hq % hkv == 0."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]            # MLA: v head dim may differ from qk head dim
    g = hq // hkv
    qf = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, dv).astype(v.dtype)


# ---------------------------------------------------------------------------
# WKV6 (RWKV 'Finch' recurrence with data-dependent decay)
# ---------------------------------------------------------------------------

def wkv6_ref(r, k, v, lw, u, state=None):
    """Exact sequential recurrence.

    r,k: (b, h, s, K); lw: (b, h, s, K) log-decay, w = exp(lw); v: (b, h,
    s, V); u: (h, K); state: (b, h, K, V).
    y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    """
    b, h, s, K = r.shape
    V = v.shape[-1]
    S = (jnp.zeros((b, h, K, V), jnp.float32) if state is None
         else state.astype(jnp.float32))
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    wf = jnp.exp(lw.astype(jnp.float32))
    uf = u.astype(jnp.float32)[None]
    ys = []
    for t in range(s):
        kv = kf[:, :, t, :, None] * vf[:, :, t, None, :]
        y = jnp.einsum("bhk,bhkv->bhv", rf[:, :, t], S + uf[..., None] * kv)
        ys.append(y)
        S = wf[:, :, t, :, None] * S + kv
    return jnp.stack(ys, axis=2).astype(v.dtype), S


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_ref(x, w, *, eps: float = 1e-6, plus_one: bool = False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if plus_one else w.astype(jnp.float32)
    return (y * scale).astype(x.dtype)
