"""State-space blocks: Mamba (Jamba's SSM layer) and RWKV6 'Finch' time/channel
mix with data-dependent decay.

Training uses a chunked WKV6 formulation (intra-chunk matmuls + inter-chunk
state carry — exponents are ≤0 by construction so it is overflow-safe);
decode carries O(1) recurrent state.  The sequential recurrence doubles as
the oracle for the chunked/Pallas variants.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import P, SpecTree, rms_norm
from .sharding import shard

# ---------------------------------------------------------------------------
# WKV6 core: r,k,w: (b, h, s, K); v: (b, h, s, V); u: (h, K)
# recurrence: y_t = r_t·(S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T
# ---------------------------------------------------------------------------

def wkv6_sequential(r, k, v, w, u, state=None):
    b, h, s, K = r.shape
    V = v.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, K, V), jnp.float32)
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    wf = w.astype(jnp.float32)
    uf = u.astype(jnp.float32)[None, :, :]          # (1, h, K)

    def step(S, t):
        rt, kt, vt, wt = t                          # (b,h,K)/(b,h,V)
        kv = kt[..., :, None] * vt[..., None, :]    # (b,h,K,V)
        y = jnp.einsum("bhk,bhkv->bhv", rt, S + uf[..., None] * kv)
        S_new = wt[..., None] * S + kv
        return S_new, y

    xs = (jnp.moveaxis(rf, 2, 0), jnp.moveaxis(kf, 2, 0),
          jnp.moveaxis(vf, 2, 0), jnp.moveaxis(wf, 2, 0))
    S, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 2).astype(v.dtype), S


def wkv6_chunked(r, k, v, w, u, state=None, chunk: int = 32):
    """Chunked parallel WKV6.  All exponentials have exponent ≤ 0."""
    b, h, s, K = r.shape
    V = v.shape[-1]
    if s % chunk or s <= chunk:
        return wkv6_sequential(r, k, v, w, u, state)
    if state is None:
        state = jnp.zeros((b, h, K, V), jnp.float32)
    n = s // chunk
    L = chunk
    rf = r.astype(jnp.float32).reshape(b, h, n, L, K)
    kf = k.astype(jnp.float32).reshape(b, h, n, L, K)
    vf = v.astype(jnp.float32).reshape(b, h, n, L, V)
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38)
                 ).reshape(b, h, n, L, K)
    uf = u.astype(jnp.float32)[None, :, None, :]     # (1, h, 1, K)

    sw = jnp.cumsum(lw, axis=3) - lw                 # exclusive cumsum
    sw_end = sw[..., -1, :] + lw[..., -1, :]         # total chunk decay

    tri = jnp.tril(jnp.ones((L, L), bool), k=-1)     # j < t

    def chunk_step(S, xs):
        rc, kc, vc, lwc, swc, swe = xs
        # intra-chunk: exponent(t,j,k) = sw_t - sw_j - lw_j  (≤ 0 for j < t)
        expo = swc[..., :, None, :] - swc[..., None, :, :] - lwc[..., None, :, :]
        expo = jnp.where(tri[None, None, :, :, None], expo, -jnp.inf)
        A = jnp.einsum("bhtk,bhjk,bhtjk->bhtj", rc, kc, jnp.exp(expo))
        y = jnp.einsum("bhtj,bhjv->bhtv", A, vc)
        # current-step bonus
        a = jnp.sum(rc * uf * kc, axis=-1)           # (b,h,L)
        y += a[..., None] * vc
        # inter-chunk: query the carried state
        q = rc * jnp.exp(swc)
        y += jnp.einsum("bhtk,bhkv->bhtv", q, S)
        # state update
        kk2 = kc * jnp.exp(swe[..., None, :] - swc - lwc)   # exponent ≤ 0
        S_new = jnp.exp(swe)[..., None] * S + jnp.einsum(
            "bhjk,bhjv->bhkv", kk2, vc)
        return S_new, y

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (rf, kf, vf, lw, sw))
    xs = xs + (jnp.moveaxis(sw_end, 2, 0),)
    S, ys = jax.lax.scan(chunk_step, state, xs)
    y = jnp.moveaxis(ys, 0, 2).reshape(b, h, s, V)
    return y.astype(v.dtype), S


# ---------------------------------------------------------------------------
# RWKV6 block (time-mix + channel-mix)
# ---------------------------------------------------------------------------

_TM_LORA = 32
_TD_LORA = 64


def rwkv6_spec(cfg) -> SpecTree:
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    h = d // hs
    return {
        "tm": {
            "maa_x": P((d,), ("embed",), "zeros"),
            "maa": P((5, d), (None, "embed"), "zeros"),       # w,k,v,r,g
            "maa_w1": P((d, 5 * _TM_LORA), ("embed", None), "small"),
            "maa_w2": P((5, _TM_LORA, d), (None, None, "embed"), "small"),
            "decay": P((d,), ("embed",), "zeros"),
            "decay_w1": P((d, _TD_LORA), ("embed", None), "small"),
            "decay_w2": P((_TD_LORA, d), (None, "embed"), "small"),
            "faaaa": P((h, hs), ("heads", None), "zeros"),
            "wr": P((d, d), ("embed", "heads")),
            "wk": P((d, d), ("embed", "heads")),
            "wv": P((d, d), ("embed", "heads")),
            "wg": P((d, d), ("embed", "heads")),
            "wo": P((d, d), ("heads", "embed")),
            "ln_w": P((d,), ("embed",), "ones"),
            "ln_b": P((d,), ("embed",), "zeros"),
        },
        "cm": {
            "maa_k": P((d,), ("embed",), "zeros"),
            "maa_r": P((d,), ("embed",), "zeros"),
            "wk": P((d, cfg.d_ff), ("embed", "mlp")),
            "wv": P((cfg.d_ff, d), ("mlp", "embed")),
            "wr": P((d, d), ("embed", "embed2")),
        },
    }


def _token_shift(x, prev):
    """shift right by one; position 0 sees ``prev`` (zeros at seq start)."""
    shifted = jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)
    return shifted


def rwkv6_time_mix(p, x, cfg, state=None, wkv_impl=wkv6_chunked):
    """x: (b, s, d).  state: None (train, zero init) or dict with
    'shift' (b, d) and 'wkv' (b, h, K, V).  ``wkv_impl`` is the selected
    WKV6 callable."""
    b, s, d = x.shape
    hs = cfg.rwkv_head_size
    h = d // hs
    prev = state["shift"] if state is not None else jnp.zeros((b, d), x.dtype)
    xx = _token_shift(x, prev)
    sx = xx - x

    xxx = x + sx * p["maa_x"].astype(x.dtype)
    mixed = jnp.tanh(jnp.einsum("bsd,dr->bsr", xxx, p["maa_w1"].astype(x.dtype)))
    mixed = mixed.reshape(b, s, 5, _TM_LORA)
    offs = jnp.einsum("bsfr,frd->fbsd", mixed, p["maa_w2"].astype(x.dtype))
    maa = p["maa"].astype(x.dtype)
    xw = x + sx * (maa[0] + offs[0])
    xk = x + sx * (maa[1] + offs[1])
    xv = x + sx * (maa[2] + offs[2])
    xr = x + sx * (maa[3] + offs[3])
    xg = x + sx * (maa[4] + offs[4])

    r = jnp.einsum("bsd,dk->bsk", xr, p["wr"].astype(x.dtype))
    k = jnp.einsum("bsd,dk->bsk", xk, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dk->bsk", xv, p["wv"].astype(x.dtype))
    g = jax.nn.silu(jnp.einsum("bsd,dk->bsk", xg, p["wg"].astype(x.dtype)))

    dd = jnp.tanh(jnp.einsum("bsd,dr->bsr", xw, p["decay_w1"].astype(x.dtype)))
    dd = jnp.einsum("bsr,rd->bsd", dd, p["decay_w2"].astype(x.dtype))
    w = jnp.exp(-jnp.exp((p["decay"].astype(jnp.float32)
                          + dd.astype(jnp.float32))))        # (b,s,d) in (0,1)

    def heads(t):
        return jnp.swapaxes(t.reshape(b, s, h, hs), 1, 2)
    rh, kh, vh, wh = heads(r), heads(k), heads(v), heads(w.astype(x.dtype))
    rh = shard(rh, "act_batch", "act_heads", "act_seq", None)

    wkv_state = state["wkv"] if state is not None else None
    y, S = wkv_impl(rh, kh, vh, wh, p["faaaa"], wkv_state)
    y = jnp.swapaxes(y, 1, 2).reshape(b, s, d)

    # per-head group norm
    yg = y.reshape(b, s, h, hs).astype(jnp.float32)
    mu = jnp.mean(yg, -1, keepdims=True)
    var = jnp.var(yg, -1, keepdims=True)
    yg = (yg - mu) * jax.lax.rsqrt(var + 64e-5)
    y = (yg.reshape(b, s, d) * p["ln_w"] + p["ln_b"]).astype(x.dtype)

    out = jnp.einsum("bsk,kd->bsd", y * g, p["wo"].astype(x.dtype))
    new_state = {"shift": x[:, -1, :], "wkv": S}
    return shard(out, "act_batch", "act_seq", "act_embed"), new_state


def rwkv6_channel_mix(p, x, cfg, state=None):
    b, s, d = x.shape
    prev = state if state is not None else jnp.zeros((b, d), x.dtype)
    xx = _token_shift(x, prev)
    sx = xx - x
    xk = x + sx * p["maa_k"].astype(x.dtype)
    xr = x + sx * p["maa_r"].astype(x.dtype)
    k = jnp.einsum("bsd,df->bsf", xk, p["wk"].astype(x.dtype))
    k = jnp.square(jax.nn.relu(k))
    k = shard(k, "act_batch", "act_seq", "act_mlp")
    kv = jnp.einsum("bsf,fd->bsd", k, p["wv"].astype(x.dtype))
    rgate = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr,
                                      p["wr"].astype(x.dtype)))
    return rgate * kv, x[:, -1, :]


def rwkv6_state_spec(cfg, batch: int) -> SpecTree:
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    h = d // hs
    return {
        "tm_shift": P((batch, d), ("cache_batch", None), "zeros"),
        "wkv": P((batch, h, hs, hs),
                 ("cache_batch", "cache_heads", None, None), "zeros",
                 dtype="float32"),
        "cm_shift": P((batch, d), ("cache_batch", None), "zeros"),
    }


# ---------------------------------------------------------------------------
# Mamba block (Jamba SSM layer)
# ---------------------------------------------------------------------------

def mamba_spec(cfg) -> SpecTree:
    d = cfg.d_model
    din = d * cfg.ssm_expand
    N = cfg.ssm_state
    dt_rank = max(1, d // 16)
    return {
        "w_in": P((d, 2 * din), ("embed", "inner")),
        "conv": P((din, cfg.ssm_conv), ("inner", "conv"), "small"),
        "conv_b": P((din,), ("inner",), "zeros"),
        "w_x": P((din, dt_rank + 2 * N), ("inner", None)),
        "dt_norm": P((dt_rank,), (None,), "ones"),
        "b_norm": P((N,), (None,), "ones"),
        "c_norm": P((N,), (None,), "ones"),
        "w_dt": P((dt_rank, din), (None, "inner")),
        "dt_bias": P((din,), ("inner",), "zeros"),
        "a_log": P((din, N), ("inner", "state"), "small"),
        "dparam": P((din,), ("inner",), "ones"),
        "w_out": P((din, d), ("inner", "embed")),
    }


def mamba_block(p, x, cfg, state=None):
    """x: (b, s, d).  state: None or {'conv': (b, din, conv-1),
    'ssm': (b, din, N)} for decode."""
    b, s, d = x.shape
    din = d * cfg.ssm_expand
    N = cfg.ssm_state
    dt_rank = max(1, d // 16)
    K = cfg.ssm_conv

    xz = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(x.dtype))
    xs, z = jnp.split(xz, 2, axis=-1)           # (b, s, din)
    xs = shard(xs, "act_batch", "act_seq", "act_inner")

    # causal depthwise conv over seq
    prev = (state["conv"] if state is not None
            else jnp.zeros((b, din, K - 1), x.dtype))
    xt = jnp.swapaxes(xs, 1, 2)                 # (b, din, s)
    xpad = jnp.concatenate([prev, xt], axis=-1)
    new_conv = xpad[..., -(K - 1):] if K > 1 else prev
    conv_w = p["conv"].astype(x.dtype)
    xc = sum(xpad[..., i:i + s] * conv_w[:, i][None, :, None]
             for i in range(K)) + p["conv_b"].astype(x.dtype)[None, :, None]
    xc = jax.nn.silu(jnp.swapaxes(xc, 1, 2))    # (b, s, din)

    xdb = jnp.einsum("bsi,ie->bse", xc, p["w_x"].astype(x.dtype))
    dt, B, C = jnp.split(xdb, [dt_rank, dt_rank + N], axis=-1)
    dt = rms_norm(dt, p["dt_norm"])
    B = rms_norm(B, p["b_norm"]).astype(jnp.float32)
    C = rms_norm(C, p["c_norm"]).astype(jnp.float32)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", dt, p["w_dt"].astype(x.dtype))
        + p["dt_bias"].astype(x.dtype)).astype(jnp.float32)   # (b, s, din)

    A = -jnp.exp(p["a_log"].astype(jnp.float32))              # (din, N)
    dA = jnp.exp(dt[..., None] * A[None, None])               # (b, s, din, N)
    dBx = (dt * xc.astype(jnp.float32))[..., None] * B[:, :, None, :]

    h0 = (state["ssm"].astype(jnp.float32) if state is not None
          else jnp.zeros((b, din, N), jnp.float32))

    def step(h, t):
        dA_t, dBx_t, C_t = t
        h = dA_t * h + dBx_t
        y = jnp.einsum("bin,bn->bi", h, C_t)
        return h, y

    xs_scan = (jnp.moveaxis(dA, 1, 0), jnp.moveaxis(dBx, 1, 0),
               jnp.moveaxis(C, 1, 0))
    h, ys = jax.lax.scan(step, h0, xs_scan)
    y = jnp.moveaxis(ys, 0, 1)                                # (b, s, din)
    y = y + xc.astype(jnp.float32) * p["dparam"].astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"].astype(x.dtype))
    new_state = {"conv": new_conv, "ssm": h.astype(jnp.float32)}
    return shard(out, "act_batch", "act_seq", "act_embed"), new_state


def mamba_state_spec(cfg, batch: int) -> SpecTree:
    din = cfg.d_model * cfg.ssm_expand
    return {
        "conv": P((batch, din, cfg.ssm_conv - 1),
                  ("cache_batch", "act_inner", None), "zeros"),
        "ssm": P((batch, din, cfg.ssm_state),
                 ("cache_batch", "act_inner", "state"), "zeros",
                 dtype="float32"),
    }
