"""Attention: GQA (naive / chunked-flash / pallas), MLA, sliding window,
softcap, M-RoPE; training and decode (KV cache) paths.

The *kernel* actually used is a uniform component (kernel/attention)
selected by the lazy-builder and passed in as a callable: ``naive`` for tiny
smoke shapes, ``lax-flash`` (chunked online-softmax, VMEM-bounded) for
compiled CPU/dry-run targets, and the Pallas TPU kernel when the specSheet
says a real TPU is present.
"""
from __future__ import annotations

import functools
import math
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import P, SpecTree, apply_rope
from .sharding import shard

NEG_INF = -2.0 ** 30   # finite: keeps masked softmax NaN-free on empty rows


# ---------------------------------------------------------------------------
# Core attention kernels (q: (b, hq, sq, d); k/v: (b, hkv, skv, d))
# ---------------------------------------------------------------------------

def naive_attention(q, k, v, *, scale, causal=True, window=0, softcap=0.0,
                    q_offset=0, kv_len=None):
    """``q_offset`` / ``kv_len`` may be scalars or (b,) vectors — the vector
    form supports slot-based continuous batching where every sequence in the
    batch sits at its own decode depth."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    q = q.reshape(b, hkv, g, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    skv = k.shape[2]
    qo = jnp.asarray(q_offset)
    per_slot = qo.ndim > 0 or (kv_len is not None
                               and jnp.asarray(kv_len).ndim > 0)
    if per_slot:
        # masks shaped (b, 1, 1, sq, skv)
        qpos = qo.reshape(-1, 1, 1)[..., None] \
            + jnp.arange(sq)[None, None, :, None]          # (b,1,sq,1)
        kpos = jnp.arange(skv)[None, None, None, :]
        mask = jnp.ones((b, 1, sq, skv), bool)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        if kv_len is not None:
            kl = jnp.asarray(kv_len).reshape(-1, 1, 1, 1)
            mask &= kpos < kl
        mask = mask[:, :, None, :, :]                      # (b,1,1,sq,skv)
    else:
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = jnp.arange(skv)[None, :]
        mask = jnp.ones((sq, skv), bool)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        if kv_len is not None:
            mask &= kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(v.dtype)


def lax_flash_attention(q, k, v, *, scale, causal=True, window=0,
                        softcap=0.0, q_offset=0, kv_len=None,
                        block_q=512, block_k=1024):
    """Chunked online-softmax attention: scan over q blocks, inner scan over
    kv blocks.  Working set per step is (bq, bk) — the XLA analogue of the
    Pallas kernel's VMEM tiling, used for compiled dry-run/roofline paths."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if sq % bq or skv % bk:
        return naive_attention(q, k, v, scale=scale, causal=causal,
                               window=window, softcap=softcap,
                               q_offset=q_offset, kv_len=kv_len)
    nq, nk = sq // bq, skv // bk
    dv = v.shape[-1]           # MLA: v head dim may differ from qk head dim
    qr = q.reshape(b, hkv, g, nq, bq, d).astype(jnp.float32)
    kr = k.reshape(b, hkv, nk, bk, d).astype(jnp.float32)
    vr = v.reshape(b, hkv, nk, bk, dv).astype(jnp.float32)

    def q_block(carry, qi):
        qb, iq = qi            # (b,hkv,g,bq,d), scalar index
        m0 = jnp.full((b, hkv, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, dv), jnp.float32)

        def kv_block(c, kj):
            m, l, acc = c
            kb, vb, jk = kj
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb) * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            qpos = q_offset + iq * bq + jnp.arange(bq)[:, None]
            kpos = jk * bk + jnp.arange(bk)[None, :]
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= qpos >= kpos
            if window:
                mask &= qpos - kpos < window
            if kv_len is not None:
                mask &= kpos < kv_len
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vb)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.moveaxis(kr, 2, 0), jnp.moveaxis(vr, 2, 0),
             jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-37)
        return carry, out

    _, outs = jax.lax.scan(
        q_block, None,
        (jnp.moveaxis(qr, 3, 0), jnp.arange(nq)))   # (nq, b,hkv,g,bq,dv)
    o = jnp.moveaxis(outs, 0, 3).reshape(b, hq, sq, dv)
    return o.astype(v.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------

def gqa_spec(cfg) -> SpecTree:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    sp: SpecTree = {
        "wq": P((d, h * hd), ("embed", "heads")),
        "wk": P((d, kv * hd), ("embed", "kv_heads")),
        "wv": P((d, kv * hd), ("embed", "kv_heads")),
        "wo": P((h * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        sp["bq"] = P((h * hd,), ("heads",), "zeros")
        sp["bk"] = P((kv * hd,), ("kv_heads",), "zeros")
        sp["bv"] = P((kv * hd,), ("kv_heads",), "zeros")
    return sp


def _proj(x, w, b=None):
    y = jnp.einsum("bsd,df->bsf", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def gqa_attention(params, x, cfg, *, positions, kernel=lax_flash_attention,
                  window=0, cache=None, cache_pos=None,
                  query_scale: Optional[float] = None):
    """Returns (out, new_cache).  ``kernel`` is the selected attention
    callable.  Train: cache=None.  Decode: cache is {'k': (b, kv, S, hd),
    'v': ...} updated at cache_pos (int32 scalar; the Python int 0 marks a
    fresh prefill)."""
    b, s, dm = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = _proj(x, params["wq"], params.get("bq")).reshape(b, s, h, hd)
    k = _proj(x, params["wk"], params.get("bk")).reshape(b, s, kv, hd)
    v = _proj(x, params["wv"], params.get("bv")).reshape(b, s, kv, hd)

    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary,
                       cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary,
                       cfg.mrope_sections)
    q = jnp.swapaxes(q, 1, 2)   # (b, h, s, hd)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    q = shard(q, "act_batch", "act_heads", "act_seq", None)

    scale = query_scale if query_scale is not None else 1.0 / math.sqrt(hd)
    new_cache = None
    if cache is None:
        o = kernel(q, k, v, scale=scale, causal=True, window=window,
                   softcap=cfg.attn_softcap)
    else:
        cache_len = cache["k"].shape[2]
        ring = bool(window) and cache_len <= window
        per_slot = jnp.asarray(cache_pos).ndim > 0
        if ring:
            # sliding-window ring buffer: the cache holds only `window`
            # entries; token t lives in slot t % window.  128x smaller
            # local-layer caches for long-context decode.
            if s == 1:
                slot = jnp.asarray(cache_pos) % window
                if per_slot:
                    upd = jax.vmap(
                        lambda c, n, p: jax.lax.dynamic_update_slice(
                            c, n, (0, p, 0)))
                    ck = upd(cache["k"], k.astype(cache["k"].dtype), slot)
                    cv = upd(cache["v"], v.astype(cache["v"].dtype), slot)
                else:
                    ck = jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype),
                        (0, 0, slot, 0))
                    cv = jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype),
                        (0, 0, slot, 0))
                new_cache = {"k": ck, "v": cv}
                kv_len = jnp.minimum(jnp.asarray(cache_pos) + 1, window)
                o = naive_attention(q, ck, cv, scale=scale, causal=False,
                                    softcap=cfg.attn_softcap, kv_len=kv_len)
            else:
                # prefill: attend within the chunk, keep the last `window`
                # tokens (requires s % window == 0 or s <= window so slot
                # layout stays aligned)
                assert s % window == 0 or s < window, (s, window)
                o = kernel(q, k, v, scale=scale, causal=True,
                           window=window, softcap=cfg.attn_softcap)
                if s >= window:
                    ck = k[:, :, -window:, :].astype(cache["k"].dtype)
                    cv = v[:, :, -window:, :].astype(cache["v"].dtype)
                else:
                    ck = jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
                    cv = jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
                new_cache = {"k": ck, "v": cv}
        else:
            if per_slot:
                # continuous batching: each slot writes at its own position
                upd = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
                    c, n, (0, p, 0)))
                ck = upd(cache["k"], k.astype(cache["k"].dtype), cache_pos)
                cv = upd(cache["v"], v.astype(cache["v"].dtype), cache_pos)
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype),
                    (0, 0, cache_pos, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype),
                    (0, 0, cache_pos, 0))
            new_cache = {"k": ck, "v": cv}
            if s == 1:   # decode: one query over the cache, O(S) per step
                o = naive_attention(q, ck, cv, scale=scale, causal=False,
                                    window=window, softcap=cfg.attn_softcap,
                                    q_offset=cache_pos, kv_len=cache_pos + 1)
            elif isinstance(cache_pos, int) and cache_pos == 0:
                # fresh prefill: the cache holds nothing before this chunk,
                # so the chunk attends only to itself
                o = kernel(q, k, v, scale=scale, causal=True,
                           window=window, softcap=cfg.attn_softcap)
            else:        # prefill chunk continuing a partly filled cache
                o = kernel(q, ck, cv, scale=scale, causal=True,
                           window=window, softcap=cfg.attn_softcap,
                           q_offset=cache_pos, kv_len=cache_pos + s)
    o = jnp.swapaxes(o, 1, 2).reshape(b, s, h * hd)
    out = jnp.einsum("bsf,fd->bsd", o, params["wo"].astype(o.dtype))
    return shard(out, "act_batch", "act_seq", "act_embed"), new_cache


def gqa_cache_spec(cfg, batch: int, max_seq: int) -> SpecTree:
    kv, hd = cfg.n_kv, cfg.head_dim
    ax = ("cache_batch", "cache_heads", "cache_seq", None)
    return {"k": P((batch, kv, max_seq, hd), ax, "zeros"),
            "v": P((batch, kv, max_seq, hd), ax, "zeros")}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 Multi-head Latent Attention)
# ---------------------------------------------------------------------------

def mla_spec(cfg) -> SpecTree:
    d, h = cfg.d_model, cfg.n_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq_a": P((d, ql), ("embed", "lora")),
        "q_norm": P((ql,), ("lora",), "ones"),
        "wq_b": P((ql, h * (dn + dr)), ("lora", "heads")),
        "wkv_a": P((d, kvl + dr), ("embed", "lora")),
        "kv_norm": P((kvl,), ("lora",), "ones"),
        "wkv_b": P((kvl, h * (dn + dv)), ("lora", "heads")),
        "wo": P((h * dv, d), ("heads", "embed")),
    }


def _rms(x, w):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6) * w).astype(x.dtype)


def mla_attention(params, x, cfg, *, positions, kernel=lax_flash_attention,
                  cache=None, cache_pos=None, **_):
    """Train path decompresses K/V per head and runs flash; decode path keeps
    the cache *compressed* (c_kv + k_rope) — the MLA memory saving — and
    absorbs the up-projections into the query/output."""
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank

    q_lat = _rms(_proj(x, params["wq_a"]), params["q_norm"])
    q = _proj(q_lat, params["wq_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = _proj(x, params["wkv_a"])                # (b, s, kvl + dr)
    c_kv = _rms(kv_a[..., :kvl], params["kv_norm"])
    k_rope = apply_rope(kv_a[..., kvl:][:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]     # (b, s, dr) shared

    scale = 1.0 / math.sqrt(dn + dr)
    wkv_b = params["wkv_b"].reshape(kvl, h, dn + dv)

    if cache is None:
        kv = jnp.einsum("bsl,lhe->bshe", c_kv, wkv_b.astype(c_kv.dtype))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))],
            axis=-1)
        qf = jnp.swapaxes(jnp.concatenate([q_nope, q_rope], -1), 1, 2)
        kf = jnp.swapaxes(k, 1, 2)
        vf = jnp.swapaxes(v, 1, 2)
        qf = shard(qf, "act_batch", "act_heads", "act_seq", None)
        o = kernel(qf, kf, vf, scale=scale, causal=True)
        o = jnp.swapaxes(o, 1, 2)
        new_cache = None
    else:
        per_slot = jnp.asarray(cache_pos).ndim > 0
        if per_slot:
            upd = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
                c, n, (p, 0)))
            cc = upd(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype),
                     cache_pos)
            cr = upd(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                     cache_pos)
        else:
            cc = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype),
                (0, cache_pos, 0))
            cr = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                (0, cache_pos, 0))
        new_cache = {"c_kv": cc, "k_rope": cr}
        w_uk, w_uv = wkv_b[:, :, :dn], wkv_b[:, :, dn:]
        # absorb: q_c = q_nope @ w_uk^T  -> compressed-space query
        q_c = jnp.einsum("bshd,lhd->bshl", q_nope, w_uk.astype(q_nope.dtype))
        s_c = jnp.einsum("bshl,bTl->bhsT", q_c.astype(jnp.float32),
                         cc.astype(jnp.float32))
        s_r = jnp.einsum("bshd,bTd->bhsT", q_rope.astype(jnp.float32),
                         cr.astype(jnp.float32))
        att = (s_c + s_r) * scale
        S = cc.shape[1]
        if per_slot:
            qpos = (jnp.asarray(cache_pos).reshape(-1, 1, 1)
                    + jnp.arange(s)[None, :, None])         # (b, s, 1)
            kpos = jnp.arange(S)[None, None, :]
            mask = (kpos <= qpos) & (
                kpos < jnp.asarray(cache_pos).reshape(-1, 1, 1) + s)
            mask = mask[:, None]                            # (b, 1, s, S)
        else:
            qpos = cache_pos + jnp.arange(s)[:, None]
            kpos = jnp.arange(S)[None, :]
            mask = ((kpos <= qpos) & (kpos < cache_pos + s))[None, None]
        att = jnp.where(mask, att, NEG_INF)
        p = jax.nn.softmax(att, axis=-1)
        o_c = jnp.einsum("bhsT,bTl->bshl", p, cc.astype(jnp.float32))
        o = jnp.einsum("bshl,lhd->bshd", o_c, w_uv.astype(jnp.float32))
        o = o.astype(x.dtype)

    o = o.reshape(b, s, h * dv)
    out = jnp.einsum("bsf,fd->bsd", o, params["wo"].astype(o.dtype))
    return shard(out, "act_batch", "act_seq", "act_embed"), new_cache


def mla_cache_spec(cfg, batch: int, max_seq: int) -> SpecTree:
    return {
        "c_kv": P((batch, max_seq, cfg.kv_lora_rank),
                  ("cache_batch", "cache_seq", None), "zeros"),
        "k_rope": P((batch, max_seq, cfg.qk_rope_dim),
                    ("cache_batch", "cache_seq", None), "zeros"),
    }
