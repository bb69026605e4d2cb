"""Model assembly: layer patterns → scanned stacks → LM (+MTP) heads.

Families
  dense-lm   : uniform attention+FFN layers (optionally alternating
               local/global sliding-window — gemma2)
  moe-lm     : attention + MoE layers (optionally a dense prefix — deepseek)
  ssm-lm     : RWKV6 time-mix + channel-mix
  hybrid-lm  : Jamba period-8 super-blocks (1 attn : 7 mamba, MoE every 2nd)
  audio-lm   : dense decoder over precomputed EnCodec frame embeddings (stub)
  vlm-lm     : dense decoder with M-RoPE + injected patch embeddings (stub)

Layers are stacked and driven by ``lax.scan`` (small HLO, fast compile, the
MaxText idiom); KV caches / recurrent states ride along as scan xs/ys.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .attention import (gqa_attention, gqa_cache_spec, gqa_spec,
                        lax_flash_attention, mla_attention, mla_cache_spec,
                        mla_spec)
from .common import (P, SpecTree, apply_norm, axes_tree, cross_entropy,
                     embed_spec, embed_tokens, eval_shape_tree, head_spec,
                     init_tree, lm_logits, norm_spec, rms_norm,
                     sinusoidal_pos, softcap, stacked)
from .ffn import MOE_IMPLS, ffn_apply, ffn_spec, moe_spec
from .sharding import shard
from .ssm import (mamba_block, mamba_spec, mamba_state_spec,
                  rwkv6_channel_mix, rwkv6_spec, rwkv6_state_spec,
                  rwkv6_time_mix, wkv6_chunked)


@dataclasses.dataclass
class Variants:
    """The kernel callables and policies one build selected."""
    attn_kernel: Callable = lax_flash_attention
    moe_impl: str = "grouped"
    wkv_impl: Callable = wkv6_chunked
    rms_norm: Callable = rms_norm
    remat: str = "full"            # none | full | dots
    capacity_factor: float = 1.25
    moe_combine: str = "f32"       # f32 | bf16 slot tensors / combine
    moe_slot_dp: bool = False      # shard slot capacity dim over data


@dataclasses.dataclass
class Stack:
    """One scanned group of identical layers."""
    name: str
    n: int
    spec: SpecTree                              # per-layer (unstacked)
    apply: Callable                             # (p, x, positions, cache, pos) -> (x, cache, aux)
    cache_spec: Callable                        # (batch, max_seq) -> SpecTree or None


@dataclasses.dataclass
class Model:
    cfg: Any
    variants: Variants
    stacks: Tuple[Stack, ...]
    specs: SpecTree                             # full stacked param tree
    mtp: bool = False

    # -- params ---------------------------------------------------------
    def init(self, key, dtype=None):
        import numpy as _np
        dt = jnp.dtype(dtype or self.cfg.dtype)
        return init_tree(key, self.specs, dt)

    def param_axes(self):
        return axes_tree(self.specs)

    def param_shapes(self, dtype=None):
        dt = jnp.dtype(dtype or self.cfg.dtype)
        return eval_shape_tree(self.specs, dt)

    # -- caches ----------------------------------------------------------
    def cache_specs(self, batch: int, max_seq: int) -> SpecTree:
        out: SpecTree = {}
        for st in self.stacks:
            cs = st.cache_spec(batch, max_seq)
            if cs is not None:
                out[st.name] = stacked(cs, st.n)
        return out

    def init_cache(self, batch: int, max_seq: int, dtype=None):
        dt = jnp.dtype(dtype or self.cfg.dtype)
        return init_tree(jax.random.PRNGKey(0),
                         self.cache_specs(batch, max_seq), dt)

    def cache_axes(self, batch: int, max_seq: int):
        return axes_tree(self.cache_specs(batch, max_seq))

    # -- forward ----------------------------------------------------------
    def backbone(self, params, x, positions, cache=None, cache_pos=0):
        """x: (b, s, d) embeddings → (h, new_cache, aux)."""
        aux = jnp.zeros((), jnp.float32)
        new_cache: Dict[str, Any] = {}
        for st in self.stacks:
            body = st.apply
            if self.variants.remat != "none" and cache is None:
                policy = None
                if self.variants.remat == "dots":
                    policy = jax.checkpoint_policies.checkpoint_dots
                body = jax.checkpoint(body, policy=policy,
                                      static_argnums=())
            st_cache = cache.get(st.name) if cache is not None else None

            def scan_fn(carry, xs, _body=body):
                h, a = carry
                p, c = xs
                h, c_new, a_l = _body(p, h, positions, c, cache_pos)
                return (h, a + a_l), c_new

            stacked_params = params[st.name]
            (x, aux), c_out = jax.lax.scan(
                scan_fn, (x, aux), (stacked_params, st_cache))
            if st_cache is not None:
                new_cache[st.name] = c_out
        return x, (new_cache if cache is not None else None), aux

    def logits_fn(self, params, embeds, positions, cache=None, cache_pos=0):
        h, new_cache, aux = self.backbone(params, embeds, positions,
                                          cache, cache_pos)
        h = apply_norm(params["final_norm"], h, self.cfg,
                       self.variants.rms_norm)
        logits = lm_logits(params.get("head", {}), params["embed"], h,
                           self.cfg)
        logits = shard(logits, "act_batch", "act_seq", "act_vocab")
        return logits, h, new_cache, aux

    # -- embedding frontends ----------------------------------------------
    def embed(self, params, batch):
        cfg = self.cfg
        if cfg.family == "audio-lm":
            # frontend stub: precomputed EnCodec frame embeddings
            e = batch["embeds"].astype(jnp.dtype(cfg.dtype))
            pos2d = batch["positions"] if batch["positions"].ndim == 2 \
                else batch["positions"][0]
            e = e + sinusoidal_pos(pos2d, cfg.d_model).astype(e.dtype)
            return e
        e = embed_tokens(params["embed"], batch["tokens"], cfg)
        if cfg.family == "vlm-lm" and "vis_embeds" in batch:
            ve = batch["vis_embeds"].astype(e.dtype)
            e = jax.lax.dynamic_update_slice(e, ve, (0, 0, 0))
        return e

    # -- train loss ---------------------------------------------------------
    def loss(self, params, batch):
        cfg = self.cfg
        e = self.embed(params, batch)
        e = shard(e, "act_batch", "act_seq", "act_embed")
        positions = batch["positions"]
        logits, h, _, aux = self.logits_fn(params, e, positions)
        loss = cross_entropy(logits, batch["labels"], batch.get("mask"))
        metrics = {"ce": loss, "aux": aux}
        total = loss + 0.01 * aux
        if self.mtp and "mtp" in params:
            mtp_loss = self._mtp_loss(params, h, e, batch)
            metrics["mtp"] = mtp_loss
            total = total + 0.1 * mtp_loss
        return total, metrics

    def _mtp_loss(self, params, h, e, batch):
        """DeepSeek-V3 multi-token prediction: one extra block predicts
        token t+2 from (norm(h_t), norm(emb_{t+1}))."""
        cfg = self.cfg
        p = params["mtp"]
        h_in = apply_norm(p["norm_h"], h, cfg, self.variants.rms_norm)
        e_next = jnp.roll(e, -1, axis=1)
        e_in = apply_norm(p["norm_e"], e_next, cfg, self.variants.rms_norm)
        x = jnp.einsum("bsd,de->bse",
                       jnp.concatenate([h_in, e_in], -1),
                       p["proj"].astype(h.dtype))
        positions = batch["positions"]
        x, _, _ = self._mtp_block_apply(p["block"], x, positions)
        x = apply_norm(params["final_norm"], x, cfg,
                       self.variants.rms_norm)
        logits = lm_logits(params.get("head", {}), params["embed"], x, cfg)
        labels2 = jnp.roll(batch["labels"], -1, axis=1)
        mask = jnp.ones_like(labels2, jnp.float32).at[:, -2:].set(0.0)
        return cross_entropy(logits, labels2, mask)

    # populated by build_model for MTP archs
    _mtp_block_apply: Callable = None

    # -- serving -------------------------------------------------------------
    def prefill(self, params, batch, cache):
        """Prefill computes the LM head for the LAST position only — the
        full-sequence vocab projection (b, s, V) is pure waste at prefill
        (s=32k × vocab=256k would dwarf the backbone's own traffic)."""
        e = self.embed(params, batch)
        positions = batch["positions"]
        h, cache, _ = self.backbone(params, e, positions, cache, 0)
        h_last = apply_norm(params["final_norm"], h[:, -1:, :], self.cfg,
                            self.variants.rms_norm)
        logits = lm_logits(params.get("head", {}), params["embed"], h_last,
                           self.cfg)
        return logits[:, 0, :], cache

    def decode_step(self, params, tokens, positions, cache, cache_pos):
        """tokens: (b, 1); positions: (b, 1) or (3, b, 1)."""
        batch = {"tokens": tokens}
        if self.cfg.family == "audio-lm":
            # decode feeds embeddings: frontends decode via embedding table
            e = params["embed"]["tok"][tokens]
            pos2d = positions if positions.ndim == 2 else positions[0]
            e = e + sinusoidal_pos(pos2d, self.cfg.d_model).astype(e.dtype)
        else:
            e = embed_tokens(params["embed"], tokens, self.cfg)
        logits, _, cache, _ = self.logits_fn(params, e, positions, cache,
                                             cache_pos)
        return logits[:, -1, :], cache


# ---------------------------------------------------------------------------
# Block builders
# ---------------------------------------------------------------------------

def _attn_block_spec(cfg, window: bool) -> SpecTree:
    sp: SpecTree = {"norm1": norm_spec(cfg),
                    "attn": mla_spec(cfg) if cfg.attention == "mla"
                    else gqa_spec(cfg)}
    if cfg.post_norms:
        sp["post1"] = norm_spec(cfg)
    return sp


def _ffn_part_spec(cfg, moe: bool) -> SpecTree:
    sp: SpecTree = {"norm2": norm_spec(cfg),
                    "ffn": moe_spec(cfg) if moe else ffn_spec(cfg)}
    if cfg.post_norms:
        sp["post2"] = norm_spec(cfg)
    return sp


def _make_attn_ffn_block(cfg, v: Variants, *, moe: bool, window: int):
    attn_fn = mla_attention if cfg.attention == "mla" else gqa_attention
    moe_fn = MOE_IMPLS[v.moe_impl]
    if v.moe_impl == "grouped":
        moe_fn = functools.partial(moe_fn,
                                   capacity_factor=v.capacity_factor,
                                   combine_dtype=v.moe_combine,
                                   slot_dp_shard=v.moe_slot_dp)
    qscale = None
    if cfg.arch_id.startswith("gemma"):
        qscale = (cfg.d_model / cfg.n_heads) ** -0.5   # query_pre_attn_scalar

    def apply(p, x, positions, cache, cache_pos):
        h = apply_norm(p["norm1"], x, cfg, v.rms_norm)
        a, new_cache = attn_fn(p["attn"], h, cfg, positions=positions,
                               kernel=v.attn_kernel, window=window,
                               cache=cache, cache_pos=cache_pos,
                               query_scale=qscale)
        if cfg.post_norms:
            a = apply_norm(p["post1"], a, cfg, v.rms_norm)
        x = x + a
        h = apply_norm(p["norm2"], x, cfg, v.rms_norm)
        if moe:
            f, aux = moe_fn(p["ffn"], h, cfg)
        else:
            f, aux = ffn_apply(p["ffn"], h, cfg), jnp.zeros((), jnp.float32)
        if cfg.post_norms:
            f = apply_norm(p["post2"], f, cfg, v.rms_norm)
        x = x + f
        return x, new_cache, aux

    spec = {**_attn_block_spec(cfg, window > 0), **_ffn_part_spec(cfg, moe)}
    return spec, apply


def _attn_cache_spec_fn(cfg):
    def fn(batch, max_seq):
        if cfg.attention == "mla":
            return mla_cache_spec(cfg, batch, max_seq)
        return gqa_cache_spec(cfg, batch, max_seq)
    return fn


# -- dense / moe stacks -------------------------------------------------------

def _uniform_stacks(cfg, v: Variants) -> Tuple[Stack, ...]:
    stacks = []
    if cfg.alt_local_global:
        # gemma2: scanned super-block = [local(window), global]
        spec_l, apply_l = _make_attn_ffn_block(cfg, v, moe=False,
                                               window=cfg.sliding_window)
        spec_g, apply_g = _make_attn_ffn_block(cfg, v, moe=False, window=0)

        def apply(p, x, positions, cache, cache_pos):
            cl = cache.get("local") if cache else None
            cg = cache.get("global") if cache else None
            x, c1, a1 = apply_l(p["local"], x, positions, cl, cache_pos)
            x, c2, a2 = apply_g(p["global"], x, positions, cg, cache_pos)
            nc = {"local": c1, "global": c2} if cache is not None else None
            return x, nc, a1 + a2

        cs = _attn_cache_spec_fn(cfg)

        def cache_spec(batch, max_seq):
            # local layers only ever see `window` tokens: ring-buffer cache
            local_len = min(max_seq, cfg.sliding_window) if cfg.sliding_window \
                else max_seq
            return {"local": cs(batch, local_len), "global": cs(batch, max_seq)}

        return (Stack("blocks", cfg.num_layers // 2,
                      {"local": spec_l, "global": spec_g}, apply, cache_spec),)

    if cfg.is_moe and cfg.first_dense_layers:
        spec_d, apply_d = _make_attn_ffn_block(cfg, v, moe=False, window=0)
        spec_m, apply_m = _make_attn_ffn_block(cfg, v, moe=True, window=0)
        cs = _attn_cache_spec_fn(cfg)
        stacks.append(Stack(
            "dense", cfg.first_dense_layers, spec_d,
            lambda p, x, pos, c, cp: apply_d(p, x, pos, c, cp),
            lambda b, s: cs(b, s)))
        stacks.append(Stack(
            "moe", cfg.num_layers - cfg.first_dense_layers, spec_m,
            lambda p, x, pos, c, cp: apply_m(p, x, pos, c, cp),
            lambda b, s: cs(b, s)))
        return tuple(stacks)

    moe = cfg.is_moe
    spec, apply = _make_attn_ffn_block(cfg, v, moe=moe,
                                       window=cfg.sliding_window
                                       if not cfg.alt_local_global else 0)
    cs = _attn_cache_spec_fn(cfg)
    return (Stack("blocks", cfg.num_layers, spec, apply,
                  lambda b, s: cs(b, s)),)


# -- rwkv stack ----------------------------------------------------------------

def _rwkv_stacks(cfg, v: Variants) -> Tuple[Stack, ...]:
    spec = {"norm1": norm_spec(cfg), "norm2": norm_spec(cfg),
            **rwkv6_spec(cfg)}

    def apply(p, x, positions, cache, cache_pos):
        tm_state = None
        if cache is not None:
            tm_state = {"shift": cache["tm_shift"], "wkv": cache["wkv"]}
        h = apply_norm(p["norm1"], x, cfg, v.rms_norm)
        a, tm_new = rwkv6_time_mix(p["tm"], h, cfg, tm_state, v.wkv_impl)
        x = x + a
        h = apply_norm(p["norm2"], x, cfg, v.rms_norm)
        cm_state = cache["cm_shift"] if cache is not None else None
        f, cm_new = rwkv6_channel_mix(p["cm"], h, cfg, cm_state)
        x = x + f
        nc = None
        if cache is not None:
            nc = {"tm_shift": tm_new["shift"], "wkv": tm_new["wkv"],
                  "cm_shift": cm_new}
        return x, nc, jnp.zeros((), jnp.float32)

    return (Stack("blocks", cfg.num_layers, spec, apply,
                  lambda b, s: rwkv6_state_spec(cfg, b)),)


# -- jamba hybrid stack ----------------------------------------------------------

def _hybrid_stacks(cfg, v: Variants) -> Tuple[Stack, ...]:
    period = cfg.attn_period
    n_super = cfg.num_layers // period
    moe_fn = MOE_IMPLS[v.moe_impl]
    if v.moe_impl == "grouped":
        moe_fn = functools.partial(moe_fn,
                                   capacity_factor=v.capacity_factor,
                                   combine_dtype=v.moe_combine,
                                   slot_dp_shard=v.moe_slot_dp)

    sub_specs: SpecTree = {}
    for i in range(period):
        is_attn = (i == cfg.attn_offset)
        is_moe = cfg.is_moe and (i % cfg.moe_every == 1)
        sp: SpecTree = {"norm1": norm_spec(cfg)}
        sp["mix"] = gqa_spec(cfg) if is_attn else mamba_spec(cfg)
        sp["norm2"] = norm_spec(cfg)
        sp["ffn"] = moe_spec(cfg) if is_moe else ffn_spec(cfg)
        sub_specs[f"l{i}"] = sp

    def apply(p, x, positions, cache, cache_pos):
        aux = jnp.zeros((), jnp.float32)
        nc: Dict[str, Any] = {}
        for i in range(period):
            sp = p[f"l{i}"]
            is_attn = (i == cfg.attn_offset)
            is_moe = cfg.is_moe and (i % cfg.moe_every == 1)
            ci = cache.get(f"l{i}") if cache is not None else None
            h = apply_norm(sp["norm1"], x, cfg, v.rms_norm)
            if is_attn:
                a, c_new = gqa_attention(sp["mix"], h, cfg,
                                         positions=positions,
                                         kernel=v.attn_kernel,
                                         cache=ci, cache_pos=cache_pos)
            else:
                a, c_new = mamba_block(sp["mix"], h, cfg, ci)
                if cache is None:
                    c_new = None
            x = x + a
            h = apply_norm(sp["norm2"], x, cfg, v.rms_norm)
            if is_moe:
                f, a_l = moe_fn(sp["ffn"], h, cfg)
                aux = aux + a_l
            else:
                f = ffn_apply(sp["ffn"], h, cfg)
            x = x + f
            if cache is not None:
                nc[f"l{i}"] = c_new
        return x, (nc if cache is not None else None), aux

    def cache_spec(batch, max_seq):
        out: SpecTree = {}
        for i in range(period):
            if i == cfg.attn_offset:
                out[f"l{i}"] = gqa_cache_spec(cfg, batch, max_seq)
            else:
                out[f"l{i}"] = mamba_state_spec(cfg, batch)
        return out

    return (Stack("blocks", n_super, sub_specs, apply, cache_spec),)


# ---------------------------------------------------------------------------
# build_model — the Uniform Component Assembler's model half
# ---------------------------------------------------------------------------

def build_model(cfg, variants: Optional[Variants] = None) -> Model:
    v = variants or Variants()
    if cfg.family == "ssm-lm":
        stacks = _rwkv_stacks(cfg, v)
    elif cfg.family == "hybrid-lm":
        stacks = _hybrid_stacks(cfg, v)
    else:
        stacks = _uniform_stacks(cfg, v)

    specs: SpecTree = {"embed": embed_spec(cfg),
                       "final_norm": norm_spec(cfg)}
    hs = head_spec(cfg)
    if hs:
        specs["head"] = hs
    for st in stacks:
        specs[st.name] = stacked(st.spec, st.n)

    mtp_apply = None
    if cfg.mtp:
        blk_spec, blk_apply = _make_attn_ffn_block(cfg, v, moe=False, window=0)
        specs["mtp"] = {
            "norm_h": norm_spec(cfg), "norm_e": norm_spec(cfg),
            "proj": P((2 * cfg.d_model, cfg.d_model), ("embed", "embed")),
            "block": blk_spec,
        }
        def mtp_apply(p, x, positions, _apply=blk_apply):
            return _apply(p, x, positions, None, 0)

    m = Model(cfg=cfg, variants=v, stacks=tuple(stacks), specs=specs,
              mtp=cfg.mtp)
    m._mtp_block_apply = mtp_apply
    return m
