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
MaxText idiom).  In a prefill the caches go into the scan as xs and come
back whole as ys.  In a step of one token (decode) nothing is copied whole:
  * a full-length GQA K/V cache goes in as xs and is only read; each layer
    hands back its new token (``KVWrite``) as ys, and after the scan each
    token is written into the stacked cache at its slot's position;
  * every cache a layer rewrites whole (RWKV and Mamba states, sliding-
    window ring caches, MLA caches) rides the scan's carry: each layer reads
    its slice of the stacked buffer and writes its new slice back into it.
So a donated cache is updated in place.  What a layer returns (a
``KVWrite`` or a whole leaf) decides the path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .attention import (KVWrite, gqa_attention, gqa_cache_spec, gqa_spec,
                        lax_flash_attention, mla_attention, mla_cache_spec,
                        mla_spec, write_token)
from .common import (P, SpecTree, apply_norm, axes_tree, cross_entropy,
                     embed_spec, embed_tokens, eval_shape_tree, head_spec,
                     init_tree, lm_logits, matmul_precision, norm_spec,
                     rms_norm, sinusoidal_pos, softcap, stacked)
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
    # layers carry recurrent state, which would integrate a prompt's
    # padding: such a model prefills at the prompt's exact length
    recurrent: bool = False

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
        with matmul_precision(self.cfg):
            for st in self.stacks:
                body = st.apply
                if self.variants.remat != "none" and cache is None:
                    policy = None
                    if self.variants.remat == "dots":
                        policy = jax.checkpoint_policies.checkpoint_dots
                    body = jax.checkpoint(body, policy=policy,
                                          static_argnums=())
                st_cache = cache.get(st.name) if cache is not None else None
                stacked_params = params[st.name]
                has_cache, state = st_cache is not None, None
                if has_cache and x.shape[1] == 1:
                    whole, st_cache = _split_for_step(
                        body, stacked_params, x, positions, st_cache,
                        cache_pos)
                    if whole is not None:
                        state = (jnp.zeros((), jnp.int32), whole,
                                 _layer(whole, 0))

                def scan_fn(carry, xs, _body=body):
                    h, a, state = carry
                    p, c = xs
                    if state is not None:
                        i, whole, now = state
                        c = _merge(now, c)
                    h, c_new, a_l = _body(p, h, positions, c, cache_pos)
                    if state is not None:
                        # write layer i's new state in place, then read
                        # layer i + 1's (past the last layer the index
                        # clamps): no read of the buffer is pending at the
                        # write, so no compiler copies the buffer
                        new, c_new = _split(c_new, c_new)
                        whole = jax.tree.map(
                            lambda s, n: jax.lax.dynamic_update_index_in_dim(
                                s, n, i, 0), whole, new)
                        state = (i + 1, whole, _layer(whole, i + 1))
                    return (h, a + a_l, state), c_new

                (x, aux, state), c_out = jax.lax.scan(
                    scan_fn, (x, aux, state), (stacked_params, st_cache))
                if has_cache:
                    new_cache[st.name] = _merge(
                        state[1] if state is not None else None,
                        _write_back(st_cache, c_out, cache_pos))
        return x, (new_cache if cache is not None else None), aux

    def logits_fn(self, params, embeds, positions, cache=None, cache_pos=0):
        h, new_cache, aux = self.backbone(params, embeds, positions,
                                          cache, cache_pos)
        return self._head(params, h), h, new_cache, aux

    def _head(self, params, h):
        """Final norm and LM head: (b, s, d) hidden states → f32 logits."""
        h = apply_norm(params["final_norm"], h, self.cfg,
                       self.variants.rms_norm)
        logits = lm_logits(params.get("head", {}), params["embed"], h,
                           self.cfg)
        return shard(logits, "act_batch", "act_seq", "act_vocab")

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
    def _embed_served(self, params, tokens, positions):
        """Served tokens (b, s) → embeddings, through the table; an
        ``audio-lm`` adds sinusoidal embeddings of its (b, s) positions
        (``embed``, the training front end, takes precomputed frame
        embeddings instead)."""
        e = embed_tokens(params["embed"], tokens, self.cfg)
        if self.cfg.family == "audio-lm":
            e = e + sinusoidal_pos(positions, self.cfg.d_model).astype(e.dtype)
        return e

    def _rope_positions(self, positions):
        """(b, s) positions in the layout the rotary embedding takes:
        M-RoPE's (3, b, s), one position on all three axes, where the
        config asks for it.  (3, b, s) positions pass as they are."""
        if self.cfg.mrope_sections and positions.ndim == 2:
            return jnp.broadcast_to(positions, (3,) + positions.shape)
        return positions

    def prefill(self, params, batch, cache, length=None):
        """Prefill ``batch["tokens"]`` (b, s) into ``cache``: (next-token
        logits (b, vocab), cache).  The backbone runs over all s positions;
        the final norm and the LM head run on row ``length - 1`` alone, the
        prompt's last real token (``length`` defaults to s: no padding).
        A full-sequence vocab projection (b, s, V) would be pure waste.
        Every family prefills from tokens alone, as it decodes: the
        training front end's ``embeds`` and ``vis_embeds`` are not read."""
        positions = batch["positions"]
        e = self._embed_served(params, batch["tokens"], positions)
        h, cache, _ = self.backbone(params, e, self._rope_positions(positions),
                                    cache, 0)
        length = h.shape[1] if length is None else length
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        return self._head(params, h_last)[:, 0, :], cache

    def decode_step(self, params, tokens, positions, cache, cache_pos):
        """tokens: (b, 1); positions: (b, 1) or (3, b, 1)."""
        e = self._embed_served(params, tokens, positions)
        logits, _, cache, _ = self.logits_fn(
            params, e, self._rope_positions(positions), cache, cache_pos)
        return logits[:, -1, :], cache


def _write_back(cache, out, cache_pos):
    """A stack's cache after its scan: each ``KVWrite`` in the scan's ys
    ((layers, b, kv, 1, hd) leaves) is written into its cache at
    ``cache_pos``; every other subtree of ys is the new cache as it is."""
    if out is None:
        return None
    if isinstance(out, KVWrite):
        return {"k": write_token(cache["k"], out.k, cache_pos),
                "v": write_token(cache["v"], out.v, cache_pos)}
    if isinstance(out, dict):
        return {name: _write_back(cache[name], o, cache_pos)
                for name, o in out.items()}
    return out


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
        stacks, recurrent = _rwkv_stacks(cfg, v), True
    elif cfg.family == "hybrid-lm":
        stacks, recurrent = _hybrid_stacks(cfg, v), True
    else:
        stacks, recurrent = _uniform_stacks(cfg, v), False

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
              mtp=cfg.mtp, recurrent=recurrent)
    m._mtp_block_apply = mtp_apply
    return m


def _split_for_step(body, stacked_params, x, positions, cache, cache_pos):
    """A stack's cache in a step of one token, split by what a layer
    (``body``) returns for it: (whole, written), the subtrees it hands back
    whole, which ride the scan's carry, and those it hands back as a
    ``KVWrite``, which stay xs.  The layer is traced for shapes only."""
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         (stacked_params, cache))
    out = jax.eval_shape(
        lambda p, c: body(p, x, positions, c, cache_pos)[1], *layer)
    return _split(cache, out)


def _split(tree, out):
    """(whole, written): the subtrees of ``tree`` (a cache, or what a layer
    returns for it) that the layer's output ``out`` hands back whole, and
    those it hands back as a ``KVWrite``; None where there are none."""
    if isinstance(out, KVWrite):
        return None, tree
    if isinstance(out, dict):
        parts = {name: _split(tree[name], o) for name, o in out.items()}
        whole = {n: w for n, (w, _) in parts.items() if w is not None}
        written = {n: t for n, (_, t) in parts.items() if t is not None}
        return whole or None, written or None
    return tree, None


def _layer(tree, i):
    """Layer ``i`` of a stacked cache subtree."""
    return jax.tree.map(
        lambda s: jax.lax.dynamic_index_in_dim(s, i, keepdims=False), tree)


def _merge(a, b):
    """The union of two cache subtrees that ``_split`` took apart."""
    if a is None or b is None:
        return b if a is None else a
    return {name: _merge(a.get(name), b.get(name)) for name in {**a, **b}}
