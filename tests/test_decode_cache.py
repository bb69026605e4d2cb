"""The decode step updates the donated cache in place.  The layer scan only
reads a full-length KV cache and each new token is written after the scan;
a state a layer rewrites whole (RWKV, Mamba) rides the scan's carry, each
layer's slice written back into the stacked buffer.  The step's program
holds no second cache and copies none."""
import copy
import dataclasses
import math
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import LazyBuilder, PreBuilder, cpu_smoke
from repro.models import attention, transformer
from repro.models.common import apply_rope, matmul_precision
from repro.serving import ServingEngine


def _built(service, smoke_mesh, cfg):
    """The model a serve build of ``cfg`` gives, and its parameters."""
    inst = LazyBuilder(service).build(
        PreBuilder(service).prebuild(cfg, entrypoint="serve"), cpu_smoke(),
        mesh=smoke_mesh)
    return inst.model, inst.model.init(jax.random.PRNGKey(0))


def _phi4(service, smoke_mesh):
    """The reduced phi4-mini (partial rotary, the benchmark's model) and
    its parameters."""
    return _built(service, smoke_mesh, ARCHS["phi4-mini-3.8b"].reduced())


def _copies(compiled, cache) -> list:
    """The compiled program's copies of a buffer shaped as a cache leaf."""
    dims = {",".join(map(str, x.shape)) for x in jax.tree.leaves(cache)}
    return [ln for ln in compiled.as_text().splitlines()
            if any(re.search(rf"= \w+\[{d}\]\{{[^}}]*\}} copy(-start)?\(",
                             ln) for d in dims)]


def test_decode_writes_the_donated_cache_in_place(service, smoke_mesh):
    model, params = _phi4(service, smoke_mesh)
    eng = ServingEngine(model, params, num_slots=4, max_seq=256,
                        prefill_buckets=(16,))
    compiled = eng.lower_decode().compile()
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
    copies = _copies(compiled, {"k": eng.cache["blocks"]["k"]})
    assert not copies, copies[:2]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < cache_bytes
    assert mem.alias_size_in_bytes == cache_bytes


def test_decode_updates_the_donated_recurrent_state_in_place(service,
                                                              smoke_mesh):
    """RWKV-6's three state leaves ride the layer scan's carry: the compiled
    decode copies none of them, holds no second state (8 layers, so the
    stacked state outweighs one layer's temporaries) and aliases the whole
    state to its output."""
    cfg = dataclasses.replace(ARCHS["rwkv6-1.6b"].reduced(), num_layers=8)
    model, params = _built(service, smoke_mesh, cfg)
    eng = ServingEngine(model, params, num_slots=16, max_seq=64,
                        prefill_buckets=(16,))
    compiled = eng.lower_decode().compile()
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
    copies = _copies(compiled, eng.cache)
    assert not copies, copies[:2]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < state_bytes
    assert mem.alias_size_in_bytes == state_bytes


def _write_then_attend(params, x, cfg, *, positions, cache=None,
                       cache_pos=None, **kw):
    """The decode step as it was before the cache was written in place: the
    token is written into a copy of the layer's cache, attends over its
    positions < cache_pos + 1, and the whole cache is handed back."""
    if cache is None or x.shape[1] != 1:
        return attention.gqa_attention(params, x, cfg, positions=positions,
                                       cache=cache, cache_pos=cache_pos, **kw)
    b, h, kv, hd = x.shape[0], cfg.n_heads, cfg.n_kv, cfg.head_dim

    def proj(w, n):
        return attention._proj(x, params["w" + w], params.get("b" + w)) \
            .reshape(b, 1, n, hd)

    q, k, v = proj("q", h), proj("k", kv), proj("v", kv)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary,
                   cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary,
                   cfg.mrope_sections)
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    upd = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
        c, n, (0, p, 0)))
    ck = upd(cache["k"], k.astype(cache["k"].dtype), cache_pos)
    cv = upd(cache["v"], v.astype(cache["v"].dtype), cache_pos)
    o = attention.naive_attention(q, ck, cv, scale=1.0 / math.sqrt(hd),
                                  causal=False, softcap=cfg.attn_softcap,
                                  q_offset=cache_pos, kv_len=cache_pos + 1)
    o = jnp.swapaxes(o, 1, 2).reshape(b, 1, h * hd)
    out = jnp.einsum("bsf,fd->bsd", o, params["wo"].astype(o.dtype))
    return out, {"k": ck, "v": cv}


def test_in_place_cache_matches_write_then_attend(service, smoke_mesh,
                                                  monkeypatch):
    """Staggered slots and a slot that stays dead: after every step the
    cache written in place equals, row for row, the cache the write-then-
    attend step builds, and the dead slot's writes land only at
    max_seq - 1."""
    model, params = _phi4(service, smoke_mesh)
    monkeypatch.setattr(transformer, "gqa_attention", _write_then_attend)
    ref_model = transformer.build_model(model.cfg, model.variants)
    monkeypatch.undo()

    max_seq, dead = 64, 2
    engines = [ServingEngine(m, params, num_slots=3, max_seq=max_seq,
                             prefill_buckets=(16,))
               for m in (model, ref_model)]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, model.cfg.vocab, n).tolist() for n in (5, 11)]
    for step in range(9):
        for eng in engines:
            if step in (0, 3):      # the second request starts 3 steps later
                eng.submit(prompts[step // 3], max_new_tokens=8)
            eng.tick()
        got, ref = engines
        assert list(got.slot_pos) == list(ref.slot_pos)
        for name in ("k", "v"):
            # the softmax sums in another order: float32 rounding, carried
            # through the layers, against the cache's largest entry
            want = np.asarray(ref.cache["blocks"][name])
            np.testing.assert_allclose(
                np.asarray(got.cache["blocks"][name]), want, rtol=1e-5,
                atol=1e-5 * np.abs(want).max(), err_msg=f"step {step} {name}")
    assert got.slot_req[dead] is None and got.slot_pos[0] != got.slot_pos[1]
    for name in ("k", "v"):
        rows = np.asarray(got.cache["blocks"][name])[:, dead]
        assert not rows[:, :, :max_seq - 1].any()
        assert rows[:, :, max_seq - 1].any()
    assert [r.tokens for r in got.finished] == \
        [r.tokens for r in ref.finished]


def _scanned_backbone(self, params, x, positions, cache=None, cache_pos=0):
    """The layer scan as it was before a step's state rode the carry: every
    cache leaf goes in as xs and comes back as ys (a new buffer), and each
    ``KVWrite`` is written into its cache after the scan."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    with matmul_precision(self.cfg):
        for st in self.stacks:
            st_cache = cache.get(st.name) if cache is not None else None

            def scan_fn(carry, xs, _body=st.apply):
                h, a = carry
                p, c = xs
                h, c_new, a_l = _body(p, h, positions, c, cache_pos)
                return (h, a + a_l), c_new

            (x, aux), c_out = jax.lax.scan(
                scan_fn, (x, aux), (params[st.name], st_cache))
            if st_cache is not None:
                new_cache[st.name] = transformer._write_back(
                    st_cache, c_out, cache_pos)
    return x, (new_cache if cache is not None else None), aux


def _jamba():
    """The reduced Jamba in two super-blocks, its attention layer moved
    inside the reduced 4-layer period: each super-block carries Mamba
    states and holds a KV cache."""
    return dataclasses.replace(ARCHS["jamba-v0.1-52b"].reduced(),
                               num_layers=8, attn_offset=1)


@pytest.mark.parametrize("make_cfg", [
    lambda: ARCHS["rwkv6-1.6b"].reduced(), _jamba], ids=["rwkv6", "jamba"])
def test_carried_state_matches_the_scanned_state(service, smoke_mesh,
                                                 make_cfg):
    """Staggered slots and a slot that stays dead: after every tick the
    decode over the carried state gives the logits and every cache leaf
    of the decode that scanned the state as xs and ys, bitwise, and the
    two engines hold the same caches and tokens."""
    model, params = _built(service, smoke_mesh, make_cfg())
    ref_model = copy.copy(model)
    ref_model.backbone = types.MethodType(_scanned_backbone, ref_model)
    steps = [jax.jit(m.decode_step) for m in (model, ref_model)]

    max_seq = 64
    engines = [ServingEngine(m, params, num_slots=3, max_seq=max_seq,
                             prefill_buckets=(16,))
               for m in (model, ref_model)]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, model.cfg.vocab, n).tolist() for n in (5, 11)]
    for step in range(9):
        for eng in engines:
            if step in (0, 3):      # the second request starts 3 steps later
                eng.submit(prompts[step // 3], max_new_tokens=8)
            eng.tick()
        got, ref = engines
        assert list(got.slot_pos) == list(ref.slot_pos)
        for a, b in zip(jax.tree.leaves(got.cache), jax.tree.leaves(ref.cache)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"step {step}")
        # the next decode, both ways, on the same staggered inputs
        live = np.array([r is not None for r in got.slot_req])
        pos = jnp.asarray(np.where(live, got.slot_pos, max_seq - 1)
                          .astype(np.int32))
        toks = jnp.asarray(got._next_tokens)[:, None]
        outs = [f(params, toks, pos[:, None], got.cache, pos) for f in steps]
        for a, b in zip(*(jax.tree.leaves(o) for o in outs)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"step {step}")
    assert got.slot_req[2] is None and got.slot_pos[0] != got.slot_pos[1]
    assert [r.tokens for r in got.finished] == \
        [r.tokens for r in ref.finished]
