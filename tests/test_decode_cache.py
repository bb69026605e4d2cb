"""The decode step writes each new token into the donated KV cache in place:
the layer scan only reads a full-length cache, and the step's program holds
no second cache and copies none."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.core import LazyBuilder, PreBuilder, cpu_smoke
from repro.models import attention, transformer
from repro.models.common import apply_rope
from repro.serving import ServingEngine


def _phi4(service, smoke_mesh):
    """The reduced phi4-mini (partial rotary, the benchmark's model) and
    its parameters."""
    cfg = ARCHS["phi4-mini-3.8b"].reduced()
    inst = LazyBuilder(service).build(
        PreBuilder(service).prebuild(cfg, entrypoint="serve"), cpu_smoke(),
        mesh=smoke_mesh)
    return inst.model, inst.model.init(jax.random.PRNGKey(0))


def test_decode_writes_the_donated_cache_in_place(service, smoke_mesh):
    model, params = _phi4(service, smoke_mesh)
    eng = ServingEngine(model, params, num_slots=4, max_seq=256,
                        prefill_buckets=(16,))
    compiled = eng.lower_decode().compile()
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
    dims = ",".join(map(str, eng.cache["blocks"]["k"].shape))
    copies = [ln for ln in compiled.as_text().splitlines()
              if re.search(rf"= \w+\[{dims}\]\{{[^}}]*\}} copy(-start)?\(",
                           ln)]
    assert not copies, copies[:2]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < cache_bytes
    assert mem.alias_size_in_bytes == cache_bytes


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
