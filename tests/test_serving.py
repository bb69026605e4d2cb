"""Serving engine: continuous batching correctness — staggered slot-based
decode must produce exactly the tokens of isolated greedy decoding."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import LazyBuilder, PreBuilder, cpu_smoke
from repro.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _isolated_greedy(model, params, prompt, n_new, max_seq=64):
    """Reference: decode one request alone through the cache."""
    cfg = model.cfg
    b, s = 1, len(prompt)
    cache = model.init_cache(1, max_seq)
    toks = jnp.asarray([prompt], jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos, (3, 1, s))
    batch = {"tokens": toks, "positions": pos}
    logits, cache = model.prefill(params, batch, cache)
    out = [int(jnp.argmax(logits[0]))]
    for t in range(s, s + n_new - 1):
        p1 = jnp.full((1, 1), t, jnp.int32)
        if cfg.mrope_sections:
            p1 = jnp.broadcast_to(p1, (3, 1, 1))
        logits, cache = model.decode_step(
            params, jnp.asarray([[out[-1]]], jnp.int32), p1, cache,
            jnp.int32(t))
        out.append(int(jnp.argmax(logits[0])))
    return out


@pytest.mark.parametrize("arch_id", ["codeqwen1.5-7b", "gemma2-9b",
                                     "jamba-v0.1-52b", "phi4-mini-3.8b"])
def test_continuous_batching_matches_isolated_decode(arch_id, service,
                                                     smoke_mesh):
    cfg = ARCHS[arch_id].reduced()
    pb = PreBuilder(service)
    lb = LazyBuilder(service)
    inst = lb.build(pb.prebuild(cfg, entrypoint="serve"), cpu_smoke(),
                    mesh=smoke_mesh)
    model = inst.model
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab, rng.integers(3, 9)).tolist()
               for _ in range(5)]
    n_new = 5

    expected = [_isolated_greedy(model, params, p, n_new) for p in prompts]

    # 2 slots for 5 requests: forces queueing, staggered positions and
    # slot reuse — the adversarial case for per-slot cache_pos
    eng = ServingEngine(model, params, num_slots=2, max_seq=64,
                        prefill_buckets=(16,))
    for p in prompts:
        eng.submit(p, max_new_tokens=n_new)
    resp = eng.run_until_drained()
    got = {r.rid: r.tokens for r in resp}
    assert len(got) == 5
    for i, exp in enumerate(expected):
        assert got[i] == exp, f"{arch_id} request {i}: {got[i]} != {exp}"


@pytest.mark.parametrize("arch_id", [
    "phi4-mini-3.8b",       # dense: an 11-token prompt in a 16 bucket
    "qwen2-vl-2b",          # M-RoPE: the model lays out (b, s) positions
    "musicgen-medium",      # audio-lm: tokens through the embedding table
    "rwkv6-1.6b",           # recurrent: prefilled at the exact length
])
def test_engine_prefill_matches_the_entry_on_the_unpadded_prompt(
        arch_id, service, smoke_mesh):
    """The engine prefills through the serve entry's ``Model.prefill``: at a
    padded bucket it gives the next-token logits of the unpadded prompt."""
    cfg = ARCHS[arch_id].reduced()
    inst = LazyBuilder(service).build(
        PreBuilder(service).prebuild(cfg, entrypoint="serve"), cpu_smoke(),
        mesh=smoke_mesh)
    params = inst.model.init(jax.random.PRNGKey(0))
    engine = inst.entry["make_engine"](params, num_slots=1, max_seq=32,
                                       prefill_buckets=(16,))
    prompt = np.random.default_rng(2).integers(1, cfg.vocab, 11).tolist()
    got, _, n_kept = engine.prefill(prompt)
    batch = {"tokens": jnp.asarray([prompt], jnp.int32),
             "positions": jnp.arange(len(prompt), dtype=jnp.int32)[None]}
    want, _ = inst.entry["prefill"](params, batch,
                                    inst.model.init_cache(1, 32))
    assert n_kept == len(prompt)
    assert engine._bucket_for(len(prompt)) == (
        len(prompt) if inst.model.recurrent else 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_engine_respects_max_new_tokens(service, smoke_mesh):
    cfg = ARCHS["starcoder2-3b"].reduced()
    pb = PreBuilder(service)
    lb = LazyBuilder(service)
    inst = lb.build(pb.prebuild(cfg, entrypoint="serve"), cpu_smoke(),
                    mesh=smoke_mesh)
    model = inst.model
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=3, max_seq=64,
                        prefill_buckets=(16,))
    for n in (1, 3, 7):
        eng.submit([1, 2, 3], max_new_tokens=n)
    resp = eng.run_until_drained()
    assert sorted(len(r.tokens) for r in resp) == [1, 3, 7]


def test_temperature_sampling_differs_from_greedy(service, smoke_mesh):
    cfg = ARCHS["phi4-mini-3.8b"].reduced()
    pb = PreBuilder(service)
    lb = LazyBuilder(service)
    inst = lb.build(pb.prebuild(cfg, entrypoint="serve"), cpu_smoke(),
                    mesh=smoke_mesh)
    model = inst.model
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=2, max_seq=64,
                        prefill_buckets=(16,), rng_seed=7)
    eng.submit([5, 6, 7], max_new_tokens=12, temperature=0.0)
    eng.submit([5, 6, 7], max_new_tokens=12, temperature=5.0)
    resp = {r.rid: r.tokens for r in eng.run_until_drained()}
    # first emitted token comes from prefill argmax for both; the decode
    # tail should diverge at high temperature
    assert resp[0] != resp[1]


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, numpy as np
    from repro.configs import ARCHS
    from repro.core import LazyBuilder, catalog
    from repro.launch.serve import (build_serving, init_params,
                                    rebuild_with_kernels)

    svc = catalog.build_service()
    for arch in ("phi4-mini-3.8b", "rwkv6-1.6b"):
        # four kv heads, so the kernels' head axis splits over the devices
        cfg = dataclasses.replace(ARCHS[arch].reduced(), n_kv=4)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (5, 16)]
        tokens = {}
        for shape, axes in (((1,), ("data",)), ((1, 4), ("data", "model"))):
            builder = LazyBuilder(svc)
            inst = build_serving(builder, cfg, shape, axes)
            inst.wait("ready")
            inst = rebuild_with_kernels(builder, inst, {
                "attention": "pallas-interpret", "wkv6": "pallas-interpret"})
            params = init_params(inst, 0)
            engine = inst.entry["make_engine"](
                params, num_slots=2, max_seq=32, prefill_buckets=(16,))
            for p in prompts:
                engine.submit(p, max_new_tokens=4)
            tokens[shape] = sorted((r.rid, r.tokens)
                                   for r in engine.run_until_drained())
            if shape == (1, 4):
                # the decode plan splits the KV cache, and rwkv's
                # recurrent state, over their 4 heads
                for tree in (params, engine.cache):
                    assert any(not x.sharding.is_fully_replicated
                               for x in jax.tree.leaves(tree)), arch
        assert tokens[(1,)] == tokens[(1, 4)], (arch, tokens)
    print("SHARDED-OK")
""")


def test_sharded_engine_matches_one_device():
    """Serving on a (1, 4) mesh (parameters and cache spread by the serve
    plan, the Pallas kernels run per shard) gives the one-device tokens."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED-OK" in r.stdout
