"""Per-arch smoke tests: reduced same-family config, one lazy-built train
step on CPU, assert output shapes + finite values.  Serve-decode smoke for
every arch as well."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, ARCHS
from repro.core import LazyBuilder, PreBuilder, cpu_smoke


@pytest.fixture(scope="module")
def built(service, smoke_mesh):
    lb = LazyBuilder(service)
    pb = PreBuilder(service)
    cache = {}

    def build(arch_id, entrypoint="train"):
        key = (arch_id, entrypoint)
        if key not in cache:
            cfg = ARCHS[arch_id].reduced()
            cir = pb.prebuild(cfg, entrypoint=entrypoint)
            cache[key] = lb.build(cir, cpu_smoke(), mesh=smoke_mesh)
        return cache[key]
    return build


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_train_step_smoke(arch_id, built):
    inst = built(arch_id)
    e = inst.entry
    cfg = inst.model.cfg
    state = e["init_state"](jax.random.PRNGKey(0))
    raw = e["batch_fn"](64, 2)
    batch = {k: jnp.asarray(v) for k, v in raw.items()}
    assert batch["tokens"].shape == (2, 64)
    state, metrics = jax.jit(e["train_step"])(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and 0.0 < loss < 20.0, (arch_id, loss)
    # params stay finite after the update
    leaves = jax.tree_util.tree_leaves(state["params"])
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
               for l in leaves), arch_id
    # a second step decreases nothing catastrophically
    state, m2 = jax.jit(e["train_step"])(state, batch)
    assert np.isfinite(float(m2["loss"]))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_decode_smoke(arch_id, built):
    """All ten archs are decoder-style: one prefill + two decode steps."""
    inst = built(arch_id, "serve")
    model = inst.model
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    b, s, max_seq = 2, 8, 32
    cache = model.init_cache(b, max_seq)
    toks = jnp.ones((b, s), jnp.int32)
    pos = jnp.tile(jnp.arange(s), (b, 1))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos, (3, b, s))
    batch = {"tokens": toks, "positions": pos}
    logits, cache = inst.entry["prefill"](params, batch, cache)
    assert logits.shape == (b, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for t in range(s, s + 2):
        p1 = jnp.full((b, 1), t, jnp.int32)
        if cfg.mrope_sections:
            p1 = jnp.broadcast_to(p1, (3, b, 1))
        logits, cache = inst.entry["decode_step"](params, nxt, p1, cache,
                                                  jnp.int32(t))
        assert logits.shape == (b, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits))), (arch_id, t)
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_matches_assignment(arch_id):
    """The full configs carry the exact public numbers."""
    cfg = ARCHS[arch_id]
    expected = {
        "deepseek-v3-671b": (61, 7168, 128, 128, 129280),
        "dbrx-132b": (40, 6144, 48, 8, 100352),
        "gemma2-9b": (42, 3584, 16, 8, 256000),
        "codeqwen1.5-7b": (32, 4096, 32, 4, 92416),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 200064),
        "starcoder2-3b": (30, 3072, 24, 2, 49152),
        "musicgen-medium": (48, 1536, 24, 24, 2048),
        "rwkv6-1.6b": (24, 2048, 32, 32, 65536),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 65536),
        "qwen2-vl-2b": (28, 1536, 12, 2, 151936),
    }[arch_id]
    assert (cfg.num_layers, cfg.d_model, cfg.n_heads, cfg.n_kv,
            cfg.vocab) == expected


def test_param_counts_in_published_ballpark():
    """Analytic param counts land near the published sizes (within ~20%)."""
    expect = {
        "deepseek-v3-671b": 671e9, "dbrx-132b": 132e9, "gemma2-9b": 9.2e9,
        "codeqwen1.5-7b": 7.3e9, "phi4-mini-3.8b": 3.8e9,
        "starcoder2-3b": 3.0e9, "rwkv6-1.6b": 1.6e9,
        "jamba-v0.1-52b": 52e9, "qwen2-vl-2b": 1.5e9,
    }
    for aid, n in expect.items():
        got = ARCHS[aid].param_count()
        assert abs(got - n) / n < 0.25, (aid, got, n)
