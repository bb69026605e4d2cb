"""The serving path's Pallas kernels compile for a described TPU v5e.

The TPU compiler compiles for a chip that is described and not attached, so
these tests need no chip: each compiles one kernel at the widths of a model
the repo serves and checks that the compiled program holds the kernel
(``tpu_custom_call``) and did not fall back to another path.  The topology
is described only inside a fixture (never at import), so one test worker
loads the TPU library and the others collect the same tests.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels import pallas_attention, pallas_rmsnorm, pallas_wkv6
from repro.models.sharding import RULE_SETS, ShardingPlan, use_plan
from repro.models.transformer import Variants, build_model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b,s,act", [
    (1, 512, jnp.bfloat16),     # rwkv6-1.6b prefill, 8 chunks of 64
    (1, 50, jnp.bfloat16),      # a prompt length the wrapper pads to the chunk
    (4, 1, jnp.bfloat16),       # one decode step over 4 slots
    (16, 1, jnp.float32),       # rwkv6-1.6b.gen decode: 16 slots, f32
])
def test_wkv6_compiles_at_rwkv6_widths(one_chip, b, s, act):
    h, K = 32, 64                   # d_model 2048, head size 64
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rkv = [sd((b, h, s, K), act)] * 3
    lw = sd((b, h, s, K), jnp.float32)      # log-decay, float32
    args = rkv + [lw, sd((h, K), jnp.bfloat16), sd((b, h, K, K), jnp.float32)]
    text = _compiled_text(
        lambda r, k, v, lw, u, st: pallas_wkv6(r, k, v, lw, u, st,
                                               interpret=False), *args)
    assert "tpu_custom_call" in text
    if s == 1:      # the step kernel: y at the token's own length, no pad
        dt = "f32" if act == jnp.float32 else "bf16"
        assert re.search(rf"%wkv6[.\d]* = \({dt}\[{b},32,1,64\]", text)
        assert " pad(" not in text


@pytest.mark.parametrize("hq,hkv,s", [
    (24, 8, 64),       # phi4-mini-3.8b, one serving prefill bucket
    (24, 8, 2048),
    (32, 32, 64),      # multi-head attention: as many kv heads as query heads
    (32, 32, 2048),
    (32, 4, 64),       # codeqwen1.5-7b whole: 8 query heads per kv head
    (32, 4, 2048),
    (8, 1, 64),        # codeqwen1.5-7b per chip at tp4: 8 query heads, 1 kv
    (8, 1, 2048),
])
def test_flash_attention_compiles_at_model_head_layouts(one_chip, hq, hkv,
                                                        s):
    d = 128
    q = jax.ShapeDtypeStruct((1, hq, s, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, hkv, s, d), jnp.bfloat16,
                              sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: pallas_attention(q, k, v, scale=d ** -0.5,
                                         interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [3072, 4096])   # phi4-mini, codeqwen widths
def test_rmsnorm_compiles_at_model_widths(one_chip, d):
    x = jax.ShapeDtypeStruct((1, 64, d), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((d,), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda x, w: pallas_rmsnorm(x, w, interpret=False), x, w)
    assert "tpu_custom_call" in text


def test_attention_runs_per_shard_on_four_chips(topo):
    """Under a (1, 4) serve plan the kernel runs on each chip's heads: it
    compiles with no collective to gather q, k or v."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    plan = ShardingPlan("decode", mesh, RULE_SETS["decode"](mesh.axis_names))
    heads = NamedSharding(mesh, PartitionSpec(None, "model", None, None))
    q = jax.ShapeDtypeStruct((1, 32, 64, 128), jnp.bfloat16, sharding=heads)

    def attend(q, k, v):
        with use_plan(plan):
            return pallas_attention(q, k, v, scale=128 ** -0.5,
                                    interpret=False)

    text = _compiled_text(attend, q, q, q)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-to-all" not in text


def test_codeqwen_tp4_programs_keep_the_cache_on_its_chip(topo):
    """codeqwen1.5-7b at full width over a (1, 4) serve plan: each chip
    holds one kv head of the cache, so the decode step and the largest
    prefill move nothing between chips but the all-reduces after each
    block's output projection (no all-to-all, no all-gather of K or V)."""
    from repro.models.common import P
    from repro.serving import ServingEngine

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    plan = ShardingPlan("decode", mesh, RULE_SETS["decode"](mesh.axis_names))
    cfg = ARCHS["codeqwen1.5-7b"]
    model = build_model(cfg, Variants(
        attn_kernel=functools.partial(pallas_attention, interpret=False),
        rms_norm=functools.partial(pallas_rmsnorm, interpret=False)))
    slots, max_seq = 32, 2304

    def placed(shapes, specs):
        return jax.tree.map(
            lambda a, p: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=plan.sharding(p.axes, p.shape)),
            shapes, specs, is_leaf=lambda x: isinstance(x, P))

    def cache(b):
        return placed(jax.eval_shape(lambda: model.init_cache(b, max_seq)),
                      model.cache_specs(b, max_seq))

    def cache_shardings(b):
        return jax.tree.map(lambda a: a.sharding, cache(b))

    engine = object.__new__(ServingEngine)   # the programs, not the cache
    engine.model, engine.plan, engine.max_seq = model, plan, max_seq
    params = placed(model.param_shapes(), model.specs)
    whole = NamedSharding(mesh, PartitionSpec())
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=whole)
    assert cache(slots)["blocks"]["k"].sharding.spec[2] == "model"
    decode = jax.jit(engine._decode_impl,
                     out_shardings=(None, cache_shardings(slots)),
                     donate_argnums=(1,)).lower(
        params, cache(slots), sd((slots,), jnp.int32),
        sd((slots,), jnp.int32), jax.random.PRNGKey(0),
        sd((slots,), jnp.float32)).compile()
    prefill = jax.jit(engine._prefill_impl,
                      out_shardings=(None, cache_shardings(1))).lower(
        params, sd((1, 2048), jnp.int32), sd((), jnp.int32)).compile()
    for compiled in (decode, prefill):
        text = compiled.as_text()
        assert "all-reduce" in text
        assert " all-to-all(" not in text and "all-to-all-start" not in text
        assert not re.search(r"bf16\[[^\]]*128\][^=]* all-gather", text)
    assert "tpu_custom_call" in prefill.as_text()
    # a quarter of the weights and of the cache on each chip, little else
    mem = decode.memory_analysis()
    assert mem.alias_size_in_bytes == 32 * slots * 1 * max_seq * 128 * 2 * 2
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_rwkv6_decode_updates_its_state_in_place(one_chip):
    """rwkv6-1.6b's decode at full width, 16 slots, with the Pallas kernels:
    the donated state is updated in place, with no whole-state copy and no
    second state among the temporaries (a state scanned as xs and ys held
    409.6 MB of them)."""
    model = build_model(ARCHS["rwkv6-1.6b"], Variants(
        wkv_impl=functools.partial(pallas_wkv6, interpret=False),
        rms_norm=functools.partial(pallas_rmsnorm, interpret=False),
        remat="none"))
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(16, 2048)))
    assert cache["blocks"]["wkv"].shape == (24, 16, 32, 64, 64)
    slots = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)

    def decode(params, cache, tokens, positions):
        return model.decode_step(params, tokens[:, None], positions[:, None],
                                 cache, positions)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        on_chip(model.param_shapes()), cache, slots, slots).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    copies = [ln for ln in text.splitlines()
              if re.search(r"= f32\[24,16,(32,64,64|2048)\]\S* copy", ln)]
    assert not copies, copies[:2]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 20
    # the whole state is aliased, as laid out on the chip: the wkv state's
    # 64-wide minor dim padded to 128 lanes (402.7 MB), two shift states
    assert mem.alias_size_in_bytes == (24 * 16 * 32 * 64 * 128
                                       + 2 * 24 * 16 * 2048) * 4
