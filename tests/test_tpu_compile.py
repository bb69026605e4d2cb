"""The serving path's Pallas kernels compile for a described TPU v5e.

The TPU compiler compiles for a chip that is described and not attached, so
these tests need no chip: each compiles one kernel at the widths of a model
the repo serves and checks that the compiled program holds the kernel
(``tpu_custom_call``) and did not fall back to another path.  The topology
is described only inside a fixture (never at import), so one test worker
loads the TPU library and the others collect the same tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax.sharding import SingleDeviceSharding

from repro.kernels import pallas_attention, pallas_rmsnorm, pallas_wkv6
from repro.models.sharding import RULE_SETS, ShardingPlan, use_plan


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


@pytest.mark.parametrize("b,s", [
    (1, 512),     # rwkv6-1.6b prefill, 8 chunks of 64
    (1, 50),      # a prompt length the wrapper pads to the chunk
    (4, 1),       # one decode step over 4 slots
])
def test_wkv6_compiles_at_rwkv6_widths(one_chip, b, s):
    h, K = 32, 64                   # d_model 2048, head size 64
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rkvw = [sd((b, h, s, K), jnp.bfloat16)] * 4
    args = rkvw + [sd((h, K), jnp.bfloat16), sd((b, h, K, K), jnp.float32)]
    text = _compiled_text(
        lambda r, k, v, w, u, st: pallas_wkv6(r, k, v, w, u, st,
                                              interpret=False), *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("hq,hkv,s", [
    (24, 8, 64),       # phi4-mini-3.8b, one serving prefill bucket
    (24, 8, 2048),
    (32, 32, 64),      # codeqwen1.5-7b
    (32, 32, 2048),
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
