"""Sharding plans: logical-axis → PartitionSpec math, shape-aware axis
dropping, ZeRO-1 placement.  Uses AbstractMesh so no devices are needed."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec

from repro.launch.mesh import parse_mesh
from repro.models.sharding import (RULE_SETS, ShardingPlan, zero1_axes)


def _abstract_mesh(shape, axes):
    return AbstractMesh(shape, axes)


def _plan(rules_name, shape=(16, 16), axes=("data", "model")):
    mesh = _abstract_mesh(shape, axes)
    return ShardingPlan(rules_name, mesh,
                        RULE_SETS[rules_name](axes))


def test_tp_rules_spec():
    p = _plan("tp")
    assert p.spec(("embed", "mlp")) == PartitionSpec(None, "model")
    assert p.spec(("act_batch", "act_seq", "act_embed")) == \
        PartitionSpec("data", None, None)
    assert p.spec(("vocab", "embed")) == PartitionSpec("model", None)


def test_fsdp_tp_shards_embed_over_data():
    p = _plan("fsdp-tp")
    assert p.spec(("embed", "mlp")) == PartitionSpec("data", "model")


def test_multipod_batch_axes_compose():
    p = _plan("fsdp-tp", (2, 16, 16), ("pod", "data", "model"))
    s = p.spec(("act_batch", "act_seq", "act_embed"))
    assert s == PartitionSpec(("pod", "data"), None, None)


def test_axis_used_once_per_spec():
    p = _plan("tp")
    # both logical dims map to 'model': the second must drop it
    s = p.spec(("heads", "mlp"))
    assert s == PartitionSpec("model", None)


def test_shape_aware_dropping():
    p = _plan("tp")
    # 12 heads cannot shard over a 16-way axis
    assert p.spec(("act_batch", "act_heads", None, None),
                  (8, 12, 128, 64)) == \
        PartitionSpec(None, None, None, None)   # 8 % 16 != 0 too
    assert p.spec(("act_batch", "act_heads", None, None),
                  (32, 32, 128, 64)) == \
        PartitionSpec("data", "model", None, None)


def test_decode_rules_shard_cache_seq():
    p = _plan("decode")
    s = p.spec(("cache_batch", "cache_heads", "cache_seq", None),
               (128, 8, 32768, 256))
    assert s == PartitionSpec("data", None, "model", None)


def test_sp_decode_rules_all_axes_on_seq():
    p = _plan("sp-decode")
    s = p.spec(("cache_batch", "cache_heads", "cache_seq", None),
               (1, 8, 524288, 256))
    assert s == PartitionSpec(None, None, ("data", "model"), None)


def test_prefill_sp_rules_shard_sequence():
    p = _plan("prefill-sp")
    s = p.spec(("act_batch", "act_heads", "act_seq", None),
               (32, 24, 32768, 128))
    assert s == PartitionSpec("data", None, "model", None)
    # matmul activations stay local (no head/mlp sharding)
    assert p.spec(("act_batch", "act_seq", "act_mlp"),
                  (32, 32768, 12288)) == \
        PartitionSpec("data", "model", None)


def test_dp_rules_replicate_params_shard_batch_everywhere():
    p = _plan("dp")
    assert p.spec(("embed", "mlp"), (1536, 6144)) == \
        PartitionSpec(None, None)
    assert p.spec(("act_batch", "act_seq", "act_embed"),
                  (256, 4096, 1536)) == \
        PartitionSpec(("data", "model"), None, None)
    # ZeRO-1 target covers the whole mesh
    axes = zero1_axes(("embed", "mlp"), p, (1536, 6144))
    assert "_zero1" in axes


def test_zero1_places_on_largest_free_dim():
    p = _plan("tp")
    # (vocab, embed) -> vocab sharded by model; embed free and divisible
    axes = zero1_axes(("vocab", "embed"), p, (129280, 7168))
    assert axes == ("vocab", "_zero1")
    # nothing free & divisible -> unchanged
    axes = zero1_axes(("vocab",), p, (100,))
    assert axes == ("vocab",)


@pytest.mark.parametrize("text,want", [
    ("1", ((1,), ("data",))),
    ("4", ((4,), ("data",))),
    ("1x4", ((1, 4), ("data", "model"))),
    ("2X2", ((2, 2), ("data", "model"))),
])
def test_parse_mesh(text, want):
    assert parse_mesh(text) == want


@pytest.mark.parametrize("text", ["0", "1x0", "2x2x2", "x4", "four"])
def test_parse_mesh_rejects_malformed(text):
    with pytest.raises(ValueError):
        parse_mesh(text)
