"""Logical-axis sharding plans (GSPMD) + activation constraint helper.

A *plan* maps logical axis names (declared by each param spec / activation
site) onto physical mesh axes.  Plans are uniform components — the
lazy-builder's deployability logic picks the variant fitting the platform
(pure-TP when the model replicates into HBM, FSDP+TP otherwise, SP rules for
long-context decode).
"""
from __future__ import annotations

import contextvars
import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisVal = Union[None, str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# Rule sets
# ---------------------------------------------------------------------------

def _batch_axes(mesh_axes: Sequence[str]) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh_axes)


def tp_rules(mesh_axes: Sequence[str]) -> Dict[str, AxisVal]:
    """Pure tensor parallel over 'model'; params replicated across data."""
    b = _batch_axes(mesh_axes)
    return {
        # params
        "vocab": "model", "embed": None, "mlp": "model", "heads": "model",
        "kv_heads": "model", "head_dim": None, "expert": "model",
        "expert_mlp": None, "layer": None, "lora": None, "state": None,
        "conv": None, "inner": "model",
        # activations
        "act_batch": b, "act_seq": None, "act_embed": None,
        "act_heads": "model", "act_kv_heads": "model", "act_vocab": "model",
        "act_mlp": "model", "act_inner": "model", "act_expert": "model",
        # kv-cache / recurrent state
        "cache_batch": b, "cache_seq": None, "cache_heads": "model",
        # optimizer-state extra sharding (ZeRO-1) target axis
        "_zero1": b,
    }


def fsdp_tp_rules(mesh_axes: Sequence[str]) -> Dict[str, AxisVal]:
    """TP over 'model' + param FSDP over the batch axes ('embed' dim)."""
    r = tp_rules(mesh_axes)
    b = _batch_axes(mesh_axes)
    r.update({"embed": b, "expert_mlp": None})
    return r


def decode_rules(mesh_axes: Sequence[str]) -> Dict[str, AxisVal]:
    """Batched decode, batch over the data axes.  The KV cache shards its
    heads over 'model' where the kv heads divide that axis: each chip then
    attends with its own query heads to its own kv heads, as the attention
    weights are split, and nothing moves between chips but the all-reduce
    after the output projection.  Where they do not divide it (kv_heads
    rarely does at 16-way), the head dim is replicated and 'model' falls to
    the sequence dim instead (flash-decode — GSPMD turns the seq-contracted
    attention einsum into partial softmax sums + an all-reduce)."""
    r = fsdp_tp_rules(mesh_axes)
    b = _batch_axes(mesh_axes)
    r.update({
        "cache_batch": b, "cache_heads": "model", "cache_seq": "model",
    })
    return r


def sp_decode_rules(mesh_axes: Sequence[str]) -> Dict[str, AxisVal]:
    """Long-context decode (batch=1): the KV cache / recurrent state is the
    entire footprint, so its sequence dim shards over EVERY mesh axis."""
    r = fsdp_tp_rules(mesh_axes)
    b = _batch_axes(mesh_axes)
    r.update({
        "cache_batch": None, "cache_seq": b + ("model",),
        "cache_heads": None, "act_batch": None,
    })
    return r


def dp_rules(mesh_axes: Sequence[str]) -> Dict[str, AxisVal]:
    """Pure data parallelism over EVERY mesh axis: params replicated, the
    batch sharded 256-way.  The right plan for models small enough to
    replicate — TP of a 1.5 GB model over 16 chips leaves each matmul too
    skinny to pay for its resharding collectives.  Optimizer moments stay
    ZeRO-1-sharded over the whole mesh."""
    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh_axes)
    r = {k: None for k in tp_rules(mesh_axes)}
    r.update({
        "act_batch": all_axes, "cache_batch": all_axes,
        "_zero1": all_axes,
    })
    return r


def prefill_sp_rules(mesh_axes: Sequence[str]) -> Dict[str, AxisVal]:
    """Prefill sequence parallelism: activations shard over 'model' on the
    SEQUENCE dim instead of heads/mlp.  For GQA with tiny kv (kv_heads <
    model axis), head-sharding degenerates to replication + per-layer
    all-gathers; seq-sharding keeps every matmul fully local and only the
    (small) K/V tensors are gathered for causal attention."""
    r = fsdp_tp_rules(mesh_axes)
    r.update({
        "act_seq": "model", "act_heads": None, "act_mlp": None,
        "act_vocab": None, "act_inner": None,
        "cache_seq": "model", "cache_heads": None,
    })
    return r


RULE_SETS = {
    "tp": tp_rules,
    "fsdp-tp": fsdp_tp_rules,
    "decode": decode_rules,
    "sp-decode": sp_decode_rules,
    "prefill-sp": prefill_sp_rules,
    "dp": dp_rules,
}


@dataclasses.dataclass
class ShardingPlan:
    name: str
    mesh: Mesh
    rules: Dict[str, AxisVal]

    def spec(self, logical: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> PartitionSpec:
        """When ``shape`` is given, mesh axes that do not divide the dim are
        dropped (replicated) — 12 heads never shard over a 16-way axis."""
        used = set()
        parts = []
        for i, ax in enumerate(logical):
            val = self.rules.get(ax) if ax else None
            if val is None:
                parts.append(None)
                continue
            axes = (val,) if isinstance(val, str) else tuple(val)
            axes = tuple(a for a in axes
                         if a in self.mesh.axis_names and a not in used)
            if shape is not None:
                kept = []
                dim = shape[i]
                for a in axes:
                    n = self.mesh.shape[a]
                    if dim % n == 0 and dim >= n:
                        kept.append(a)
                        dim //= n
                axes = tuple(kept)
            used.update(axes)
            if not axes:
                parts.append(None)
            elif len(axes) == 1:
                parts.append(axes[0])
            else:
                parts.append(axes)
        return PartitionSpec(*parts)

    def sharding(self, logical: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))

    def tree_shardings(self, axes_tree) -> Any:
        return jax.tree.map(
            lambda ax: self.sharding(ax), axes_tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))


# ---------------------------------------------------------------------------
# Activation-constraint context: model code calls shard(x, 'act_batch',
# 'act_seq', 'act_embed'); a plan must be active for it to take effect.
# ---------------------------------------------------------------------------

_ACTIVE_PLAN: contextvars.ContextVar[Optional[ShardingPlan]] = \
    contextvars.ContextVar("repro_sharding_plan", default=None)


class use_plan:
    def __init__(self, plan: Optional[ShardingPlan]):
        self.plan = plan

    def __enter__(self):
        self._tok = _ACTIVE_PLAN.set(self.plan)
        return self.plan

    def __exit__(self, *exc):
        _ACTIVE_PLAN.reset(self._tok)


def current_plan() -> Optional[ShardingPlan]:
    return _ACTIVE_PLAN.get()


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    plan = _ACTIVE_PLAN.get()
    if plan is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    return jax.lax.with_sharding_constraint(
        x, plan.sharding(logical, x.shape))


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer-state sharding = param sharding + batch-axis sharding on
# the largest unsharded dimension.
# ---------------------------------------------------------------------------

def zero1_axes(axes: Tuple[Optional[str], ...], plan: ShardingPlan,
               shape: Tuple[int, ...]) -> Tuple[Optional[str], ...]:
    target = plan.rules.get("_zero1") or ()
    if isinstance(target, str):
        target = (target,)
    target = tuple(a for a in target if a in plan.mesh.axis_names)
    if not target:
        return axes
    n = 1
    for a in target:
        n *= plan.mesh.shape[a]
    # find largest dim whose logical axis maps to nothing and divides n
    best, best_size = -1, 0
    spec = plan.spec(axes)
    for i, (dim, ax) in enumerate(zip(shape, spec)):
        if ax is None and dim % n == 0 and dim > best_size:
            best, best_size = i, dim
    if best < 0:
        return axes
    new_axes = list(axes)
    new_axes[best] = "_zero1"
    return tuple(new_axes)
