"""Wrappers adapting the Pallas kernels to the model-layer interfaces.

These are the payloads of the ``kernel/*`` uniform components with
``env='tpu-pallas'`` / ``env='pallas-interpret'``.  The catalog binds
``interpret`` from the build's ``SpecSheet.interpret_kernels`` and hands the
bound callable to the model through ``Variants``, so every build carries its
own mode: a cpu and a tpu instance built in one process do not share it.

With ``interpret=True`` the kernel body runs in Python via the Pallas
interpreter — bit-accurate for correctness tests, useless for speed; that
asymmetry is exactly the deployability trade-off Algorithm 1 scores.

Under an active sharding plan on more than one device, each kernel runs per
shard (``shard_map`` over the plan's batch and head axes): a ``pallas_call``
cannot be partitioned automatically.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..models.sharding import current_plan
from .flash_attention import flash_attention
from .rmsnorm import rmsnorm_pallas
from .rwkv6_scan import wkv6_pallas

_HEADS = ("act_batch", "act_heads", None, None)     # (b, h, s, d) layout


def _per_shard(fn: Callable, args: Sequence[jax.Array],
               in_specs: Sequence[PartitionSpec], out_specs):
    """Run ``fn`` on each device's shard of ``args`` under the active plan."""
    plan = current_plan()
    if plan is None or plan.mesh.size == 1:
        return fn(*args)
    return jax.shard_map(fn, mesh=plan.mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)(*args)


def _heads_spec(x) -> PartitionSpec:
    plan = current_plan()
    if plan is None:
        return PartitionSpec(None, None, None, None)
    return plan.spec(_HEADS, x.shape)


def pallas_attention(q, k, v, *, scale, causal=True, window=0, softcap=0.0,
                     q_offset=0, kv_len=None, interpret: bool,
                     block_q=512, block_k=512):
    """Attention-kernel interface over the Pallas flash kernel.

    A prefill chunk that continues a partly filled cache (``q_offset`` /
    ``kv_len``) takes the blocked-lax path: the kernel models a chunk that
    attends only to itself.
    """
    from ..models.attention import lax_flash_attention, naive_attention
    fresh = kv_len is None and isinstance(q_offset, int) and q_offset == 0
    if not fresh:
        return lax_flash_attention(q, k, v, scale=scale, causal=causal,
                                   window=window, softcap=softcap,
                                   q_offset=q_offset, kv_len=kv_len)
    sq, skv = q.shape[2], k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if sq % bq or skv % bk:
        if not interpret:
            raise ValueError(
                f"flash attention needs lengths that are multiples of its "
                f"blocks: q {sq} / {bq}, kv {skv} / {bk}")
        return naive_attention(q, k, v, scale=scale, causal=causal,
                               window=window, softcap=softcap)
    fn = functools.partial(flash_attention, scale=scale, causal=causal,
                           window=window, softcap=softcap, block_q=bq,
                           block_k=bk, interpret=interpret)
    # the kv-head spec shards q too: a q-head shard must hold the q heads
    # of exactly the kv heads in its kv shard
    spec = _heads_spec(k)
    return _per_shard(fn, (q, k, v), (spec, spec, spec), spec)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pallas_wkv6(r, k, v, w, u, state=None, *, interpret: bool,
                chunk: int = 64):
    """WKV-impl interface over the Pallas WKV6 kernel.

    Any length runs the kernel: the tail is padded to the chunk with
    ``r = k = v = 0`` and ``w = 1``, which leaves the output of the real
    tokens and the carried state exact.  Short inputs (decode) use one
    chunk of 16, the bf16 sublane tile.
    """
    b, h, s, K = r.shape
    V = v.shape[-1]
    L = chunk if s >= chunk else _round_up(s, 16)
    pad = _round_up(s, L) - s
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        r, k, v = (jnp.pad(t, widths) for t in (r, k, v))
        w = jnp.pad(w, widths, constant_values=1)
    if state is None:
        state = jnp.zeros((b, h, K, V), jnp.float32)
    fn = functools.partial(wkv6_pallas, chunk=L, interpret=interpret)
    spec = _heads_spec(r)
    u_spec = PartitionSpec(spec[1], None)
    y, s_out = _per_shard(fn, (r, k, v, w, u, state),
                          (spec, spec, spec, spec, u_spec, spec),
                          (spec, spec))
    return y[:, :, :s], s_out


def pallas_rmsnorm(x, w, eps: float = 1e-6, plus_one: bool = False, *,
                   interpret: bool):
    """rms_norm interface over the fused Pallas kernel; x: (b, s, d)."""
    fn = functools.partial(rmsnorm_pallas, eps=eps, plus_one=plus_one,
                           interpret=interpret)
    plan = current_plan()
    spec = (plan.spec(("act_batch", "act_seq", None), x.shape)
            if plan is not None else None)
    return _per_shard(fn, (x, w), (spec, PartitionSpec()), spec)
