"""Pallas TPU kernels for the perf-critical compute layers.

<name>.py   — pl.pallas_call + BlockSpec kernel (TPU target)
ops.py      — wrappers matching the model-layer kernel interfaces
ref.py      — pure-jnp oracles the tests assert against
"""
from .ops import pallas_attention, pallas_rmsnorm, pallas_wkv6  # noqa: F401
