"""``ServingEngine.wkv_tokens``: the tokens each program hands one layer's
WKV call, and those the call computes after padding, counted when the
program is traced (lowering is enough)."""
import dataclasses
import functools

import pytest

from repro.configs import ARCHS
from repro.kernels import pallas_wkv6
from repro.models import build_model
from repro.models.ssm import wkv6_chunked
from repro.models.transformer import Variants
from repro.serving import ServingEngine

SLOTS = 4


def _engine(arch, **variants):
    cfg = dataclasses.replace(ARCHS[arch].reduced(), dtype="bfloat16")
    model = build_model(cfg, Variants(**variants))
    return ServingEngine(model, model.param_shapes(), num_slots=SLOTS,
                         max_seq=256, prefill_buckets=(64,))


@pytest.mark.parametrize("impl,pads_prefill", [
    (functools.partial(pallas_wkv6, interpret=True), True),   # the chip's
    (wkv6_chunked, False),
], ids=["pallas", "chunked-lax"])
def test_rwkv6_decode_and_prefill(impl, pads_prefill):
    engine = _engine("rwkv6-1.6b", wkv_impl=impl)
    engine.lower_decode()
    # one token per slot and layer, computed as one: nothing is padded
    assert engine.wkv_tokens == {"decode": {"real": SLOTS,
                                            "computed": SLOTS}}
    # a recurrent model prefills at the prompt's exact length; the Pallas
    # wrapper pads to 16 below its 64-token chunk, to the chunk above
    for n, padded in ((23, 32), (100, 128)):
        engine.lower_prefill(list(range(1, n + 1)))
        assert engine.wkv_tokens["prefill"] == {
            "real": n, "computed": padded if pads_prefill else n}


def test_phi4_has_no_entry():
    engine = _engine("phi4-mini-3.8b")
    engine.lower_decode()
    engine.lower_prefill(list(range(1, 24)))
    assert engine.wkv_tokens == {}
