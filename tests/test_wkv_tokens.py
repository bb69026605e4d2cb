"""The tokens each of the engine's programs hands one layer's WKV call, and
the length the Pallas wrapper pads them to, read by spies on the WKV
implementation while the program is traced (lowering is enough)."""
import dataclasses
import functools

import pytest

from repro.configs import ARCHS
from repro.kernels import ops, pallas_wkv6
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


def _spy(calls, name, real):
    """``real``, recording (name, batch, tokens) of every call."""
    def spy(r, *a, **kw):
        calls.append((name, r.shape[0], r.shape[2]))
        return real(r, *a, **kw)
    return spy


@pytest.mark.parametrize("pallas", [True, False],
                         ids=["pallas", "chunked-lax"])
def test_rwkv6_decode_and_prefill(pallas, monkeypatch):
    calls = []
    if pallas:      # the chip's: the spies sit below the wrapper's padding
        for kernel in ("wkv6_step_pallas", "wkv6_pallas"):
            monkeypatch.setattr(ops, kernel,
                                _spy(calls, kernel, getattr(ops, kernel)))
        impl = functools.partial(pallas_wkv6, interpret=True)
    else:
        impl = _spy(calls, "chunked-lax", wkv6_chunked)
    engine = _engine("rwkv6-1.6b", wkv_impl=impl)
    engine.lower_decode()
    # one token per slot and layer, handed over as one: nothing is padded
    step = "wkv6_step_pallas" if pallas else "chunked-lax"
    assert set(calls) == {(step, SLOTS, 1)}
    # a recurrent model prefills at the prompt's exact length; the Pallas
    # wrapper pads to 16 below its 64-token chunk, to the chunk above
    for n, padded in ((23, 32), (100, 128)):
        calls.clear()
        engine.lower_prefill(list(range(1, n + 1)))
        assert set(calls) == ({("wkv6_pallas", 1, padded)} if pallas
                              else {("chunked-lax", 1, n)})


def test_phi4_has_no_entry():
    calls = []
    engine = _engine("phi4-mini-3.8b",
                     wkv_impl=_spy(calls, "chunked-lax", wkv6_chunked))
    engine.lower_decode()
    engine.lower_prefill(list(range(1, 24)))
    assert calls == []
