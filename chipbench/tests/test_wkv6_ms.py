"""``metrics/wkv6_ms.tput.py`` on a synthetic reduced trace: device ms of the
``wkv6`` kernel per decode run, from the decode's calls alone."""
from types import SimpleNamespace

import pytest

from chipbench import run as bench, trace


def _wkv(b, s, n=0):
    """HLO text of one ``wkv6`` call at batch ``b`` and length ``s``."""
    t = f"bf16[{b},32,{s},64]"
    return (f"%wkv6.{n} = ({t}, f32[{b},32,64,64]) custom-call({t} %r, "
            f"{t} %k, {t} %v, f32[{b},32,{s},64] %lw, bf16[32,1,64] %u, "
            f"f32[{b},32,64,64] %st), custom_call_target=\"tpu_custom_call\"")


def _device(calls, decode_runs):
    """A reduced device: ``calls`` maps HLO text to [seconds, count]."""
    kernels = {}
    if calls:
        kernels["wkv6"] = [sum(s for s, _ in calls.values()),
                           sum(n for _, n in calls.values()),
                           {repr(trace.shapes(h)): v
                            for h, v in calls.items()}]
    return {"programs": {"jit__decode_impl": [0.5, decode_runs],
                         "jit__prefill_impl": [0.1, 2]},
            "kernels": kernels}


def _run(*devices):
    return SimpleNamespace(trace={"window_s": 4.0, "devices": list(devices)})


@pytest.fixture
def read():
    return bench.reader("wkv6_ms.tput")


def test_decode_calls_per_decode_run(read):
    # 5 decode runs of 24 layers at 16 slots: 0.4 s of the kernel
    dev = _device({_wkv(16, 16): [0.4, 120]}, decode_runs=5)
    assert read(_run(dev)) == pytest.approx(80.0)


def test_prefill_shaped_calls_are_left_out(read):
    dev = _device({_wkv(16, 16): [0.4, 120],
                   _wkv(1, 16, 1): [0.02, 24],       # a 16-token prompt
                   _wkv(1, 192, 2): [0.05, 24]}, decode_runs=5)
    assert read(_run(dev)) == pytest.approx(80.0)
    # averaged over the chips, per decode run of the first
    other = _device({_wkv(16, 16): [0.5, 120]}, decode_runs=5)
    assert read(_run(dev, other)) == pytest.approx(90.0)


def test_none_without_decode_calls(read):
    assert read(_run(_device({}, decode_runs=5))) is None
    prefill_only = _device({_wkv(1, 64): [0.02, 24]}, decode_runs=5)
    assert read(_run(prefill_only)) is None
    assert read(_run(_device({_wkv(16, 16): [0.4, 120]}, 0))) is None
    assert read(SimpleNamespace(trace=None)) is None
