"""Kernel wiring: the kernel callables resolution picked are the ones the
model runs, each build with its own interpret mode."""
import dataclasses

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import LazyBuilder, PreBuilder, TPU_V5E, cpu_smoke
from repro.kernels import ops
from repro.launch.serve import (REFERENCE_KERNELS, build_serving,
                                init_params, rebuild_with_kernels)

PALLAS_INTERPRET = {"attention": "pallas-interpret",
                    "wkv6": "pallas-interpret"}


@pytest.mark.parametrize("arch_id,kernel", [
    ("rwkv6-1.6b", "wkv6_pallas"),
    ("phi4-mini-3.8b", "flash_attention"),
])
def test_pallas_interpret_build_runs_kernel_and_matches_reference(
        arch_id, kernel, service, monkeypatch):
    calls = []
    real = getattr(ops, kernel)

    def spy(*a, **kw):
        calls.append(kw["interpret"])
        return real(*a, **kw)

    monkeypatch.setattr(ops, kernel, spy)
    builder = LazyBuilder(service)
    inst = build_serving(builder, ARCHS[arch_id].reduced())
    inst.wait("ready")
    pallas = rebuild_with_kernels(builder, inst, PALLAS_INTERPRET)
    ref = rebuild_with_kernels(builder, inst, REFERENCE_KERNELS)
    params = init_params(inst, 0)

    # 11 tokens: not a multiple of the WKV6 chunk, so the padding runs too
    prompt = list(range(1, 12))
    logits = {}
    for name, build in (("pallas", pallas), ("ref", ref)):
        engine = build.entry["make_engine"](params, num_slots=1, max_seq=64,
                                            prefill_buckets=(16,))
        logits[name] = np.asarray(engine.prefill(prompt)[0])
        if name == "pallas":
            assert calls == [True], "the Pallas kernel was not traced"
    assert calls == [True], "the reference build called the Pallas kernel"
    np.testing.assert_allclose(logits["pallas"], logits["ref"],
                               atol=1e-4, rtol=1e-4)


def test_builds_in_one_process_keep_their_own_interpret_mode(service,
                                                            smoke_mesh):
    cfg = ARCHS["rwkv6-1.6b"].reduced()
    cir = PreBuilder(service).prebuild(cfg, entrypoint="serve")
    tpu = dataclasses.replace(cpu_smoke(), platform_id="tpu-described",
                              chip=TPU_V5E, backend="tpu",
                              interpret_kernels=False)
    builder = LazyBuilder(service)
    on_tpu = builder.build(cir, tpu)
    on_cpu = builder.build(cir, cpu_smoke(), mesh=smoke_mesh)
    on_cpu = rebuild_with_kernels(builder, on_cpu,
                                  {"wkv6": "pallas-interpret"})

    def wkv(inst):
        picked = inst.bundle.component("kernel", "wkv6").env
        return picked, inst.model.variants.wkv_impl.keywords["interpret"]

    assert wkv(on_tpu) == ("tpu-pallas", False)
    assert wkv(on_cpu) == ("pallas-interpret", True)
    # building the cpu instance left the tpu instance's mode alone
    assert wkv(on_tpu) == ("tpu-pallas", False)
