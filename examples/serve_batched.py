"""Batched serving example: lazy-build a serve container and drive the
slot-based continuous-batching engine with a bursty synthetic workload.

Run:  PYTHONPATH=src python examples/serve_batched.py [--arch gemma2-9b]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS
from repro.core import LazyBuilder, PreBuilder, probe_host
from repro.core import catalog
from repro.launch.mesh import make_smoke_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b",
                    choices=sorted(ARCHS.keys()))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].reduced()
    service = catalog.build_service()
    cir = PreBuilder(service).prebuild(cfg, entrypoint="serve")
    inst = LazyBuilder(service).build(
        cir, probe_host(mesh_shape=(1,), mesh_axes=("data",)),
        mesh=make_smoke_mesh(1), overrides={"workload": "decode"})
    print(f"lazy-built {cfg.arch_id} for serving "
          f"(plan={inst.bundle.context.get('plan.rules')})")

    params = inst.model.init(jax.random.PRNGKey(0))
    engine = inst.entry["make_engine"](
        params, num_slots=args.slots, max_seq=256, prefill_buckets=(32,))

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    # bursty arrivals: half up front, half mid-flight
    for _ in range(args.requests // 2):
        engine.submit(rng.integers(1, cfg.vocab,
                                   int(rng.integers(4, 28))).tolist(),
                      max_new_tokens=args.max_new)
    for _ in range(20):
        engine.tick()
    for _ in range(args.requests - args.requests // 2):
        engine.submit(rng.integers(1, cfg.vocab,
                                   int(rng.integers(4, 28))).tolist(),
                      max_new_tokens=args.max_new)
    responses = engine.run_until_drained()
    dt = time.perf_counter() - t0

    toks = sum(len(r.tokens) for r in responses)
    lat = sorted(r.queued_s + r.prefill_s + r.decode_s for r in responses)
    print(f"{len(responses)} responses, {toks} tokens, {dt:.1f}s wall "
          f"({toks/dt:.1f} tok/s, {engine._ticks} fused decode ticks)")
    print(f"latency p50={lat[len(lat)//2]*1e3:.0f}ms "
          f"p95={lat[int(len(lat)*0.95)]*1e3:.0f}ms")


if __name__ == "__main__":
    main()
