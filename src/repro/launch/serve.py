"""Serving launcher: lazy-build a CIR for serving and drive the
slot-based continuous-batching engine with synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b -n 16

Scale-to-zero support: ``--snapshot-out PATH`` writes an
ASSEMBLED+COMPILED snapshot once the instance is READY; ``--restore PATH``
rebuilds from such a snapshot — resolution is a pin replay, the fetch is a
chunk delta against the local store, and the compile stage restores the
executable through the compile cache — instead of a full cold build.

Provenance: ``--sbom-out PATH`` emits the CycloneDX-shaped SBOM of the
resolved dependency closure (docs/cir-format.md §12, R-096) once the
instance is READY.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Mapping

import jax
import numpy as np

from ..configs import ARCHS
from ..core import (CompileCache, InstanceSnapshot, LazyBuilder, PreBuilder,
                    SPEC_LEASE_PREFIX, probe_host, restore_instance,
                    snapshot_instance, write_sbom)
from ..core import catalog
from .jax_cache import enable_compile_cache
from .mesh import _make_mesh, parse_mesh

# the plain variants a platform build's kernels are checked against
REFERENCE_KERNELS = {"attention": "naive", "wkv6": "sequential",
                     "rmsnorm": "xla"}


def build_serving(builder: LazyBuilder, cfg, mesh_shape=(1,),
                  mesh_axes=("data",), *, compile_steps: bool = False):
    """PreBuilder → ``LazyBuilder.build(probe_host(...))`` of a serving CIR
    for a mesh over the local devices.  Non-blocking: callers wait on the
    instance's lifecycle stages."""
    cir = PreBuilder(builder.service).prebuild(cfg, entrypoint="serve")
    spec = probe_host(mesh_shape=tuple(mesh_shape),
                      mesh_axes=tuple(mesh_axes))
    # the orchestrator overlaps assemble/compile with the weight-asset tail
    return builder.build(cir, spec, mesh=_make_mesh(mesh_shape, mesh_axes),
                         overrides={"workload": "decode"},
                         compile_steps=compile_steps, block=False)


def rebuild_with_kernels(builder: LazyBuilder, inst,
                         kernels: Mapping[str, str]):
    """``inst`` rebuilt from its own lock with the kernel variants named in
    ``kernels`` (kernel name → env, e.g. :data:`REFERENCE_KERNELS`) in place
    of the ones resolution picked: same CIR, platform and plan."""
    lock = inst.lock.repinned(builder.service, "kernel", kernels)
    return builder.build_from_lock(inst.cir, lock, inst.spec,
                                   mesh=inst.entry["plan"].mesh)


def init_params(inst, seed: int):
    """Random parameters from ``seed``, created by one jitted init straight
    into the serve plan's parameter shardings: no device ever holds more
    of the model than the plan gives it."""
    init = jax.jit(inst.model.init,
                   out_shardings=inst.entry["param_shardings"]())
    return init(jax.random.PRNGKey(seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b",
                    choices=sorted(ARCHS.keys()))
    ap.add_argument("-n", "--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default="1", type=parse_mesh,
                    help="local device mesh: N (data) or DxM (data x "
                         "model); feeds both probe_host and the mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--snapshot-out", metavar="PATH", default=None,
                    help="write an ASSEMBLED+COMPILED instance snapshot "
                         "once READY (restorable via --restore)")
    ap.add_argument("--restore", metavar="PATH", default=None,
                    help="restore a scaled-to-zero instance from a snapshot "
                         "instead of a full cold build")
    ap.add_argument("--sbom-out", metavar="PATH", default=None,
                    help="write the CycloneDX-shaped SBOM of the resolved "
                         "dependency closure once READY (docs §12, R-096)")
    ap.add_argument("--platform-report", action="store_true",
                    help="build with the §13 performance-portable split "
                         "(shared IR module + per-platform artifact tail + "
                         "autotune table) and print which of those "
                         "components were peer-shared vs locally built")
    ap.add_argument("--retire-spec", action="store_true",
                    help="after writing the snapshot, demote the instance's "
                         "content to the speculative eviction tier (a spec: "
                         "soft lease): it becomes the first thing capacity "
                         "pressure reclaims, and a restore promotes whatever "
                         "survived back to demand content")
    args = ap.parse_args(argv)
    if args.retire_spec and not args.snapshot_out:
        ap.error("--retire-spec requires --snapshot-out (retiring without "
                 "a snapshot would strand the instance)")

    print("compile cache:", enable_compile_cache())
    mesh_shape, mesh_axes = args.mesh

    svc = catalog.default_service()
    builder = LazyBuilder(svc, compile_cache=CompileCache(),
                          ir_components=args.platform_report)

    if args.restore:
        with open(args.restore) as f:
            snap = InstanceSnapshot.from_json(f.read())
        inst = restore_instance(snap, builder,
                                mesh=_make_mesh(mesh_shape, mesh_axes),
                                block=False)
        cfg = inst.cir.arch_config()
    else:
        cfg = ARCHS[args.arch]
        if not args.full:
            cfg = cfg.reduced()
        inst = build_serving(builder, cfg, mesh_shape, mesh_axes,
                             compile_steps=bool(args.snapshot_out
                                                or args.platform_report))
    cir = inst.cir
    inst.wait("ready")
    verb = "restored" if args.restore else "lazy-built"
    print(f"{verb} {cir.name} for {inst.spec.platform_id}; "
          f"deployable at {inst.report.critical_path_s * 1e3:.1f} ms "
          f"(stage={inst.stage}, CIR={cir.size_bytes()}B)")
    if args.sbom_out:
        sbom = builder.sbom(inst)
        write_sbom(args.sbom_out, sbom)
        print(f"SBOM written to {args.sbom_out} "
              f"({len(sbom['components'])} components)")
    if args.platform_report:
        inst.wait("complete")
        rep = inst.report

        def src(shared: int, built: int) -> str:
            if shared:
                return f"shared ({shared / 2**20:.1f} MiB from the fleet)"
            if built:
                return f"locally built ({built / 2**20:.1f} MiB published)"
            return "resident (no bytes moved)"

        print("platform report (docs §13 split, "
              f"compile_key={(inst.compile_key or '')[:16]}):")
        print(f"  ir module      {src(rep.ir_shared_bytes, rep.ir_bytes_published)}")
        print(f"  platform tail  "
              f"{src(rep.artifact_bytes_fetched, rep.artifact_bytes_published)}")
        print(f"  autotune table "
              f"{src(rep.autotune_bytes_fetched, rep.autotune_bytes_published)}")
    # first weight use: block until the asset tail has fully landed
    inst.wait("weights")
    print(f"weights landed; fetched={inst.report.bytes_fetched}B "
          f"(overlap {inst.report.overlap_s * 1e3:.1f} ms)")
    if args.snapshot_out:
        with open(args.snapshot_out, "w") as f:
            f.write(snapshot_instance(inst).to_json())
        print(f"snapshot written to {args.snapshot_out} "
              f"(stage={inst.stage}, compile_key="
              f"{(inst.compile_key or '')[:16]})")
        if args.retire_spec:
            # scale-to-zero retirement: the content stays resident but
            # drops to the speculative eviction tier — first victim under
            # pressure, promoted back on the next demand (restore) hit
            builder.store.acquire_build_lease(
                f"{SPEC_LEASE_PREFIX}retired:{cir.digest()[:16]}",
                list(inst.bundle.components()))
            print("instance content demoted to the speculative eviction "
                  "tier (evictable first; restore promotes it back)")

    params = init_params(inst, args.seed)
    engine = inst.entry["make_engine"](
        params, num_slots=args.slots, max_seq=args.max_seq,
        prefill_buckets=(32,))

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for i in range(args.requests):
        ln = int(rng.integers(4, 24))
        engine.submit(rng.integers(1, cfg.vocab, ln).tolist(),
                      max_new_tokens=args.max_new,
                      temperature=args.temperature)
    resp = engine.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in resp)
    print(f"{len(resp)} responses, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, {engine._ticks} engine ticks)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
