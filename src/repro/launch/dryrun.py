"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input-shape × mesh) cell:
  pre-build the arch's CIR → lazy-build it for the TPU-pod specSheet
  (the paper's deployment-time path, with workload overrides) →
  ``jax.jit(step, in_shardings=…).lower(*input_specs(...)).compile()`` →
  print ``memory_analysis()`` + ``cost_analysis()`` and persist the parsed
  HLO stats (FLOPs / HBM bytes / collective bytes, while-corrected) to
  ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` for §Roofline.

NOTE: jit's in_shardings rejects kwargs, so the lowering is positional —
``input_specs()`` returns an ordered dict and we lower ``*specs.values()``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--quiet]
"""
import argparse
import json
import os
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs import ARCHS
from ..core import PreBuilder, LazyBuilder, tpu_multi_pod, tpu_single_pod
from ..core import catalog
from .hlo_stats import module_cost, xla_cost_analysis
from .mesh import (SHAPES, ShapeSpec, applicable, build_overrides,
                   make_production_mesh)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(cfg, shape: ShapeSpec, entry: Dict[str, Any]
                ) -> Dict[str, Any]:
    """Ordered kwargs-dict of ShapeDtypeStructs for the cell's step fn."""
    B, S = shape.global_batch, shape.seq_len
    i32, f32 = jnp.int32, jnp.float32
    dt = jnp.dtype(cfg.dtype)

    def pos_struct(b, s):
        if cfg.mrope_sections:
            return jax.ShapeDtypeStruct((3, b, s), i32)
        return jax.ShapeDtypeStruct((b, s), i32)

    if shape.kind == "train":
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
            "positions": pos_struct(B, S),
            "mask": jax.ShapeDtypeStruct((B, S), f32),
        }
        if cfg.family == "audio-lm":
            batch["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), f32)
        if cfg.family == "vlm-lm":
            batch["vis_embeds"] = jax.ShapeDtypeStruct(
                (B, min(64, S), cfg.d_model), f32)
        state = jax.eval_shape(lambda: entry["init_state"](
            jax.random.PRNGKey(0)))
        return {"state": state, "batch": batch}

    model = entry["_model"]
    params = model.param_shapes()
    cache = jax.eval_shape(
        lambda: model.init_cache(B, S))
    if shape.kind == "prefill":
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "positions": pos_struct(B, S),
        }
        if cfg.family == "audio-lm":
            batch["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), f32)
        if cfg.family == "vlm-lm":
            batch["vis_embeds"] = jax.ShapeDtypeStruct(
                (B, min(64, S), cfg.d_model), f32)
        return {"params": params, "batch": batch, "cache": cache}

    # decode: one new token with a seq_len-deep cache
    return {
        "params": params,
        "tokens": jax.ShapeDtypeStruct((B, 1), i32),
        "positions": pos_struct(B, 1),
        "cache": cache,
        "cache_pos": jax.ShapeDtypeStruct((), i32),
    }


def _shardings_for(cfg, shape: ShapeSpec, entry, specs, plan
                   ) -> Tuple[Any, ...]:
    from ..core.catalog import make_batch_shardings, make_state_shardings
    from jax.sharding import NamedSharding, PartitionSpec

    repl = NamedSharding(plan.mesh, PartitionSpec())
    if shape.kind == "train":
        st = entry["state_shardings"]()
        b = entry["batch_shardings"](specs["batch"])
        return (st, b)
    psh = entry["param_shardings"]()
    csh = entry["cache_shardings"](shape.global_batch, shape.seq_len)
    if shape.kind == "prefill":
        bsh = entry["batch_shardings"](specs["batch"])
        return (psh, bsh, csh)
    tok_sh = entry["batch_shardings"](
        {"tokens": specs["tokens"], "positions": specs["positions"]})
    return (psh, tok_sh["tokens"], tok_sh["positions"], csh, repl)


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch_id: str, shape_name: str, multi_pod: bool = False,
             quiet: bool = False, save: bool = True,
             overrides: Optional[Dict[str, Any]] = None,
             mesh=None, tag: str = "") -> Dict[str, Any]:
    cfg = ARCHS[arch_id]
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_name, "skipped": why}

    spec = tpu_multi_pod() if multi_pod else tpu_single_pod()
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)

    svc = catalog.default_service()
    pb = PreBuilder(svc)
    lb = LazyBuilder(svc)
    entrypoint = "train" if shape.kind == "train" else "serve"
    cir = pb.prebuild(cfg, entrypoint=entrypoint)
    ov = dict(build_overrides(cfg, shape, spec))
    ov.update(overrides or {})

    t0 = time.perf_counter()
    inst = lb.build(cir, spec, mesh=mesh, overrides=ov)
    entry = dict(inst.entry)
    entry["_model"] = inst.model
    build_s = time.perf_counter() - t0

    specs = input_specs(cfg, shape, entry)
    shardings = _shardings_for(cfg, shape, entry, specs, entry["plan"])

    if shape.kind == "train":
        fn = entry["train_step"]
        donate = (0,)
    elif shape.kind == "prefill":
        fn = entry["prefill"]
        donate = (2,)
    else:
        fn = entry["decode_step"]
        donate = (3,)

    t0 = time.perf_counter()
    with mesh:
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*specs.values())
        lower_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    ca = xla_cost_analysis(compiled)
    txt = compiled.as_text()
    hlo = module_cost(txt)

    result = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": spec.num_chips,
        "overrides": {k: v for k, v in ov.items()},
        "variant_picks": {f"{c.manager}:{c.name}": c.env
                          for c in inst.bundle.components()},
        "build_s": round(build_s, 3),
        "lower_s": round(lower_s, 3), "compile_s": round(compile_s, 3),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes),
        },
        "xla_cost_analysis": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "hlo_stats": {
            "flops_per_device": hlo.flops,
            "hbm_bytes_per_device": hlo.hbm_bytes,
            "collective_bytes_per_device": hlo.collective_bytes,
            "by_collective": hlo.by_collective,
            "n_dots": hlo.dots, "n_collectives": hlo.collectives,
        },
        "hlo_chars": len(txt),
    }
    if not quiet:
        print(f"== {arch_id} × {shape_name} × {result['mesh']} "
              f"(compile {compile_s:.1f}s)")
        print(f"   memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f} "
              f"GiB  temp={ma.temp_size_in_bytes/2**30:.2f} GiB  "
              f"out={ma.output_size_in_bytes/2**30:.2f} GiB  per device")
        print(f"   cost_analysis:   flops={ca.get('flops', 0):.3e}  "
              f"bytes={ca.get('bytes accessed', 0):.3e} (scan bodies x1)")
        print(f"   hlo_stats:       flops={hlo.flops:.3e}  "
              f"hbm={hlo.hbm_bytes:.3e}  coll={hlo.collective_bytes:.3e} "
              f"B/device  {hlo.by_collective}")
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        base = f"{arch_id}__{shape_name}__{result['mesh']}{suffix}"
        with open(os.path.join(ARTIFACT_DIR, base + ".json"), "w") as f:
            json.dump(result, f, indent=1)
        # keep the compiled HLO so stats can be re-derived without
        # recompiling (parser iterations, per-op profiles)
        import gzip
        with gzip.open(os.path.join(ARTIFACT_DIR, base + ".hlo.gz"),
                       "wt") as f:
            f.write(txt)
    return result


def reparse_artifacts(pattern: str = "*") -> int:
    """Re-derive hlo_stats for every saved artifact from its stored HLO
    (used after hlo_stats refinements; no recompilation)."""
    import glob
    import gzip
    n = 0
    for fn in sorted(glob.glob(os.path.join(ARTIFACT_DIR,
                                            pattern + ".json"))):
        hlo_fn = fn[:-5] + ".hlo.gz"
        if not os.path.exists(hlo_fn):
            continue
        with gzip.open(hlo_fn, "rt") as f:
            txt = f.read()
        hlo = module_cost(txt)
        with open(fn) as f:
            result = json.load(f)
        result["hlo_stats"] = {
            "flops_per_device": hlo.flops,
            "hbm_bytes_per_device": hlo.hbm_bytes,
            "collective_bytes_per_device": hlo.collective_bytes,
            "by_collective": hlo.by_collective,
            "n_dots": hlo.dots, "n_collectives": hlo.collectives,
        }
        with open(fn, "w") as f:
            json.dump(result, f, indent=1)
        n += 1
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    # 512 virtual host devices back the production meshes; the flag must
    # be set before the first call initialises a jax backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    cells = []
    if args.all:
        for aid in ARCHS:
            for sname in SHAPES:
                cells.append((aid, sname))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    failures = 0
    for aid, sname in cells:
        try:
            r = run_cell(aid, sname, multi_pod=args.multi_pod,
                         quiet=args.quiet, mesh=mesh)
            if "skipped" in r:
                print(f"-- {aid} × {sname}: SKIP ({r['skipped']})")
        except Exception:
            failures += 1
            print(f"!! {aid} × {sname} FAILED", file=sys.stderr)
            traceback.print_exc()
    print(f"done; {failures} failures / {len(cells)} cells")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
