"""End-to-end training launcher.

On the CPU container this drives a reduced config (``--reduced``, default);
the same code path lowers the full configs on the production mesh (that is
what ``dryrun.py`` proves).  The flow is the paper's: pre-build a CIR →
lazy-build it for the probed platform → run the assembled container under
the fault-tolerant driver.

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --steps 50
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp

from ..configs import ARCHS
from ..core import LazyBuilder, PreBuilder, probe_host
from ..core import catalog
from ..runtime import RuntimeConfig, TrainDriver
from .jax_cache import enable_compile_cache
from .mesh import make_smoke_mesh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b",
                    choices=sorted(ARCHS.keys()))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="use the full (paper-size) config — needs real HW")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print("compile cache:", enable_compile_cache())

    cfg = ARCHS[args.arch]
    if not args.full:
        cfg = cfg.reduced()

    svc = catalog.default_service()
    cir = PreBuilder(svc).prebuild(cfg, entrypoint="train", seed=args.seed)
    print(f"CIR {cir.name} ({cir.size_bytes()} bytes on the wire)")

    spec = probe_host(mesh_shape=(1,), mesh_axes=("data",))
    mesh = make_smoke_mesh(1)
    inst = LazyBuilder(svc).build(
        cir, spec, mesh=mesh,
        overrides={"lr": args.lr, "total_steps": args.steps,
                   "warmup": max(args.steps // 10, 5)})
    print("lazy-built for", spec.platform_id, "| picks:",
          {c.name: c.env for c in inst.bundle.components()
           if c.manager in ("kernel", "parallel", "opt")})

    e = inst.entry
    step_fn = jax.jit(e["train_step"], donate_argnums=(0,))

    def batch_fn(step):
        b = e["batch_fn"](args.seq, args.batch, step=step, seed=args.seed)
        return {k: jnp.asarray(v) for k, v in b.items()}

    driver = TrainDriver(
        train_step=step_fn,
        init_state=lambda: e["init_state"](jax.random.PRNGKey(args.seed)),
        batch_fn=batch_fn,
        ckpt_dir=os.path.join(args.ckpt_dir, cfg.arch_id),
        cfg=RuntimeConfig(total_steps=args.steps,
                          checkpoint_every=args.checkpoint_every))
    t0 = time.perf_counter()
    res = driver.run()
    dt = time.perf_counter() - t0
    k = max(1, len(res.losses) // 10)
    print(f"steps={res.steps_done} wall={dt:.1f}s "
          f"loss {sum(res.losses[:k])/k:.4f} -> {sum(res.losses[-k:])/k:.4f} "
          f"restarts={res.restarts} stragglers={res.straggler_events}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
