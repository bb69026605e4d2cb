#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, at full published width.

  python chip_smoke.py [--seed N]        # one chip
  python chip_smoke.py --chips 4         # the four-chip path only

One chip: each Pallas kernel at a served model's widths against its plain
reference; then ``rwkv6-1.6b`` (the WKV6 kernel), then ``phi4-mini-3.8b``
(flash attention prefill, the fused RMSNorm and the KV cache).  Each model
phase builds through the normal entry points (``PreBuilder`` →
``LazyBuilder.build(probe_host(...))`` → ``make_engine``, via
``repro.launch.serve``), answers greedy requests made from ``--seed``, and
checks the chip's answer: the
prefill logits of the Pallas build against a rebuild of the same lock with
the reference kernels (naive attention, sequential WKV6, XLA RMSNorm) on the
same parameters, the first greedy tokens, and a ``tpu_custom_call`` in the
compiled prefill.

Four chips: ``codeqwen1.5-7b`` (32 query heads over 4 kv heads) cut to 8
layers, prefilled on one chip and on a (1, 4) ("data", "model") mesh from
the same seed, logits compared; then the full-depth model serves requests on
the four chips, each holding 8 query heads and 1 kv head of every layer
(about 3.63 GB of its 14.5 GB of bf16 weights), and the bytes in use on
every device show that it is spread.

The labelled lines are one smoke run, not metrics.  The last line is
``{"ok": true, "device": {...}}``.  Any failed check raises, so the exit
code is non-zero; so is it when JAX finds no TPU.  One process holds the
chip and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the program's package, importable once src/ is on the path
from repro.configs import ARCHS
from repro.core import CompileCache, LazyBuilder, catalog
from repro.kernels import pallas_attention, pallas_rmsnorm, pallas_wkv6
from repro.launch.jax_cache import enable_compile_cache
from repro.launch.serve import (REFERENCE_KERNELS, build_serving,
                                init_params, rebuild_with_kernels)
from repro.models.attention import naive_attention
from repro.models.common import rms_norm
from repro.models.ssm import wkv6_sequential

# Limits, as shares of the reference's largest magnitude.  A kernel's bf16
# output is rounded to 8 significant bits (2**-8 = 0.0039): one kernel call
# against its reference on the same inputs may differ by a few such steps.
KERNEL_RTOL = 1e-2
# Whole-model logits: bf16 rounding differences grow through 24-32 random
# layers.  On a TPU v5e two XLA builds that differ only in summation order
# (chunked-lax vs sequential WKV6, xla-flash vs naive attention) differ by
# 0.080-0.095 of the largest reference logit at these widths, so the Pallas
# build may differ by up to this much; the kernel check above is the tight
# one, and the greedy tokens must agree.
LOGIT_RTOL = 0.15
PROMPT_LENS = (64, 50)          # one aligned, one padded length
PREFILL_BUCKET = 64
MAX_SEQ = 128


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(tag: str, msg: str) -> None:
    print(f"[smoke {tag}] {msg}", flush=True)


def prompts_for(cfg, seed: int, n: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, PROMPT_LENS[i % len(PROMPT_LENS)])
            .tolist() for i in range(n)]


def memory(devices) -> list:
    """(bytes_in_use, peak_bytes_in_use) per device, None where the backend
    reports no memory stats."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append((st.get("bytes_in_use"), st.get("peak_bytes_in_use")))
    return out


def rel_err(got, ref) -> float:
    """max |got - ref| / max |ref|, after checking shapes and finiteness."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"shapes {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), "non-finite values")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6))


def compare_logits(got, ref, served: int) -> dict:
    """``got`` against ``ref`` (one position's logits) and the greedy token
    ``served`` from ``got``'s build.  The token agrees when it is ``ref``'s
    greedy token, or when ``ref``'s best logit leads it by no more than the
    two builds differ anywhere: a tie the tolerance cannot order."""
    ref = np.asarray(ref, np.float32)
    err = rel_err(got, ref)
    want = int(ref.argmax())
    return {"rel_err": err, "tokens": (served, want),
            "agrees": served == want or float(ref[want] - ref[served])
            <= err * float(np.abs(ref).max())}


def kernel_phase(seed: int) -> None:
    """Each Pallas kernel, compiled for the chip at a served model's widths,
    against its plain reference on the same random bf16 inputs (reference
    matmuls at the highest precision)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 10))

    def normal(*shape, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale
                ).astype(jnp.bfloat16)

    # rwkv6-1.6b: 32 heads of 64; 50 tokens, padded to the 64-token chunk
    rkv = [normal(1, 32, 50, 64) for _ in range(3)]
    log_decay = -jnp.exp(normal(1, 32, 50, 64).astype(jnp.float32) - 1.0)
    wkv = rkv + [log_decay, normal(32, 64, scale=0.1)]
    # phi4-mini-3.8b: 24 query heads on 8 kv heads of 128; one 64 bucket
    qkv = [normal(1, 24, 64, 128), normal(1, 8, 64, 128),
           normal(1, 8, 64, 128)]
    scale = 128 ** -0.5
    cases = {
        "wkv6 (y, state) at rwkv6-1.6b widths": (
            functools.partial(pallas_wkv6, interpret=False),
            wkv6_sequential, wkv),
        "flash attention at phi4-mini-3.8b heads": (
            functools.partial(pallas_attention, scale=scale,
                              interpret=False),
            functools.partial(naive_attention, scale=scale), qkv),
        "rmsnorm at phi4-mini-3.8b width": (
            functools.partial(pallas_rmsnorm, interpret=False), rms_norm,
            [normal(1, 64, 3072), normal(3072)]),
    }
    for name, (fn, ref, args) in cases.items():
        got = jax.jit(fn)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        errs = [rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                              jax.tree.leaves(want))]
        say("kernels", f"{name} vs its reference: max |diff| / max |ref| "
                       f"= {errs} (limit {KERNEL_RTOL})")
        check(max(errs) <= KERNEL_RTOL, f"{name} differs from its reference")


def build(builder, cfg, mesh_shape, mesh_axes, seed):
    """Lazy-build a serving instance and create its parameters."""
    inst = build_serving(builder, cfg, mesh_shape, mesh_axes)
    inst.wait("weights")
    params = init_params(inst, seed)
    jax.block_until_ready(params)
    return inst, params


def kernel_picks(inst) -> dict:
    """Kernel name → the env variant the build's lock pins."""
    return {c.name: c.env for c in inst.bundle.components()
            if c.manager == "kernel"}


def new_builder():
    return LazyBuilder(catalog.default_service(), compile_cache=CompileCache())


def serve_phase(tag: str, cfg, *, seed: int, n_requests: int = 4,
                max_new: int = 16) -> dict:
    """Build ``cfg`` for one chip, serve greedy requests, check them against
    the reference-kernel build.  Returns what it observed."""
    builder = new_builder()
    t0 = time.perf_counter()
    inst, params = build(builder, cfg, (1,), ("data",), seed)
    out = {"build_and_init_s": time.perf_counter() - t0,
           "picks": kernel_picks(inst),
           "interpret": inst.spec.interpret_kernels}
    say(tag, f"picks {out['picks']}; interpret mode {out['interpret']}")

    engine = inst.entry["make_engine"](
        params, num_slots=n_requests, max_seq=MAX_SEQ,
        prefill_buckets=(PREFILL_BUCKET,))
    prompts = prompts_for(cfg, seed, n_requests)
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    cold = sorted(engine.run_until_drained(), key=lambda r: r.rid)
    check(len(cold) == n_requests
          and all(len(r.tokens) == max_new for r in cold),
          f"served {[len(r.tokens) for r in cold]} tokens, "
          f"wanted {max_new} x {n_requests}")
    # request 0 is admitted first: its prefill time is the time to its first
    # token on the host, prefill compile included
    out["cold_ttft_s"] = cold[0].prefill_s
    say(tag, f"cold time to first token (compile included) "
             f"{out['cold_ttft_s']:.3f} s")

    # warm pass over the same prompts: greedy decoding is deterministic,
    # and the decode rate is timed once every slot is admitted
    engine.finished.clear()
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    engine.tick()
    t0, n_tok = time.perf_counter(), 0
    while engine.queue or any(r is not None for r in engine.slot_req):
        n_tok += engine.tick()
    out["decode_tok_s"] = n_tok / (time.perf_counter() - t0)
    warm = sorted(engine.finished, key=lambda r: r.rid)
    check([r.tokens for r in warm] == [r.tokens for r in cold],
          "warm greedy tokens differ from the cold run's")
    say(tag, f"decode after warm-up: {out['decode_tok_s']:.1f} tokens/s "
             f"({n_requests} slots, {n_tok} tokens)")

    # the chip's answer against the reference kernels on the same params
    ref = rebuild_with_kernels(builder, inst, REFERENCE_KERNELS)
    out["reference_picks"] = kernel_picks(ref)
    check(all(out["reference_picks"].get(k, v) == v
              for k, v in REFERENCE_KERNELS.items()),
          f"reference build picked {out['reference_picks']}")
    ref_engine = ref.entry["make_engine"](
        params, num_slots=1, max_seq=MAX_SEQ,
        prefill_buckets=(PREFILL_BUCKET,))
    cmp = [compare_logits(engine.prefill(p)[0][0],
                          ref_engine.prefill(p)[0][0], cold[i].tokens[0])
           for i, p in enumerate(prompts[:len(PROMPT_LENS)])]
    out["logits_rel_err"] = max(c["rel_err"] for c in cmp)
    say(tag, f"prefill logits vs reference kernels {out['reference_picks']}:"
             f" max |diff| / max |ref| = {[c['rel_err'] for c in cmp]} "
             f"(limit {LOGIT_RTOL}); first greedy tokens (served, reference)"
             f" {[c['tokens'] for c in cmp]}")
    check(out["logits_rel_err"] <= LOGIT_RTOL,
          f"logits differ from the reference by {out['logits_rel_err']}")
    check(all(c["agrees"] for c in cmp),
          f"first greedy tokens differ from the reference: {cmp}")

    t0 = time.perf_counter()
    out["custom_calls"] = {
        "prefill": engine.lower_prefill(prompts[0]).compile().as_text()
        .count("tpu_custom_call"),
        "decode": engine.lower_decode().compile().as_text()
        .count("tpu_custom_call")}
    say(tag, f"tpu_custom_call in compiled prefill / decode: "
             f"{out['custom_calls']['prefill']} / "
             f"{out['custom_calls']['decode']} "
             f"({time.perf_counter() - t0:.1f} s to re-lower and compile)")

    if "attention" in out["picks"]:
        say(tag, "decode attends over the KV cache with the plain XLA path, "
                 "not the flash kernel, by design (the kernel computes a "
                 "fresh causal chunk; a chunk continuing a filled cache "
                 "takes lax flash attention)")
    out["memory"] = memory(jax.devices()[:1])
    say(tag, f"device 0 bytes_in_use / peak_bytes_in_use: {out['memory'][0]}")
    del engine, ref_engine, params
    gc.collect()
    return out


def sharded_phase(cfg, *, seed: int, cut_layers: int = 8,
                  n_requests: int = 4, max_new: int = 16) -> dict:
    """``cfg`` cut to ``cut_layers`` on one chip and on a (1, 4) mesh from
    the same seed, prefill logits compared; then the full depth serves on
    the (1, 4) mesh.  Returns what it observed."""
    out = {}
    cut = dataclasses.replace(cfg, num_layers=cut_layers)
    prompts = prompts_for(cfg, seed, n_requests)[:len(PROMPT_LENS)]
    logits = {}
    for shape, axes in (((1,), ("data",)), ((1, 4), ("data", "model"))):
        inst, params = build(new_builder(), cut, shape, axes, seed)
        engine = inst.entry["make_engine"](
            params, num_slots=1, max_seq=MAX_SEQ,
            prefill_buckets=(PREFILL_BUCKET,))
        logits[shape] = [engine.prefill(p)[0][0] for p in prompts]
        say("sharded", f"{cut_layers}-layer {cfg.arch_id} on mesh {shape}: "
                       f"picks {inst.bundle.context['attn.impl']}"
                       f" attention, interpret {inst.spec.interpret_kernels}")
        del engine, params
        gc.collect()
    cmp = [compare_logits(a, b, int(a.argmax()))
           for a, b in zip(logits[(1, 4)], logits[(1,)])]
    out["cut_logits_rel_err"] = max(c["rel_err"] for c in cmp)
    say("sharded", f"(1, 4) vs one chip, prefill logits: max |diff| / "
                   f"max |ref| = {[c['rel_err'] for c in cmp]} (limit "
                   f"{LOGIT_RTOL}); greedy tokens (four chips, one chip) "
                   f"{[c['tokens'] for c in cmp]}")
    check(out["cut_logits_rel_err"] <= LOGIT_RTOL
          and all(c["agrees"] for c in cmp),
          f"four-chip logits differ from the one-chip run: {cmp}")

    inst, params = build(new_builder(), cfg, (1, 4), ("data", "model"),
                         seed)
    out["param_bytes_per_device"] = memory(jax.devices())
    say("sharded", f"full {cfg.num_layers}-layer {cfg.arch_id}: bytes_in_use"
                   f" per device after init {out['param_bytes_per_device']}")
    engine = inst.entry["make_engine"](
        params, num_slots=n_requests, max_seq=MAX_SEQ,
        prefill_buckets=(PREFILL_BUCKET,))
    t0 = time.perf_counter()
    for p in prompts_for(cfg, seed, n_requests):
        engine.submit(p, max_new_tokens=max_new)
    resps = engine.run_until_drained()
    check(len(resps) == n_requests
          and all(len(r.tokens) == max_new for r in resps),
          "four-chip serving did not answer every request")
    out["memory"] = memory(jax.devices())
    say("sharded", f"served {n_requests} x {max_new} tokens in "
                   f"{time.perf_counter() - t0:.1f} s (compile included); "
                   f"bytes_in_use / peak per device {out['memory']}")
    out["prefill_custom_calls"] = engine.lower_prefill(
        prompts[0]).compile().as_text().count("tpu_custom_call")
    say("sharded", f"picks {inst.bundle.context['attn.impl']} attention, "
                   f"interpret {inst.spec.interpret_kernels}; "
                   f"tpu_custom_call in the compiled four-chip prefill: "
                   f"{out['prefill_custom_calls']}")
    check(inst.bundle.context["attn.impl"] == "pallas"
          and not inst.spec.interpret_kernels
          and out["prefill_custom_calls"] > 0,
          "the four-chip prefill does not run the compiled Pallas kernels")
    used = [m[0] for m in out["param_bytes_per_device"]]
    if all(u is not None for u in used):
        check(max(used) < 0.5 * sum(used),
              f"parameters are not spread over the devices: {used}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip path and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)


    cache_dir = enable_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, jax backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    devices = jax.devices()
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but {len(devices)} devices present")
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say("setup", f"{len(devices)} x {devices[0].device_kind}; "
                 f"jax {jax.__version__}; compile cache {cache_dir} "
                 f"({warm} entries at start: 'cold' times below include "
                 f"compiles only where the cache misses)")

    if args.chips == 4:
        sharded_phase(ARCHS["codeqwen1.5-7b"], seed=args.seed)
    else:
        kernel_phase(args.seed)
        pallas = {"attention": "tpu-pallas", "wkv6": "tpu-pallas",
                  "rmsnorm": "fused-pallas"}
        for arch in ("rwkv6-1.6b", "phi4-mini-3.8b"):
            out = serve_phase(arch, ARCHS[arch], seed=args.seed)
            check(out["interpret"] is False, "kernels run in interpret mode")
            check(all(pallas[k] == v for k, v in out["picks"].items()
                      if k in pallas),
                  f"a kernel fell back from Pallas: {out['picks']}")
            check(out["custom_calls"]["prefill"] > 0,
                  "no tpu_custom_call in the compiled prefill")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
