"""The built-in component catalog — the Uniform Component Registry content.

This is the analog of the paper's converted-package registry (§4.3): every
module of this framework is published as an immutable uniform component
``(M, n, v, e)`` with metadata deps ``D``, context contribution ``C``, and
environment requirements, so the lazy-builder can assemble a platform-
fitted container from a CIR's *direct* dependency declarations only.

Managers (the environment-manager analogs):
  model    — model-family assemblers (decoder-dense/-moe/-rwkv/-hybrid/...)
  kernel   — compute kernels: attention / moe-dispatch / wkv6 / ssm-scan /
             rmsnorm, each with env variants (tpu-pallas vs xla vs naive)
  parallel — sharding plans (tp / fsdp-tp / sp-decode / pipeline)
  runtime  — step builders (train-step / serve-step / request-batcher)
  opt      — optimizer (adamw, moment-precision env variants)
  data     — input pipelines
  env      — the interpreter/runtime analogs (os-base, runtime-base)
  asset    — weights + frontend stubs (virtual bytes, never materialized)

Wire sizes: code components carry their true source size; ``env`` and
``asset`` components carry documented real-world artifact sizes (jaxlib /
libtpu / CUDA wheel sizes; 2 bytes/param for bf16 weights) — these drive
the image-size / bandwidth benchmarks exactly like the paper's packages.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from ..configs import ARCHS
from ..configs.base import ArchConfig, FAMILY_MODEL_COMPONENT
from .component import DependencyItem as D
from .component import Requirement as R
from .component import UniformComponent as C
from .lazybuild import register_payload
from .registry import (UniformComponentRegistry, UniformComponentService,
                       UpstreamSource)
from .resolution import register_context_spec_hook


def _src_size(module) -> int:
    """True source bytes of a python module — the converted-code wire size."""
    try:
        import inspect
        return len(inspect.getsource(module).encode())
    except Exception:
        return 16 * 1024


# Documented real-world artifact sizes (bytes) for the env components:
#   cpu   : jaxlib-cpu wheel ≈ 120 MB
#   tpu   : jaxlib + libtpu ≈ 450 MB
#   gpu   : jaxlib + cuda12 + cudnn wheels ≈ 2.3 GB (torch-cu12 class)
_RUNTIME_BASE_SIZES = {
    "cpu-host": 120 * 2**20,
    "tpu-v5e": 450 * 2**20,
    "gpu-a100": 2300 * 2**20,
}
_OS_BASE_SIZE = 80 * 2**20          # debian-slim base layer analog
_FRONTEND_SIZES = {                  # bf16 param bytes of the real frontends
    "audio-frames": int(60e6) * 2,   # EnCodec-class audio encoder
    "vision-patches": int(675e6) * 2,  # Qwen2-VL ViT-class vision tower
}


# ===========================================================================
# Payloads — the executable bodies the converter produced
# ===========================================================================

@register_payload("model.decoder")
def _build_decoder(cfg: ArchConfig, context: Mapping[str, Any], bundle):
    """Model-family assembler: composes the model from the kernel callables
    Algorithm 1 selected (each kernel payload binds this build's interpret
    mode from the building context)."""
    from ..models import Variants, build_model

    def kernel(name: str):
        if not bundle.has("kernel", name):
            return None
        return bundle.payload("kernel", name)(context)

    picked = {"attn_kernel": kernel("attention"),
              "wkv_impl": kernel("wkv6"),
              "rms_norm": kernel("rmsnorm")}
    v = Variants(
        moe_impl=context.get("moe.impl", "grouped"),
        remat=context.get("remat", "full"),
        capacity_factor=float(context.get("moe.capacity", 1.25)),
        moe_combine=context.get("moe.combine", "f32"),
        moe_slot_dp=bool(context.get("moe.slot_dp", False)),
        **{k: fn for k, fn in picked.items() if fn is not None},
    )
    return build_model(cfg, v)


# -- kernels: each payload takes the building context and returns the
# -- callable the model runs (Pallas ones bound to the build's interpret mode)

def _interpret(context: Mapping[str, Any]) -> bool:
    return bool(context["interpret"])


@register_payload("kernel.attention.naive")
def _k_attn_naive(context):
    from ..models.attention import naive_attention
    return naive_attention


@register_payload("kernel.attention.xla_flash")
def _k_attn_xla(context):
    from ..models.attention import lax_flash_attention
    return lax_flash_attention


@register_payload("kernel.attention.pallas")
def _k_attn_pallas(context):
    from ..kernels import pallas_attention
    return functools.partial(pallas_attention,
                             interpret=_interpret(context))


@register_payload("kernel.wkv6.sequential")
def _k_wkv_seq(context):
    from ..models.ssm import wkv6_sequential
    return wkv6_sequential


@register_payload("kernel.wkv6.chunked")
def _k_wkv_chunk(context):
    from ..models.ssm import wkv6_chunked
    return wkv6_chunked


@register_payload("kernel.wkv6.pallas")
def _k_wkv_pallas(context):
    from ..kernels import pallas_wkv6
    return functools.partial(pallas_wkv6, interpret=_interpret(context))


@register_payload("kernel.moe.grouped")
def _k_moe_grouped(context):
    from ..models.ffn import moe_grouped
    return moe_grouped


@register_payload("kernel.moe.dense")
def _k_moe_dense(context):
    from ..models.ffn import moe_dense
    return moe_dense


@register_payload("kernel.ssm_scan.lax")
def _k_ssm(context):
    from ..models.ssm import mamba_block
    return mamba_block


@register_payload("kernel.rmsnorm.xla")
def _k_rms_xla(context):
    from ..models.common import rms_norm
    return rms_norm


@register_payload("kernel.rmsnorm.pallas")
def _k_rms_pallas(context):
    from ..kernels import pallas_rmsnorm
    return functools.partial(pallas_rmsnorm, interpret=_interpret(context))


# -- parallel plans ----------------------------------------------------------

@register_payload("parallel.pipeline")
def _pipeline_combinator():
    from ..models.pipeline import pipeline_apply
    return pipeline_apply


@register_payload("parallel.plan")
def _build_plan(rules_name: str, mesh):
    from ..models.sharding import RULE_SETS, ShardingPlan
    if mesh is None:
        return None
    return ShardingPlan(rules_name, mesh, RULE_SETS[rules_name](
        mesh.axis_names))


# -- runtime: train step -------------------------------------------------------

def _batch_logical_axes(cfg: ArchConfig, batch_shapes: Mapping[str, Any]):
    """Logical axes for every batch leaf (arch-aware)."""
    out = {}
    for k, v in batch_shapes.items():
        nd = len(v.shape) if hasattr(v, "shape") else jnp.ndim(v)
        if k == "positions" and nd == 3:
            out[k] = (None, "act_batch", None)
        elif k in ("embeds", "vis_embeds"):
            out[k] = ("act_batch", None, None)
        else:
            out[k] = ("act_batch",) + (None,) * (nd - 1)
    return out


def make_state_shardings(model, plan, moments: str = "f32"):
    """NamedSharding pytree for {'params', 'opt': {'step','m','v'}}."""
    from ..models.common import P as PSpec
    from ..models.sharding import zero1_axes
    from jax.sharding import NamedSharding, PartitionSpec

    def p_shard(p: PSpec):
        return plan.sharding(p.axes, p.shape)

    def m_shard(p: PSpec):
        if moments == "int8":
            # codes keep the PARAM's shape (blocks along the last dim), so
            # they inherit the param's exact sharding; scales drop the last
            # dim — no moment↔param resharding anywhere in the update.
            if not p.shape:
                repl0 = NamedSharding(plan.mesh, PartitionSpec())
                return {"q": repl0, "s": repl0}
            nblk = (p.shape[-1] + 127) // 128
            return {"q": plan.sharding(p.axes, p.shape),
                    "s": plan.sharding(p.axes[:-1] + (None,),
                                       p.shape[:-1] + (nblk,))}
        return plan.sharding(zero1_axes(p.axes, plan, p.shape), p.shape)

    is_p = lambda x: isinstance(x, PSpec)
    params = jax.tree.map(p_shard, model.specs, is_leaf=is_p)
    moments_sh = jax.tree.map(m_shard, model.specs, is_leaf=is_p)
    repl = NamedSharding(plan.mesh, PartitionSpec())
    return {"params": params,
            "opt": {"step": repl, "m": moments_sh, "v": moments_sh}}


def make_batch_shardings(cfg, plan, batch_shapes):
    ax = _batch_logical_axes(cfg, batch_shapes)
    return {k: plan.sharding(a, tuple(batch_shapes[k].shape))
            for k, a in ax.items()}


@register_payload("runtime.train_step")
def _build_train_entry(model, cfg: ArchConfig, context, bundle, mesh=None):
    from ..optim import (AdamWConfig, TrainStepConfig, adamw_init,
                         build_train_step, cosine_schedule, ef_compress_init)
    from ..models.sharding import use_plan

    plan = _build_plan(context.get("plan.rules", "tp"), mesh)
    adamw = AdamWConfig(
        lr=cosine_schedule(float(context.get("lr", 3e-4)),
                           int(context.get("warmup", 100)),
                           int(context.get("total_steps", 10000))),
        moments=context.get("opt.moments", "f32"))
    ts = TrainStepConfig(
        microbatch=int(context.get("grad_accum", 0) or 0),
        compress=bool(context.get("train.compress", False)),
        adamw=adamw)
    raw_step = build_train_step(model, ts)

    def train_step(state, batch):
        with use_plan(plan):
            return raw_step(state, batch)

    def init_state(key=None):
        key = key if key is not None else jax.random.PRNGKey(0)
        params = model.init(key)
        state = {"params": params, "opt": adamw_init(params, ts.adamw)}
        if ts.compress:
            state["ef_err"] = ef_compress_init(params)
        return state

    def state_shardings():
        sh = make_state_shardings(model, plan, moments=ts.adamw.moments)
        if ts.compress:
            sh["ef_err"] = make_state_shardings(model, plan)["opt"]["m"]
        return sh

    return {
        "train_step": train_step,
        "init_state": init_state,
        "plan": plan,
        "ts_cfg": ts,
        "state_shardings": state_shardings,
        "batch_shardings": functools.partial(make_batch_shardings, cfg, plan),
    }


# -- runtime: serve step ---------------------------------------------------------

@register_payload("runtime.serve_step")
def _build_serve_entry(model, cfg: ArchConfig, context, bundle, mesh=None):
    from ..models.sharding import use_plan
    from ..models.common import axes_tree

    plan = _build_plan(context.get("plan.rules", "tp"), mesh)

    def prefill(params, batch, cache):
        with use_plan(plan):
            return model.prefill(params, batch, cache)

    def decode_step(params, tokens, positions, cache, cache_pos):
        with use_plan(plan):
            return model.decode_step(params, tokens, positions, cache,
                                     cache_pos)

    def cache_shardings(batch_size: int, max_seq: int):
        from ..models.common import P as PSpec
        return jax.tree.map(
            lambda p: plan.sharding(p.axes, p.shape),
            model.cache_specs(batch_size, max_seq),
            is_leaf=lambda x: isinstance(x, PSpec))

    def param_shardings():
        from ..models.common import P as PSpec
        return jax.tree.map(lambda p: plan.sharding(p.axes, p.shape),
                            model.specs,
                            is_leaf=lambda x: isinstance(x, PSpec))

    return {
        "prefill": prefill,
        "decode_step": decode_step,
        "plan": plan,
        "cache_shardings": cache_shardings,
        "param_shardings": param_shardings,
        "batch_shardings": functools.partial(make_batch_shardings, cfg, plan),
    }


@register_payload("runtime.request_batcher")
def _build_batcher(model, cfg: ArchConfig, context, bundle, mesh=None):
    from ..serving import ServingEngine

    plan = _build_plan(context.get("plan.rules", "tp"), mesh)

    def make_engine(params, **kw):
        return ServingEngine(model, params, plan=plan, **kw)

    return {"make_engine": make_engine}


# -- data / opt / assets -----------------------------------------------------------

@register_payload("data.synthetic")
def _build_data(model, cfg: ArchConfig, context, bundle, mesh=None):
    from ..data import batch_for_arch

    def batch_fn(seq_len, global_batch, step=0, seed=0, host=0, num_hosts=1):
        return batch_for_arch(cfg, seq_len, global_batch, step=step,
                              seed=seed, host=host, num_hosts=num_hosts)

    return {"batch_fn": batch_fn}


@register_payload("opt.adamw")
def _opt_adamw():
    from .. import optim
    return optim


@register_payload("asset.weights")
def _asset_weights():
    return None          # virtual bytes only — weights are lazily init'd


@register_payload("asset.frontend")
def _asset_frontend():
    return None


@register_payload("env.base")
def _env_base():
    return None


# ===========================================================================
# The registry content
# ===========================================================================

def _model_components() -> List[C]:
    from .. import models
    out: List[C] = []
    code_sz = _src_size(models.transformer if hasattr(models, "transformer")
                        else models)
    kernel_deps = {
        "decoder-dense": [D("kernel", "attention", "~=1.0")],
        "decoder-moe": [D("kernel", "attention", "~=1.0"),
                        D("kernel", "moe-dispatch", "any")],
        "decoder-rwkv": [D("kernel", "wkv6", "~=1.0")],
        "decoder-hybrid": [D("kernel", "attention", "~=1.0"),
                           D("kernel", "ssm-scan", "any"),
                           D("kernel", "moe-dispatch", "any")],
        "decoder-audio": [D("kernel", "attention", "~=1.0")],
        "decoder-vlm": [D("kernel", "attention", "~=1.0")],
    }
    for name, kdeps in kernel_deps.items():
        deps = tuple(kdeps) + (
            D("parallel", "plan", "any"),
            D("kernel", "rmsnorm", "any"),
            D("env", "runtime-base", "any"),
        )
        for version in ("1.0.0", "1.1.0"):
            out.append(C(
                manager="model", name=name, version=version, env="generic",
                deps=deps,
                context={"model.family": name, "kernel.api": "1"},
                payload="model.decoder", size_bytes=code_sz,
                perf_score=1.0 + (0.2 if version == "1.1.0" else 0.0),
                provides=("model",),
            ))
    return out


def _kernel_components() -> List[C]:
    from .. import kernels as kmod
    from ..models import attention as amod, ssm as smod, ffn as fmod
    ksz = _src_size(kmod.flash_attention) if hasattr(kmod, "flash_attention") \
        else 64 * 1024
    out: List[C] = []
    base_dep = (D("env", "runtime-base", "any"),)

    # attention — four environment variants across two versions
    for version in ("1.0.0", "1.1.0"):
        out += [
            C("kernel", "attention", version, "tpu-pallas",
              deps=base_dep, context={"attn.impl": "pallas"},
              requires=(R("vendor", "eq", "google"),
                        R("interpret", "false")),
              payload="kernel.attention.pallas",
              size_bytes=_src_size(__import__(
                  "repro.kernels.flash_attention", fromlist=["x"])),
              perf_score=3.0, provides=("attention",)),
            C("kernel", "attention", version, "pallas-interpret",
              deps=base_dep, context={"attn.impl": "pallas"},
              requires=(R("interpret", "true"),),
              payload="kernel.attention.pallas",
              size_bytes=ksz, perf_score=0.6, provides=("attention",)),
            C("kernel", "attention", version, "xla-flash",
              deps=base_dep, context={"attn.impl": "lax-flash"},
              payload="kernel.attention.xla_flash",
              size_bytes=_src_size(amod), perf_score=2.0,
              provides=("attention",)),
            C("kernel", "attention", version, "naive",
              deps=base_dep, context={"attn.impl": "naive"},
              payload="kernel.attention.naive",
              size_bytes=8 * 1024, perf_score=0.4, provides=("attention",)),
        ]

    # moe dispatch
    out += [
        C("kernel", "moe-dispatch", "1.0.0", "grouped-gemm",
          deps=base_dep, context={"moe.impl": "grouped"},
          payload="kernel.moe.grouped", size_bytes=_src_size(fmod),
          perf_score=2.0, provides=("moe",)),
        C("kernel", "moe-dispatch", "1.0.0", "dense-oracle",
          deps=base_dep, context={"moe.impl": "dense"},
          requires=(R("mesh.chips", "le", 2),),
          payload="kernel.moe.dense", size_bytes=16 * 1024,
          perf_score=2.5, provides=("moe",)),
    ]

    # wkv6
    out += [
        C("kernel", "wkv6", "1.0.0", "tpu-pallas",
          deps=base_dep, context={"wkv.impl": "pallas"},
          requires=(R("vendor", "eq", "google"), R("interpret", "false")),
          payload="kernel.wkv6.pallas",
          size_bytes=_src_size(__import__(
              "repro.kernels.rwkv6_scan", fromlist=["x"])),
          perf_score=3.0, provides=("wkv",)),
        C("kernel", "wkv6", "1.0.0", "pallas-interpret",
          deps=base_dep, context={"wkv.impl": "pallas"},
          requires=(R("interpret", "true"),),
          payload="kernel.wkv6.pallas", size_bytes=ksz,
          perf_score=0.6, provides=("wkv",)),
        C("kernel", "wkv6", "1.0.0", "chunked-lax",
          deps=base_dep, context={"wkv.impl": "chunked"},
          payload="kernel.wkv6.chunked", size_bytes=_src_size(smod),
          perf_score=2.0, provides=("wkv",)),
        C("kernel", "wkv6", "1.0.0", "sequential",
          deps=base_dep, context={"wkv.impl": "sequential"},
          payload="kernel.wkv6.sequential", size_bytes=8 * 1024,
          perf_score=0.4, provides=("wkv",)),
    ]

    # mamba scan + rmsnorm
    out += [
        C("kernel", "ssm-scan", "1.0.0", "lax-scan",
          deps=base_dep, context={"ssm.impl": "lax"},
          payload="kernel.ssm_scan.lax", size_bytes=_src_size(smod),
          perf_score=1.0, provides=("ssm",)),
        C("kernel", "rmsnorm", "1.0.0", "fused-pallas",
          deps=base_dep, requires=(R("vendor", "eq", "google"),
                                   R("interpret", "false")),
          payload="kernel.rmsnorm.pallas", size_bytes=16 * 1024,
          perf_score=2.0, provides=("norm",)),
        C("kernel", "rmsnorm", "1.0.0", "xla",
          deps=base_dep, payload="kernel.rmsnorm.xla",
          size_bytes=8 * 1024, perf_score=1.0, provides=("norm",)),
    ]
    return out


def _parallel_components() -> List[C]:
    from ..models import sharding as shmod
    sz = _src_size(shmod)
    return [
        C("parallel", "plan", "1.0.0", "fsdp-tp",
          context={"plan.rules": "fsdp-tp"},
          requires=(R("mesh.data", "ge", 2),),
          payload="parallel.plan", size_bytes=sz, perf_score=2.5),
        C("parallel", "plan", "1.0.0", "tp",
          context={"plan.rules": "tp"},
          payload="parallel.plan", size_bytes=sz, perf_score=1.5),
        C("parallel", "plan", "1.0.0", "decode",
          context={"plan.rules": "decode"},
          requires=(R("workload", "eq", "decode"),),
          payload="parallel.plan", size_bytes=sz, perf_score=3.0),
        C("parallel", "plan", "1.1.0", "prefill-sp",
          context={"plan.rules": "prefill-sp"},
          requires=(R("workload", "eq", "prefill-sp"),),
          payload="parallel.plan", size_bytes=sz, perf_score=3.0),
        C("parallel", "plan", "1.1.0", "dp-replicated",
          context={"plan.rules": "dp"},
          requires=(R("plan.force", "eq", "dp"),),
          payload="parallel.plan", size_bytes=sz, perf_score=3.5),
        C("parallel", "pipeline", "1.0.0", "gpipe",
          context={"pp.schedule": "gpipe"},
          requires=(R("workload", "eq", "pipeline"),),
          payload="parallel.pipeline", size_bytes=sz, perf_score=2.0),
        C("parallel", "plan", "1.0.0", "sp-decode",
          context={"plan.rules": "sp-decode"},
          requires=(R("workload", "eq", "long-decode"),),
          payload="parallel.plan", size_bytes=sz, perf_score=3.0),
    ]


def _runtime_components() -> List[C]:
    from .. import optim as omod, serving as svmod, data as dmod
    opt_dep = (D("opt", "adamw", "any"), D("env", "runtime-base", "any"))
    return [
        C("runtime", "train-step", "1.0.0", "standard",
          deps=opt_dep, payload="runtime.train_step",
          size_bytes=_src_size(omod), perf_score=1.5),
        C("runtime", "train-step", "1.0.0", "compressed-dci",
          deps=opt_dep, context={"train.compress": True},
          requires=(R("mesh.pod", "ge", 2),),
          payload="runtime.train_step", size_bytes=_src_size(omod),
          perf_score=2.5),
        C("runtime", "serve-step", "1.0.0", "standard",
          deps=(D("env", "runtime-base", "any"),),
          payload="runtime.serve_step", size_bytes=_src_size(svmod),
          perf_score=1.5),
        C("runtime", "request-batcher", "1.0.0", "slot-continuous",
          deps=(D("runtime", "serve-step", "any"),),
          payload="runtime.request_batcher", size_bytes=_src_size(svmod),
          perf_score=1.5),
        C("opt", "adamw", "1.0.0", "f32-moments",
          payload="opt.adamw", size_bytes=_src_size(omod), perf_score=1.5,
          context={"opt.moments": "f32"},
          requires=(R("hbm.per_chip", "ge", 32 * 2**30),)),
        C("opt", "adamw", "1.0.0", "bf16-moments",
          payload="opt.adamw", size_bytes=_src_size(omod), perf_score=1.2,
          context={"opt.moments": "bf16"}),
        C("opt", "adamw", "1.1.0", "int8-moments",
          payload="opt.adamw", size_bytes=_src_size(omod), perf_score=2.0,
          context={"opt.moments": "int8"},
          requires=(R("opt.int8", "true"),)),   # opt-in: HBM-starved giants
        C("data", "pipeline-synthetic", "1.0.0", "standard",
          payload="data.synthetic", size_bytes=_src_size(dmod),
          perf_score=1.0),
    ]


def _env_components() -> List[C]:
    out = [C("env", "os-base", "12.0", "any", payload="env.base",
             size_bytes=_OS_BASE_SIZE, perf_score=1.0)]
    for chip, size in _RUNTIME_BASE_SIZES.items():
        out.append(C(
            "env", "runtime-base", "0.8.2", chip,
            deps=(D("env", "os-base", "any"),),
            context={"runtime.platform": chip},
            requires=(R("chip", "eq", chip),),
            payload="env.base", size_bytes=size, perf_score=1.0))
    return out


def _asset_components() -> List[C]:
    """Weights (exact virtual bytes) + frontend stubs, as upstream-converted
    components — these come in via the UpstreamSource path to exercise the
    registry→upstream fallback (paper Fig. 5)."""
    out: List[C] = []
    for arch_id, cfg in ARCHS.items():
        n = cfg.param_count()
        out.append(C(
            "asset", f"weights-{arch_id}", "2025.12.1", "bf16",
            payload="asset.weights", size_bytes=2 * n,
            context={f"weights.{arch_id}": "2025.12.1"},
            meta={"params": n}, perf_score=1.0))
    for fe, size in _FRONTEND_SIZES.items():
        out.append(C(
            "asset", f"frontend-{fe}", "1.0.0", "bf16",
            payload="asset.frontend", size_bytes=size, perf_score=1.0))
    return out


# -- context-spec hooks (the paper's M.getSpec(C)) ---------------------------

def _kernel_spec_hook(name: str, ctx: Mapping[str, Any]) -> Optional[str]:
    """Models pin the kernel API major version through the building context
    (cross-manager constraint flow, like pip's python-version pins)."""
    api = ctx.get("kernel.api")
    if api and name in ("attention", "wkv6"):
        return f"~={api}.0"
    return None


register_context_spec_hook("kernel", _kernel_spec_hook)


# ===========================================================================
# Service construction
# ===========================================================================

def builtin_components() -> List[C]:
    return (_model_components() + _kernel_components()
            + _parallel_components() + _runtime_components()
            + _env_components())


def build_service(with_assets_upstream: bool = True
                  ) -> UniformComponentService:
    """Fresh registry + service.  Asset components live behind an
    UpstreamSource so the first request exercises on-demand conversion."""
    registry = UniformComponentRegistry()
    registry.register_all(builtin_components())
    upstreams = []
    if with_assets_upstream:
        upstreams.append(UpstreamSource(
            name="asset-hub",
            lister=lambda: [None],
            converter=lambda _raw: _asset_components()))
    else:
        registry.register_all(_asset_components())
    return UniformComponentService(registry, upstreams)


_DEFAULT: Optional[UniformComponentService] = None


def default_service() -> UniformComponentService:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = build_service()
    return _DEFAULT
