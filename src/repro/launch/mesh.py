"""Production meshes + the assigned (architecture × input-shape) cell grid.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` before first jax init and only
then calls it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


def _make_mesh(shape, axes):
    """A mesh over the first ``prod(shape)`` local devices."""
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def parse_mesh(text: str) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """``'4'`` → ((4,), ('data',)); ``'1x4'`` → ((1, 4), ('data', 'model'))."""
    shape = tuple(int(n) for n in text.lower().split("x"))
    if not 1 <= len(shape) <= 2 or min(shape) < 1:
        raise ValueError(f"mesh shape {text!r} is not N or DxM")
    return shape, ("data", "model")[:len(shape)]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(devices: int = 1):
    return _make_mesh((devices,), ("data",))


# ---------------------------------------------------------------------------
# Assigned input shapes (identical across the LM-family archs)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str                 # train | prefill | decode
    seq_len: int
    global_batch: int
    long_context: bool = False

    @property
    def workload(self) -> str:
        if self.kind == "train":
            return "train"
        if self.long_context:
            return "long-decode"
        return "decode" if self.kind == "decode" else "prefill"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1,
                           long_context=True),
}


def applicable(cfg, shape: ShapeSpec) -> Tuple[bool, str]:
    """(runs?, reason).  long_500k needs sub-quadratic attention; pure
    full-attention archs skip it (recorded in DESIGN.md §4)."""
    if shape.long_context and not cfg.sub_quadratic:
        return False, "full-attention arch: O(S) KV decode at 500k is " \
                      "intractable; skipped per assignment rules"
    return True, ""


def live_cells(arch_ids: List[str], configs) -> List[Tuple[str, str]]:
    out = []
    for aid in arch_ids:
        cfg = configs[aid]
        for sname, sh in SHAPES.items():
            ok, _ = applicable(cfg, sh)
            if ok:
                out.append((aid, sname))
    return out


# ---------------------------------------------------------------------------
# Deployment-time workload adaptation (what the lazy-builder gets told)
# ---------------------------------------------------------------------------

def suggest_grad_accum(cfg, shape: ShapeSpec, spec) -> int:
    """Napkin model for the microbatch count: saved scan-boundary
    activations must fit an HBM budget.

        act_bytes ≈ tokens × d_model × 2 B × n_scan_boundaries / dp_shards
        logits    ≈ tokens × vocab × 4 B / (dp × tp)  (freed per microbatch)

    Pick the smallest power-of-two accum that brings act_bytes under ~1/3
    of per-chip HBM, capped so the per-microbatch batch stays ≥ 1 row.
    """
    if shape.kind != "train":
        return 0
    dp = spec.axis("data") * spec.axis("pod")
    tp = spec.axis("model")
    tokens = shape.seq_len * shape.global_batch
    boundaries = cfg.num_layers + 2
    act = tokens * cfg.d_model * 2 * boundaries / dp
    logits = tokens * cfg.vocab * 4 / (dp * tp)
    budget = spec.chip.hbm_bytes / 3.0
    need = (act + logits) / budget
    accum = 1
    while accum < need and accum < shape.global_batch // dp:
        accum *= 2
    return accum if accum > 1 else 0


def replicated_fit(cfg, spec) -> bool:
    """Can the model train fully replicated (pure DP over every axis)?
    Needs params(bf16) + grads(bf16) + f32 update transients ≲ 80 % HBM and
    one whole batch row per chip."""
    n = cfg.param_count()
    need = n * (2 + 2 + 2)          # params + grads + transient slack
    return need <= 0.8 * spec.chip.hbm_bytes


def build_overrides(cfg, shape: ShapeSpec, spec) -> Dict[str, object]:
    """The building-context overrides the launcher feeds the lazy-builder —
    this is the deployment-time, architecture-aware adaptation the paper
    advocates (the developer's CIR never mentions any of it).

    Beyond the workload tag and the grad-accum napkin model, two adaptive
    plan choices validated by the §Perf hillclimb:
      * prefill of kv-narrow GQA archs (kv_heads < model axis) switches to
        sequence-parallel prefill — head-sharding would degenerate into
        score-matrix all-reduces (measured 63 s/step on starcoder2);
      * small models that fit replicated train pure-DP over every axis —
        TP of a ~2 GB model leaves matmuls too skinny for their collectives
        (4.2x roofline-fraction win on musicgen).
    """
    ov: Dict[str, object] = {"workload": shape.workload}
    if shape.kind == "prefill" \
            and cfg.family in ("dense-lm", "moe-lm", "audio-lm", "vlm-lm") \
            and cfg.attention == "gqa" and cfg.n_kv < spec.axis("model"):
        ov["workload"] = "prefill-sp"
    if shape.kind == "train" and replicated_fit(cfg, spec) \
            and shape.global_batch >= spec.num_chips:
        ov["plan.force"] = "dp"
        return ov                     # pure DP: no microbatching needed
    ga = suggest_grad_accum(cfg, shape, spec)
    if ga:
        ov["grad_accum"] = ga
    return ov
