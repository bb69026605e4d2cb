"""SpecSheet: the deployment platform description the lazy-builder reads.

The paper's specSheet "encapsulates the local hardware and software
configurations" (CPU arch, system type, interpreter, libc).  Our deployment
platforms are JAX meshes on concrete chips, so the specSheet carries the
mesh topology, per-chip compute/memory/interconnect numbers and the software
facts (jax version, backend, dtype support) that environment selection
(Algorithm 1's ES) matches component requirements against.
"""
from __future__ import annotations

import dataclasses
import json
import platform as _platform
from typing import Any, Dict, Mapping, Optional, Tuple

# ---------------------------------------------------------------------------
# Chip descriptions (hardware constants used for deployability + roofline).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    vendor: str
    peak_flops_bf16: float          # FLOP/s per chip
    hbm_bytes: int                  # bytes per chip
    hbm_bw: float                   # bytes/s per chip
    vmem_bytes: int                 # on-chip scratch (VMEM / L2)
    ici_bw_per_link: float          # bytes/s per ICI link
    ici_links: int                  # links per chip (torus degree)
    dci_bw: float                   # inter-pod bytes/s per chip (data-center net)
    mxu_align: int = 128            # matmul tile alignment
    supports: Tuple[str, ...] = ("bf16", "f32")


TPU_V5E = ChipSpec(
    name="tpu-v5e", vendor="google",
    peak_flops_bf16=197e12, hbm_bytes=16 * 2**30, hbm_bw=819e9,
    vmem_bytes=128 * 2**20, ici_bw_per_link=50e9, ici_links=4,
    dci_bw=25e9 / 8 * 4,  # ~4x 25Gbps NICs per host, bytes/s per chip (approx)
    supports=("bf16", "f32", "int8", "f8"),
)

CPU_HOST = ChipSpec(
    name="cpu-host", vendor="generic",
    peak_flops_bf16=100e9, hbm_bytes=32 * 2**30, hbm_bw=20e9,
    vmem_bytes=32 * 2**20, ici_bw_per_link=10e9, ici_links=1, dci_bw=1e9,
    supports=("f32", "bf16"),
)

# A GPU-flavoured platform: exercises the paper's cross-platform claim with a
# third heterogeneous target (deployability must pick different variants).
GPU_A100 = ChipSpec(
    name="gpu-a100", vendor="nvidia",
    peak_flops_bf16=312e12, hbm_bytes=80 * 2**30, hbm_bw=2039e9,
    vmem_bytes=40 * 2**20, ici_bw_per_link=300e9, ici_links=1, dci_bw=25e9 / 8,
    supports=("bf16", "f32", "f16", "int8"),
)

CHIPS = {c.name: c for c in (TPU_V5E, CPU_HOST, GPU_A100)}

# jax ``device_kind`` strings of the TPUs described above
TPU_KINDS = {"TPU v5e": TPU_V5E, "TPU v5 lite": TPU_V5E}


# ---------------------------------------------------------------------------
# SpecSheet
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpecSheet:
    """Everything the lazy-builder knows about the deployment platform."""

    platform_id: str                      # human name ("tpu-v5e-pod0")
    chip: ChipSpec
    mesh_shape: Tuple[int, ...]           # e.g. (16, 16) or (2, 16, 16)
    mesh_axes: Tuple[str, ...]            # e.g. ("data", "model")
    num_hosts: int = 1
    backend: str = "cpu"                  # jax backend actually present
    interpret_kernels: bool = True        # pallas must run interpret on CPU
    jax_version: str = ""
    os: str = ""
    cpu_arch: str = ""
    python: str = ""
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- derived ------------------------------------------------------------
    @property
    def num_chips(self) -> int:
        n = 1
        for s in self.mesh_shape:
            n *= s
        return n

    @property
    def axis_size(self) -> Dict[str, int]:
        return dict(zip(self.mesh_axes, self.mesh_shape))

    def axis(self, name: str, default: int = 1) -> int:
        return self.axis_size.get(name, default)

    @property
    def total_hbm(self) -> int:
        return self.num_chips * self.chip.hbm_bytes

    # The "building context" seed (Algorithm 2 initializes C from the host).
    def context(self) -> Dict[str, Any]:
        return {
            "chip": self.chip.name,
            "vendor": self.chip.vendor,
            "backend": self.backend,
            "mesh.shape": list(self.mesh_shape),
            "mesh.axes": list(self.mesh_axes),
            "mesh.chips": self.num_chips,
            "mesh.data": self.axis("data"),
            "mesh.model": self.axis("model"),
            "mesh.pod": self.axis("pod"),
            "interpret": self.interpret_kernels,
            "dtypes": list(self.chip.supports),
            "hbm.per_chip": self.chip.hbm_bytes,
            "vmem": self.chip.vmem_bytes,
        }

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        return json.dumps(d, sort_keys=True)

    def digest(self) -> str:
        """Stable content digest of the platform description (cache key)."""
        import hashlib
        return hashlib.sha256(self.to_json().encode()).hexdigest()

    @staticmethod
    def from_json(s: str) -> "SpecSheet":
        d = json.loads(s)
        d["chip"] = ChipSpec(**d["chip"])
        d["mesh_shape"] = tuple(d["mesh_shape"])
        d["mesh_axes"] = tuple(d["mesh_axes"])
        d["chip"] = dataclasses.replace(d["chip"], supports=tuple(d["chip"].supports))
        return SpecSheet(**d)


def probe_host(platform_id: str = "local",
               mesh_shape: Tuple[int, ...] = (1,),
               mesh_axes: Tuple[str, ...] = ("data",),
               chip: Optional[ChipSpec] = None) -> SpecSheet:
    """Inspect the *actual* host (paper: 'inspects the target hardware and
    driver configuration').

    A TPU is identified by its ``device_kind``; a kind or a backend with no
    ``ChipSpec`` here raises rather than borrowing another chip's peaks."""
    import jax  # local import: keep module import free of jax side effects

    backend = jax.default_backend()
    if chip is None:
        if backend == "tpu":
            kind = jax.devices()[0].device_kind
            if kind not in TPU_KINDS:
                raise ValueError(f"no ChipSpec for TPU device kind {kind!r}")
            chip = TPU_KINDS[kind]
        elif backend in ("gpu", "cuda", "rocm"):
            chip = GPU_A100
        elif backend == "cpu":
            chip = CPU_HOST
        else:
            raise ValueError(f"no ChipSpec for jax backend {backend!r}")
    return SpecSheet(
        platform_id=platform_id,
        chip=chip,
        mesh_shape=mesh_shape,
        mesh_axes=mesh_axes,
        backend=backend,
        interpret_kernels=(backend != "tpu"),
        jax_version=jax.__version__,
        os=_platform.system().lower(),
        cpu_arch=_platform.machine(),
        python=_platform.python_version(),
    )


# -- canonical deployment platforms used across benchmarks/dry-runs ---------

def tpu_single_pod(data: int = 16, model: int = 16) -> SpecSheet:
    return SpecSheet(
        platform_id=f"tpu-v5e-{data}x{model}",
        chip=TPU_V5E, mesh_shape=(data, model), mesh_axes=("data", "model"),
        num_hosts=data * model // 4, backend="cpu", interpret_kernels=True,
    )


def tpu_multi_pod(pods: int = 2, data: int = 16, model: int = 16) -> SpecSheet:
    return SpecSheet(
        platform_id=f"tpu-v5e-{pods}x{data}x{model}",
        chip=TPU_V5E, mesh_shape=(pods, data, model),
        mesh_axes=("pod", "data", "model"),
        num_hosts=pods * data * model // 4, backend="cpu",
        interpret_kernels=True,
    )


def cpu_smoke(devices: int = 1) -> SpecSheet:
    return SpecSheet(
        platform_id=f"cpu-smoke-{devices}",
        chip=CPU_HOST, mesh_shape=(devices,), mesh_axes=("data",),
        backend="cpu", interpret_kernels=True,
    )


def gpu_server() -> SpecSheet:
    """The paper's 'GPU Server' platform flavour (A100) — used to show the
    same CIR resolving to different variants on a heterogeneous target."""
    return SpecSheet(
        platform_id="gpu-a100-8", chip=GPU_A100, mesh_shape=(8,),
        mesh_axes=("data",), backend="cpu", interpret_kernels=True,
    )


PLATFORM_PRESETS = {
    "cpu-smoke": cpu_smoke,
    "tpu-pod": tpu_single_pod,
    "tpu-multipod": tpu_multi_pod,
    "gpu-server": gpu_server,
}
