"""Event-driven build orchestrator: lifecycle stages, build-graph gates,
overlap correctness (byte-identical accounting vs the barrier pipeline),
fleet lifecycle accounting, and failure propagation."""
import threading

import pytest

from repro.configs import ARCHS
from repro.core import (BuildGraph, ChunkedComponentStore, Lifecycle,
                        LazyBuilder, PreBuilder, catalog, cpu_smoke,
                        gpu_server, tpu_single_pod)
from repro.deploy import FleetDeployer

# Fast simulated link: slow enough that the weight tail is measurable wall
# time, fast enough that the whole module stays in CI budget.
_SIM_BPS = 50e9


def _builder(sim=None, **kw):
    svc = catalog.build_service()
    return (LazyBuilder(svc, ChunkedComponentStore(),
                        fetch_simulate_bps=sim, **kw),
            PreBuilder(svc))


# ---------------------------------------------------------------------------
# Lifecycle + BuildGraph units
# ---------------------------------------------------------------------------

def test_lifecycle_is_monotonic_and_waitable():
    life = Lifecycle()
    assert life.stage == "planned"
    life.advance("compiled")            # implies fetching + assembled
    assert life.reached("assembled")
    assert life.wait("fetching", timeout=0.1) == "compiled"
    with pytest.raises(TimeoutError):
        life.wait("ready", timeout=0.01)
    life.advance("complete")
    assert life.wait("weights", timeout=0.1) == "complete"   # alias


def test_lifecycle_fail_wakes_waiters_with_the_error():
    life = Lifecycle()
    life.advance("assembled")
    seen = []

    def waiter():
        try:
            life.wait("ready")
        except RuntimeError as e:
            seen.append(e)

    t = threading.Thread(target=waiter)
    t.start()
    life.fail(RuntimeError("boom"))
    t.join(timeout=5)
    assert len(seen) == 1 and "boom" in str(seen[0])
    # stages reached before the failure still wait cleanly
    assert life.wait("assembled", timeout=0.1)
    with pytest.raises(RuntimeError):
        life.wait("complete", timeout=0.1)


def test_build_graph_gates():
    g = BuildGraph()
    assert g.stage_of("model") == "assemble"
    assert g.stage_of("runtime") == "assemble"
    assert g.stage_of("data") == "assemble"
    assert g.stage_of("env") == "compile"
    assert g.stage_of("asset") == "complete"    # first-weight-use only
    assert g.stage_of("opt") == "ready"


def test_build_graph_asset_never_gates_ready(service):
    pb = PreBuilder(service)
    cir = pb.prebuild(ARCHS["starcoder2-3b"], entrypoint="serve")
    lb = LazyBuilder(service)
    inst = lb.build(cir, tpu_single_pod(), assemble=False)
    comps = inst.bundle.components()
    gates = BuildGraph().gates_for(comps)
    assets = {c.digest() for c in comps if c.manager == "asset"}
    assert assets, "serve CIR should carry weight assets"
    assert not (gates["ready"] & assets)
    assert not (gates["assemble"] & assets)
    assert assets <= gates["complete"]
    assert gates["assemble"] <= gates["ready"]
    assert gates["compile"] <= gates["ready"]


# ---------------------------------------------------------------------------
# Orchestrated builds: lifecycle progression + wait API
# ---------------------------------------------------------------------------

def test_nonblocking_build_progresses_through_stages(smoke_mesh):
    lb, pb = _builder(sim=_SIM_BPS)
    cir = pb.prebuild(ARCHS["starcoder2-3b"], entrypoint="serve")
    inst = lb.build(cir, cpu_smoke(), mesh=smoke_mesh, block=False)
    inst.wait("assembled")
    assert inst.model is not None and inst.entry
    inst.wait("ready")
    # deployable: every non-asset component's content is proven present
    for c in inst.bundle.components():
        if c.manager != "asset":
            assert lb.store.missing_chunks(c) == []
    inst.wait("weights")                 # first-weight-use gate
    assert inst.stage == "complete"
    rep = inst.report
    assert rep.orchestrated and rep.critical_path_s > 0
    for stage in ("fetching", "assembled", "compiled", "ready", "complete"):
        assert stage in rep.stage_s
    # accounting is final at COMPLETE: every planned chunk landed
    for c in inst.bundle.components():
        assert lb.store.missing_chunks(c) == []


def test_blocking_build_returns_complete_with_final_accounting():
    lb, pb = _builder(sim=_SIM_BPS)
    cir = pb.prebuild(ARCHS["starcoder2-3b"], entrypoint="serve")
    inst = lb.build(cir, tpu_single_pod(), assemble=False)
    assert inst.stage == "complete"
    assert inst.report.bytes_delta_fetched > 0
    assert inst.report.overlap_s >= 0.0


def test_barrier_mode_has_no_overlap():
    lb, pb = _builder(sim=_SIM_BPS)
    cir = pb.prebuild(ARCHS["starcoder2-3b"], entrypoint="serve")
    rep = lb.build(cir, tpu_single_pod(), assemble=False,
                   overlap=False).report
    assert not rep.orchestrated
    assert rep.overlap_s == 0.0
    # barrier critical path covers the full stage sum
    assert rep.critical_path_s >= rep.fetch_s


# ---------------------------------------------------------------------------
# Overlap correctness: byte-identical accounting, identical locks
# ---------------------------------------------------------------------------

_ACCOUNTING_FIELDS = ("bytes_delta_fetched", "bytes_fetched",
                      "bytes_total_components", "chunks_hit",
                      "chunks_missed", "chunks_waited", "cache_hits",
                      "cache_misses", "n_components")


def test_overlapped_and_barrier_builds_account_identically():
    spec = tpu_single_pod()
    reports, locks = {}, {}
    for mode, overlap in (("barrier", False), ("overlapped", True)):
        lb, pb = _builder(sim=_SIM_BPS)
        cir = pb.prebuild(ARCHS["gemma2-9b"], entrypoint="serve")
        inst = lb.build(cir, spec, assemble=False, overlap=overlap)
        reports[mode], locks[mode] = inst.report, inst.lock
    for f in _ACCOUNTING_FIELDS:
        assert getattr(reports["barrier"], f) == \
            getattr(reports["overlapped"], f), f
    assert locks["barrier"].to_json() == locks["overlapped"].to_json()


def test_overlap_cuts_time_to_ready():
    """READY fires while the weight tail is still streaming; the barrier
    pipeline's READY only lands after the full fetch.  Asserted on stage
    offsets *within* each build — cross-run wall comparisons are
    scheduler-noise-prone; ``benchmarks/build_time.py pipeline_overlap``
    gates the cross-mode >=25% reduction criterion in a fresh process."""
    spec = tpu_single_pod()
    reps = {}
    # slow simulated link: the ~18 GB weight tail costs >400 ms of wall,
    # dwarfing scheduler noise from a loaded CI machine
    for mode, overlap in (("barrier", False), ("overlapped", True)):
        lb, pb = _builder(sim=5e9)
        cir = pb.prebuild(ARCHS["gemma2-9b"], entrypoint="serve")
        reps[mode] = lb.build(cir, spec, assemble=False,
                              overlap=overlap).report
    o, b = reps["overlapped"], reps["barrier"]
    # weights are ~90% of the fetch bytes, so a READY that waited for the
    # tail would sit within a few % of COMPLETE — require a real gap
    assert o.stage_s["ready"] < 0.8 * o.stage_s["complete"]
    assert o.overlap_s > 0.0
    # the barrier build is only READY once the entire fetch has landed
    assert b.stage_s["ready"] >= b.fetch_s
    assert b.overlap_s == 0.0


def test_locked_replay_through_orchestrator_is_byte_identical():
    spec = tpu_single_pod()
    svc = catalog.build_service()
    pb = PreBuilder(svc)
    cir = pb.prebuild(ARCHS["starcoder2-3b"], entrypoint="serve")
    cold = LazyBuilder(svc, ChunkedComponentStore(),
                       fetch_simulate_bps=_SIM_BPS).build(
        cir, spec, assemble=False)
    replay = LazyBuilder(svc, ChunkedComponentStore(),
                         fetch_simulate_bps=_SIM_BPS).build_from_lock(
        cir, cold.lock, spec, assemble=False)
    for f in _ACCOUNTING_FIELDS:
        assert getattr(cold.report, f) == getattr(replay.report, f), f


def test_fleet_overlap_accounting_matches_barrier_under_singleflight():
    """A concurrent overlapped fleet (shared store, singleflight waits)
    transfers exactly the same unique bytes as a barrier fleet: no chunk is
    double-charged and no byte is dropped, whichever build wins a claim."""
    specs = [tpu_single_pod(), cpu_smoke(), gpu_server()]
    totals, locks = {}, {}
    for mode, overlap in (("barrier", False), ("overlapped", True)):
        svc = catalog.build_service()
        fd = FleetDeployer(svc, max_workers=3, fetch_workers=4,
                           fetch_simulate_bps=_SIM_BPS, overlap=overlap)
        cir = PreBuilder(svc).prebuild(ARCHS["starcoder2-3b"],
                                       entrypoint="serve")
        res = fd.deploy(cir, specs)
        assert res.ok, res.summary()
        assert res.n_failed == 0
        # singleflight invariant: fleet wire bytes == unique chunk bytes
        assert res.bytes_delta_total == \
            fd.store.chunk_stats.chunk_bytes_stored
        totals[mode] = (res.bytes_delta_total, res.chunks_missed_total,
                        res.chunks_hit_total + res.chunks_waited_total)
        locks[mode] = {d.platform_id: d.instance.lock.to_json()
                       for d in res.deployments}
    assert totals["barrier"] == totals["overlapped"]
    assert locks["barrier"] == locks["overlapped"]


def test_fleet_records_lifecycle_walls():
    svc = catalog.build_service()
    fd = FleetDeployer(svc, max_workers=2, fetch_simulate_bps=_SIM_BPS)
    cir = PreBuilder(svc).prebuild(ARCHS["starcoder2-3b"],
                                   entrypoint="serve")
    res = fd.deploy(cir, [tpu_single_pod(), cpu_smoke()])
    assert res.ok
    assert 0.0 < res.ready_s_wall <= res.wall_s
    assert res.stage_walls.get("ready", 0.0) > 0.0
    assert res.stage_walls["ready"] <= res.stage_walls["complete"]
    for d in res.deployments:
        assert d.report is not None
        assert 0.0 < d.ready_s <= d.wall_s


# ---------------------------------------------------------------------------
# Failure propagation
# ---------------------------------------------------------------------------

def test_fetch_error_fails_lifecycle_and_propagates(monkeypatch):
    lb, pb = _builder()
    cir = pb.prebuild(ARCHS["starcoder2-3b"], entrypoint="serve")

    def boom(c, nbytes, nchunks):
        if c.manager == "model":
            raise RuntimeError("link down")

    monkeypatch.setattr(lb.service, "fetch_chunks", boom)
    inst = lb.build(cir, tpu_single_pod(), assemble=False, block=False)
    with pytest.raises(RuntimeError, match="link down"):
        inst.wait("ready")
    assert inst.lifecycle.error is not None
    # blocking builds raise straight from build()
    lb2, pb2 = _builder()
    monkeypatch.setattr(lb2.service, "fetch_chunks", boom)
    with pytest.raises(RuntimeError, match="link down"):
        lb2.build(pb2.prebuild(ARCHS["starcoder2-3b"], entrypoint="serve"),
                  tpu_single_pod(), assemble=False)


def test_fleet_counts_failures_and_keeps_partial_reports(monkeypatch):
    """A failed platform is counted (n_failed) and its partial fetch work
    stays in the fleet byte accounting instead of silently vanishing."""
    from repro.core.spec import ChipSpec, SpecSheet

    svc = catalog.build_service()
    fd = FleetDeployer(svc, max_workers=2)
    cir = PreBuilder(svc).prebuild(ARCHS["starcoder2-3b"],
                                   entrypoint="serve")
    # resolution failure: a chip no env component supports
    bad = SpecSheet(platform_id="fpga-odd",
                    chip=ChipSpec(name="fpga-odd", vendor="x",
                                  peak_flops_bf16=1e9, hbm_bytes=2**30,
                                  hbm_bw=1e9, vmem_bytes=2**20,
                                  ici_bw_per_link=1e9, ici_links=1,
                                  dci_bw=1e9),
                    mesh_shape=(1,), mesh_axes=("data",))
    res = fd.deploy(cir, [tpu_single_pod(), bad])
    assert not res.ok and res.n_failed == 1
    failed = [d for d in res.deployments if not d.ok][0]
    assert failed.platform_id == "fpga-odd"
    assert failed.report is None          # never got past resolution
    ok = [d for d in res.deployments if d.ok][0]
    assert res.bytes_fetched_total == ok.report.bytes_fetched

    # mid-fetch failure: resolution succeeded, so the partial report (and
    # its real transferred bytes) must be included in the totals
    svc2 = catalog.build_service()
    fd2 = FleetDeployer(svc2, max_workers=1)
    cir2 = PreBuilder(svc2).prebuild(ARCHS["starcoder2-3b"],
                                     entrypoint="serve")

    def boom(c, nbytes, nchunks):
        if c.manager == "asset":
            raise RuntimeError("upstream 503")

    monkeypatch.setattr(svc2, "fetch_chunks", boom)
    res2 = fd2.deploy(cir2, [tpu_single_pod()])
    assert res2.n_failed == 1
    failed2 = res2.deployments[0]
    assert failed2.report is not None
    assert failed2.report.resolve_s > 0
    assert failed2.report.cache_misses > 0
    # the partial build's accounting flows into the fleet totals
    assert res2.bytes_fetched_total == failed2.report.bytes_fetched
    assert res2.bytes_delta_total == failed2.report.bytes_delta_fetched


# ---------------------------------------------------------------------------
# Satellite: probe_host maps a gpu jax backend to the GPU chip
# ---------------------------------------------------------------------------

def test_probe_host_maps_backends_to_chips(monkeypatch):
    import jax

    from repro.core import CPU_HOST, GPU_A100, TPU_V5E
    from repro.core.spec import probe_host

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    s = probe_host()
    assert s.chip is GPU_A100
    assert s.backend == "gpu" and s.interpret_kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "cuda")
    assert probe_host().chip is GPU_A100
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert probe_host().chip is CPU_HOST
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    for kind in ("TPU v5 lite", "TPU v5e"):
        monkeypatch.setattr(jax, "devices", lambda k=kind: [_Dev(k)])
        s = probe_host()
        assert s.chip is TPU_V5E and not s.interpret_kernels
    # a TPU or a backend without a ChipSpec raises instead of borrowing one
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v4")])
    with pytest.raises(ValueError, match="TPU v4"):
        probe_host()
    monkeypatch.setattr(jax, "default_backend", lambda: "metal")
    with pytest.raises(ValueError, match="metal"):
        probe_host()
