"""Lazy-builder: the staged deployment pipeline (paper §4.2).

The lazy-build is an explicit four-stage pipeline:

    resolve  → pick concrete uniform components for the target platform
               (Algorithms 1+2), or REPLAY a cached build plan;
    fetch    → pull missing content against the local store.  With the
               default ``ChunkedComponentStore`` this is a *delta* fetch:
               a missing-chunk plan per component, executed by a bounded
               thread-pool ``FetchEngine`` with singleflight dedup and
               priority ordering (model/runtime first, weight tail last);
    assemble → overlay components into the model + entrypoint callables
               (the OverlayFS-mount analogue);
    compile  → stage the step entrypoints for the target mesh (jit).

Stage 1 consults a persistent, content-addressed **build-plan cache** keyed
by ``(CIR digest, SpecSheet digest, catalog epoch, overrides)``: a hit skips
resolution/selection entirely and replays the stored version-lock manifest
against the component service + ``LocalComponentStore``.  This is what makes
re-deploying the same CIR to the same platform class — the hot path of a
deployment service — cheap, and what ``FleetDeployer`` (repro.deploy) builds
on to amortize one CIR across N heterogeneous platforms.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from .cir import CIR
from .chunkstore import CLAIM_WAIT_TIMEOUT_S, ChunkedComponentStore, FetchPlan
from .compilecache import (COMPILE_VIRTUAL_S_PER_ENTRY, CompileCache,
                           CompiledArtifact, artifact_component,
                           compile_cache_key)
from .component import UniformComponent
from .integrity import (Attestation, AttestationError, Signer, make_sbom,
                        attest as _sign_manifest, verify_attestation)
from .irmodule import (AUTOTUNE_VIRTUAL_S_PER_ENTRY,
                       IR_LOWER_VIRTUAL_S_PER_ENTRY,
                       TAIL_COMPILE_VIRTUAL_S_PER_ENTRY,
                       autotune_component, ir_module_component)
from .orchestrator import (BuildGraph, BuildOrchestrator, ComponentReadiness,
                           Lifecycle)
from .registry import RegistryError, UniformComponentService
from .resolution import (Resolution, ResolutionError, resolution_from_pins,
                         uniform_dependency_resolution)
from .simnet import SimTransport, WallClockTransport
from .spec import SpecSheet
from .store import LocalComponentStore

# Payload catalog: payload-reference -> python factory.  Populated by
# repro.core.catalog at import time (the 'converted component' bodies).
PAYLOADS: Dict[str, Callable] = {}


def register_payload(name: str):
    def deco(fn):
        if name in PAYLOADS and PAYLOADS[name] is not fn:
            raise ValueError(f"payload {name!r} already registered")
        PAYLOADS[name] = fn
        return fn
    return deco


class ComponentBundle:
    """The selected components of one build, addressable by (manager, name).

    Assembly code pulls concrete variants from here — this is how the model
    family finds *which* attention/kernel/plan variant Algorithm 1 picked.
    """

    def __init__(self, resolution: Resolution):
        self.resolution = resolution
        self._by_key = dict(resolution.selected_by_key)

    def component(self, manager: str, name: str) -> UniformComponent:
        return self._by_key[(manager, name)]

    def has(self, manager: str, name: str) -> bool:
        return (manager, name) in self._by_key

    def payload(self, manager: str, name: str) -> Callable:
        c = self.component(manager, name)
        try:
            return PAYLOADS[c.payload]
        except KeyError:
            raise KeyError(
                f"component {c.ident_str()} references unknown payload "
                f"{c.payload!r} — is repro.core.catalog imported?") from None

    def payload_of(self, c: UniformComponent) -> Callable:
        return PAYLOADS[c.payload]

    @property
    def context(self) -> Dict[str, Any]:
        return self.resolution.context

    def components(self) -> List[UniformComponent]:
        return list(self.resolution.components)


# ---------------------------------------------------------------------------
# Lockfile (paper §4.2: "a dedicated version locking file for each platform")
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Lockfile:
    cir_digest: str
    platform_id: str
    seed: int
    pins: Tuple[Tuple[str, str, str, str], ...]   # (M, n, v, e)
    digests: Tuple[str, ...]

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @staticmethod
    def from_json(s: str) -> "Lockfile":
        d = json.loads(s)
        d["pins"] = tuple(tuple(p) for p in d["pins"])
        d["digests"] = tuple(d["digests"])
        return Lockfile(**d)

    def digest(self) -> str:
        return hashlib.sha256(self.to_json().encode()).hexdigest()

    def repinned(self, service, manager: str,
                 envs: Mapping[str, str]) -> "Lockfile":
        """This lock with the named ``manager`` components pinned to other
        environment variants of the same version — e.g. the reference
        kernels a platform build is checked against."""
        pins, digests = [], []
        for (m, n, v, e), dg in zip(self.pins, self.digests):
            if m == manager and n in envs:
                e = envs[n]
                dg = service.cq(m, n, v, e).digest()
            pins.append((m, n, v, e))
            digests.append(dg)
        return dataclasses.replace(self, pins=tuple(pins),
                                   digests=tuple(digests))


# ---------------------------------------------------------------------------
# Build-plan cache (deployment-service hot path)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BuildPlan:
    """The replayable outcome of one resolution: a version-lock manifest.

    Content-addressed by ``(cir_digest, spec_digest, catalog_epoch,
    overrides)`` — any of these changing means resolution could pick
    different components, so the plan only ever replays for the exact
    deployment it was computed for.
    """
    cir_digest: str
    spec_digest: str
    catalog_epoch: str            # registry content fingerprint (hex)
    pins: Tuple[Tuple[str, str, str, str], ...]
    digests: Tuple[str, ...]

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @staticmethod
    def from_json(s: str) -> "BuildPlan":
        d = json.loads(s)
        d["pins"] = tuple(tuple(p) for p in d["pins"])
        d["digests"] = tuple(d["digests"])
        return BuildPlan(**d)


@dataclasses.dataclass
class PlanCacheStats:
    hits: int = 0
    misses: int = 0
    puts: int = 0
    stale_drops: int = 0      # replays that failed (catalog changed underfoot)
    evictions: int = 0        # LRU drops past max_entries


class BuildPlanCache:
    """Persistent, content-addressed store of build plans.

    In-memory by default; give it a directory ``path`` and plans survive
    process restarts (one JSON file per cache key, written atomically).
    Epoch-based invalidation is structural: the catalog epoch — a
    restart-stable content fingerprint — is part of the key, so a registry
    content change simply never matches old entries.

    One consequence: plans are stored under the *post-resolution* epoch.
    A build whose resolution itself pulls new components from upstream
    (on-demand conversion) therefore looks up at the pre-pull epoch and
    misses once per fresh process; builds against an already-converted
    catalog replay across restarts.

    ``max_entries`` bounds the cache LRU-wise (a long-lived deployment
    service accumulates one entry per (CIR, platform, epoch, overrides)
    forever otherwise): the least-recently-used plan — in memory *and* its
    on-disk file — is evicted past the cap, counted in ``stats.evictions``.
    """

    def __init__(self, path: Optional[str] = None,
                 max_entries: Optional[int] = None):
        if max_entries is not None and max_entries < 1:
            raise ValueError("max_entries must be >= 1 (or None)")
        self.path = path
        self.max_entries = max_entries
        self._plans: "collections.OrderedDict[str, BuildPlan]" = \
            collections.OrderedDict()
        self.stats = PlanCacheStats()
        self._lock = threading.Lock()
        if path:
            os.makedirs(path, exist_ok=True)
            self._load()
            with self._lock:
                self._evict_locked()

    @staticmethod
    def key(cir: CIR, spec: SpecSheet, catalog_epoch: str,
            overrides: Optional[Mapping[str, Any]] = None) -> str:
        blob = json.dumps({
            "cir": cir.digest(),
            "spec": spec.digest(),
            "epoch": catalog_epoch,
            "overrides": dict(overrides or {}),
        }, sort_keys=True, default=str).encode()
        return hashlib.sha256(blob).hexdigest()

    def get(self, key: str) -> Optional[BuildPlan]:
        with self._lock:
            plan = self._plans.get(key)
            if plan is None:
                self.stats.misses += 1
            else:
                self.stats.hits += 1
                self._plans.move_to_end(key)     # LRU refresh
            return plan

    def put(self, key: str, plan: BuildPlan) -> None:
        with self._lock:
            self._plans[key] = plan
            self._plans.move_to_end(key)
            self.stats.puts += 1
            if self.path:
                fn = os.path.join(self.path, key + ".json")
                tmp = fn + ".tmp"
                with open(tmp, "w") as f:
                    f.write(plan.to_json())
                os.replace(tmp, fn)
            self._evict_locked()

    def _evict_locked(self) -> None:
        """Drop least-recently-used plans past ``max_entries``; holds _lock."""
        if self.max_entries is None:
            return
        while len(self._plans) > self.max_entries:
            old, _plan = self._plans.popitem(last=False)
            self.stats.evictions += 1
            if self.path:
                try:
                    os.remove(os.path.join(self.path, old + ".json"))
                except OSError:
                    pass

    def drop(self, key: str) -> None:
        with self._lock:
            self._plans.pop(key, None)
            self.stats.stale_drops += 1
            if self.path:
                try:
                    os.remove(os.path.join(self.path, key + ".json"))
                except OSError:
                    pass

    def _load(self) -> None:
        def mtime(fn: str) -> float:
            try:
                return os.path.getmtime(os.path.join(self.path, fn))
            except OSError:
                return 0.0
        # oldest first, so insertion order approximates on-disk recency and
        # the LRU cap evicts the stalest entries after a restart
        for fn in sorted(os.listdir(self.path), key=mtime):
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.path, fn)) as f:
                    self._plans[fn[:-len(".json")]] = BuildPlan.from_json(
                        f.read())
            except (OSError, ValueError, KeyError, TypeError):
                # a torn/corrupt entry is a miss, not a fatal error — the
                # plan will be recomputed and rewritten atomically
                continue

    def __len__(self) -> int:
        return len(self._plans)


# ---------------------------------------------------------------------------
# Build report (feeds every benchmark)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BuildReport:
    cir_name: str
    platform_id: str
    resolve_s: float = 0.0
    fetch_s: float = 0.0            # wall time of the (pipelined) fetch stage
    assemble_s: float = 0.0
    bytes_cir: int = 0
    bytes_fetched: int = 0          # component-level bytes of missed components
    bytes_total_components: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    n_components: int = 0
    restarts: int = 0
    locked: bool = False
    plan_cache_hit: bool = False    # resolution skipped via build-plan cache
    compile_s: float = 0.0
    n_compiled: int = 0
    # -- chunk-level delta-fetch columns (ChunkedComponentStore path) -------
    chunked_fetch: bool = False     # fetch ran through the chunk planner
    bytes_delta_fetched: int = 0    # wire bytes: missing chunks only
    chunks_hit: int = 0             # chunks already present locally
    chunks_missed: int = 0          # chunks this build fetched (and paid for)
    chunks_waited: int = 0          # chunks in flight under another build
    fetch_concurrency: int = 1      # thread-pool width the engine used
    fetch_serial_s: float = 0.0     # sum of per-task fetch times (no overlap)
    fetch_wait_timeouts: int = 0    # in-flight waits that hit the backstop
    # -- event-driven orchestration columns (BuildOrchestrator) -------------
    orchestrated: bool = False      # stages overlapped via readiness events
    critical_path_s: float = 0.0    # measured wall: build start -> READY
    overlap_s: float = 0.0          # barrier-stage sum minus critical path
    stage_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    #                               ^ per-lifecycle-stage wall offsets
    listener_errors: int = 0        # advisory readiness-callback raises
    # -- fleet compile-cache columns (compiled-artifact components) ---------
    # Artifact bytes are accounted separately from the resolved-content
    # columns above: cache-hit and cache-miss builds of the same content
    # keep identical bytes_fetched / bytes_delta_fetched / chunk counts,
    # and NodeTraffic.bytes_total still equals bytes_delta_fetched.
    compile_cache_hit: bool = False  # executable restored from fleet cache
    compile_skips: int = 0           # step compiles skipped via the cache
    artifact_bytes_fetched: int = 0  # compiled-artifact wire bytes (peers)
    artifact_chunks_fetched: int = 0
    artifact_bytes_published: int = 0  # locally-compiled bytes stored
    # -- performance-portable IR columns (core/irmodule.py, docs §13) --------
    # Accounted exactly like artifacts: never in the resolved-content
    # columns, so with the split disabled every column below is zero and
    # the whole report is byte-identical to a pre-§13 build.
    ir_enabled: bool = False         # builder ran with the IR split on
    ir_shared_bytes: int = 0         # shared-IR bytes sourced (store/peers)
    ir_bytes_published: int = 0      # IR lowered locally + published
    platform_tail_bytes: int = 0     # per-platform bytes (tail + autotune)
    autotune_bytes_fetched: int = 0  # autotune-table wire bytes (peers)
    autotune_bytes_published: int = 0
    # -- trust & integrity columns (core/integrity.py, docs §12) -------------
    attestation_verified: bool = False  # signed manifest checked at plan time

    @property
    def bytes_wire_fetched(self) -> int:
        """Bytes that actually cross the link: the chunk delta when chunk
        accounting ran, the full missed-component bytes otherwise."""
        return self.bytes_delta_fetched if self.chunked_fetch \
            else self.bytes_fetched

    def network_time(self, bandwidth_bps: float) -> float:
        """Simulated link time: CIR pull + parallel delta fetch."""
        return (self.bytes_cir + self.bytes_wire_fetched) * 8.0 / bandwidth_bps

    def lazy_build_time(self, bandwidth_bps: float) -> float:
        """Deploy wall time at a simulated link — the orchestrator's actual
        critical path, not an analytic stage sum.

        ``overlap_s`` is the *measured* time the event-driven pipeline ran
        stages concurrently (assemble/jit under the asset tail, READY not
        gated on first-weight-use content), so the stage sum is credited by
        exactly what the orchestrator achieved; barrier builds have
        ``overlap_s == 0`` and reduce to the legacy analytic form.
        Resolution still overlaps the CIR pull + delta fetch on the link
        (paper §4.3: converters split metadata from payload).
        """
        stage_sum = self.fetch_s + self.assemble_s + self.compile_s
        return max(self.resolve_s, self.network_time(bandwidth_bps)) \
            + stage_sum - min(self.overlap_s, stage_sum)

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["bytes_wire_fetched"] = self.bytes_wire_fetched
        return d


# ---------------------------------------------------------------------------
# Fetch engine (stage 2): planner + bounded-concurrency executor
# ---------------------------------------------------------------------------

# Assembly needs the model family and runtime step builders first; kernels
# and plans next; the platform env is usually host-seeded; the weight tail
# (assets) lands last so assemble can start before it finishes.
_FETCH_PRIORITY = {"model": 0, "runtime": 0, "kernel": 1, "parallel": 1,
                   "opt": 2, "data": 2, "env": 3, "asset": 4}


def _partition(items: Sequence, n: int) -> List[List]:
    """Split ``items`` into at most ``n`` contiguous, near-equal groups."""
    n = max(1, min(n, len(items)))
    k, m = divmod(len(items), n)
    out, i = [], 0
    for j in range(n):
        step = k + (1 if j < m else 0)
        if step:
            out.append(list(items[i:i + step]))
            i += step
    return out


class FetchEngine:
    """Concurrent, pipelined, *streaming* fetch executor for the builder.

    Against a ``ChunkedComponentStore`` it plans a missing-chunk delta per
    component (priority order), stripes each component's claimed chunks
    across a bounded thread pool (range-parallel blob pulls), charges only
    delta bytes through ``service.fetch_chunks``, and waits on chunks other
    builds have in flight — the singleflight guarantee that a fleet never
    fetches the same chunk twice, even mid-transfer.

    The fetch is a streaming stage: given a ``ComponentReadiness`` tracker
    it signals each component ``ready`` the moment its content is *proven*
    present — owned stripes committed, awaited chunks landed (orphans of an
    aborted claimer reclaimed and re-pulled) — in priority order, so the
    ``BuildOrchestrator`` starts assembly while the weight-asset tail is
    still on the wire.  Accounting is independent of the overlap: byte and
    chunk columns are identical with or without a readiness consumer.

    Link time is modelled behind a **transport** (``upstream_transfer`` /
    ``peer_transfer`` / ``backoff``): ``simulate_bps`` installs the
    legacy real-sleep ``WallClockTransport`` (each stripe sleeps
    ``bytes / bps`` so benchmarks can observe real wall-clock overlap);
    a ``repro.core.simnet.SimTransport`` advances a *virtual* clock
    instead — milliseconds of wall time for a WAN-sized fleet — and may
    raise fault errors.  Accounting is identical under any transport (or
    none): the transport replaces only the sleeps, never the
    ``service.fetch_chunks`` charges or the claim/commit protocol.
    Plain ``LocalComponentStore``s keep the legacy serial
    whole-component path.

    ``peering`` is the optional chunk-source router of a fleet-topology
    node (``repro.deploy.topology.NodePeering``): when set, every claimed
    stripe is transferred through ``peering.fetch_stripe`` — which may pull
    chunks from peer nodes instead of the upstream registry and does its
    own per-link simulated sleeps — and every committed stripe is announced
    through ``peering.announce_chunks`` so other nodes can source from this
    one.  Chunk/byte accounting in the ``BuildReport`` is identical with or
    without a router; only the upstream-vs-peer split (tracked by the
    router) changes.
    """

    def __init__(self, store: LocalComponentStore,
                 service: UniformComponentService,
                 max_workers: int = 8,
                 simulate_bps: Optional[float] = None,
                 peering: Optional[Any] = None,
                 transport: Optional[Any] = None):
        self.store = store
        self.service = service
        self.max_workers = max(1, max_workers)
        self.simulate_bps = simulate_bps
        self.peering = peering
        if transport is None and simulate_bps:
            transport = WallClockTransport(default_bps=simulate_bps)
        self.transport = transport

    def fetch(self, comps: Sequence[UniformComponent],
              report: BuildReport,
              readiness: Optional[ComponentReadiness] = None) -> None:
        t0 = time.perf_counter()
        order = sorted(range(len(comps)),
                       key=lambda i: (_FETCH_PRIORITY.get(comps[i].manager, 3),
                                      i))
        ordered = [comps[i] for i in order]
        try:
            if isinstance(self.store, ChunkedComponentStore):
                self._fetch_chunked(ordered, report, readiness)
            else:
                self._fetch_serial(ordered, report, readiness)
        finally:
            report.fetch_s = time.perf_counter() - t0

    # -- legacy component-granularity path --------------------------------
    def _fetch_serial(self, comps: Sequence[UniformComponent],
                      report: BuildReport,
                      readiness: Optional[ComponentReadiness] = None) -> None:
        for c in comps:
            report.bytes_total_components += c.size_bytes
            t = time.perf_counter()
            # put() decides hit-vs-miss under the store lock, so concurrent
            # builds charge each component's bytes exactly once.
            if self.store.put(c):
                self.service.fetch(c)
                report.bytes_fetched += c.size_bytes
                report.cache_misses += 1
            else:
                report.cache_hits += 1
            report.fetch_serial_s += time.perf_counter() - t
            if readiness is not None:
                readiness.mark_ready(c)

    # -- chunk-delta path -------------------------------------------------
    def _fetch_chunked(self, comps: Sequence[UniformComponent],
                       report: BuildReport,
                       readiness: Optional[ComponentReadiness] = None) -> None:
        report.chunked_fetch = True
        plans: List[FetchPlan] = []
        for c in comps:
            report.bytes_total_components += c.size_bytes
            plan = self.store.plan_fetch(c)
            if plan.component_new or plan.rescan:
                # a rescan repairs content an aborted build left behind:
                # it does real transfer work, so it counts as a miss (and
                # keeps bytes_delta_fetched <= bytes_fetched)
                report.cache_misses += 1
                report.bytes_fetched += c.size_bytes
            else:
                report.cache_hits += 1
            report.chunks_hit += len(plan.hits)
            report.chunks_waited += len(plan.waits)
            plans.append(plan)

        width = max(1, min(self.max_workers,
                           sum(len(p.claimed) for p in plans)))
        report.fetch_concurrency = width
        # stripe each component's claim across the pool, in priority order
        stripes_of: Dict[int, List[List]] = {id(p): [] for p in plans}
        for plan in plans:
            for stripe in _partition(plan.claimed, width):
                stripes_of[id(plan)].append(stripe)

        def pull(c: UniformComponent, stripe: List) -> Tuple[int, int, float]:
            t = time.perf_counter()
            nbytes = sum(ch.size for ch, _ev in stripe)
            try:
                if self.peering is not None:
                    # fleet-topology node: the router picks the source per
                    # chunk (peer vs upstream) and does its own link sleeps
                    self.peering.fetch_stripe(c, stripe)
                else:
                    if self.transport is not None:
                        self.transport.upstream_transfer(
                            nbytes, bps=self.simulate_bps)
                    self.service.fetch_chunks(c, nbytes, len(stripe))
                self.store.commit_chunks(stripe, component=c)
            except BaseException:
                self.store.abort_chunks(stripe, component=c)
                raise
            if self.peering is not None:
                self.peering.announce_chunks([ch for ch, _ev in stripe])
            return nbytes, len(stripe), time.perf_counter() - t

        # shared wait budget for content another build is pulling — both
        # chunk-level waits and same-digest component barriers.  Scaled to
        # the awaited PLUS owned bytes when transfers are simulated: the
        # deadline starts before this build's own stripe pulls run (each
        # component finishes as its stripes land, streaming), so our own
        # simulated transfer time must not eat the waiters' budget, and a
        # legitimate slow-link stripe must not be declared dead.  The fixed
        # floor only guards against a claimer that died without
        # commit/abort.
        awaited_bytes = sum(ch.size for p in plans for ch, _ev in p.waits) \
            + sum(p.component.size_bytes for p in plans if p.barriers)
        owned_bytes = sum(ch.size for p in plans for ch, _ev in p.claimed)
        budget = CLAIM_WAIT_TIMEOUT_S
        if self.simulate_bps:
            budget += 2.0 * (awaited_bytes + owned_bytes) / self.simulate_bps
        deadline = time.monotonic() + budget

        def finish(plan: FetchPlan) -> None:
            """Prove one component's content present, then signal ready.

            Waits out transfers other builds own; if content we waited on
            was aborted by its claimer — a chunk-level wait or a whole
            component barrier — we re-claim and fetch it ourselves: a
            waiter must never finish with a hole another build's failure
            left behind.  Anything we cannot prove complete (still in
            flight under a third build, or a timed-out barrier) marks OUR
            digest incomplete, so the next build of it re-verifies — no
            permanent present-with-holes state.
            """
            timed_out = False
            for ev in [ev for _ch, ev in plan.waits] + plan.barriers:
                if not ev.wait(max(0.0, deadline - time.monotonic())):
                    report.fetch_wait_timeouts += 1
                    timed_out = True
            if plan.waits:
                orphans = self.store.reclaim_chunks([ch for ch, _ev
                                                     in plan.waits])
            elif plan.barriers:
                orphans = self.store.reclaim_component(plan.component)
            else:
                orphans = []
            if orphans:
                report.bytes_delta_fetched += \
                    sum(ch.size for ch, _ev in orphans)
                report.chunks_missed += len(orphans)
                pull(plan.component, orphans)
            holey = any(not self.store.has_chunk(ch.id)
                        for ch, _ev in plan.waits) or \
                (plan.barriers and timed_out)
            if holey:
                self.store.mark_incomplete(plan.component)
            if readiness is not None:
                readiness.mark_ready(plan.component)

        def account(res: Tuple[int, int, float]) -> None:
            nbytes, nchunks, dt = res
            report.bytes_delta_fetched += nbytes
            report.chunks_missed += nchunks
            report.fetch_serial_s += dt

        def release_from(pi: int, si: int) -> None:
            """Failure cleanup from plan ``pi``, stripe ``si`` on: abort the
            never-executed stripes' claims (or sibling builds block on
            events that can't fire) and mark every plan whose awaited
            content was never verified incomplete, so the next build of
            those digests re-scans instead of trusting a component hit."""
            for s2 in stripes_of[id(plans[pi])][si:]:
                self.store.abort_chunks(s2, component=plans[pi].component)
            for p2 in plans[pi + 1:]:
                for s2 in stripes_of[id(p2)]:
                    self.store.abort_chunks(s2, component=p2.component)
            for p2 in plans[pi:]:
                if p2.waits or p2.barriers:
                    self.store.mark_incomplete(p2.component)

        n_stripes = sum(len(s) for s in stripes_of.values())
        if width == 1 or n_stripes <= 1:
            for pi, plan in enumerate(plans):
                stripes = stripes_of[id(plan)]
                for si, stripe in enumerate(stripes):
                    try:
                        account(pull(plan.component, stripe))
                    except BaseException:
                        release_from(pi, si + 1)
                        raise
                try:
                    finish(plan)
                except BaseException:
                    # the orphan-repair re-pull can fail too: its own claim
                    # aborts inside pull(), the rest is released here
                    release_from(pi, len(stripes))
                    raise
        else:
            # every stripe is submitted eagerly (priority order == queue
            # order), so each runs pull() and aborts its own claim on
            # failure; components complete — and signal readiness — in
            # priority order as their last stripe lands
            with ThreadPoolExecutor(max_workers=width) as pool:
                futs = {id(p): [pool.submit(pull, p.component, s)
                                for s in stripes_of[id(p)]]
                        for p in plans}
                first_err: Optional[BaseException] = None
                for plan in plans:
                    results, failed = [], False
                    for f in futs[id(plan)]:
                        try:
                            results.append(f.result())
                        except BaseException as e:  # noqa: BLE001
                            failed = True
                            if first_err is None:
                                first_err = e
                    # every committed-and-charged stripe is accounted, even
                    # on a failing build — the partial report feeds fleet
                    # byte totals, which must not understate real transfers
                    for res in results:
                        account(res)
                    if first_err is None and not failed:
                        try:
                            finish(plan)
                        except BaseException as e:  # noqa: BLE001
                            # keep draining later plans' futures so their
                            # committed stripes are still accounted
                            first_err = e
                            if plan.waits or plan.barriers:
                                self.store.mark_incomplete(plan.component)
                    elif plan.waits or plan.barriers:
                        # never verified this plan's awaited content
                        self.store.mark_incomplete(plan.component)
                if first_err is not None:
                    raise first_err


# ---------------------------------------------------------------------------
# Container instance
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ContainerInstance:
    """The assembled, runnable unit, with an explicit lifecycle.

    ``model`` is the family-assembled Model object (init/apply + sharding
    rules); ``entry`` holds the built entrypoint callables (train_step or
    prefill/decode) produced by the runtime components.  The launcher gives
    it a mesh to produce shardings, lower and compile.

    The instance exists from the moment resolution pins its components
    (stage PLANNED); the orchestrator advances it through FETCHING →
    ASSEMBLED → COMPILED → READY → COMPLETE as per-component readiness
    gates fire.  ``wait(stage)`` blocks until a stage is reached (READY =
    deployable, the asset tail may still stream; ``wait("weights")`` is
    the first-weight-use gate) and re-raises the build's error if it
    failed first.  ``model``/``entry`` are populated at ASSEMBLED; the
    fetch accounting in ``report`` is final at COMPLETE.
    """
    cir: CIR
    spec: SpecSheet
    bundle: ComponentBundle
    model: Any
    entry: Dict[str, Callable]
    lock: Lockfile
    report: BuildReport
    lifecycle: Lifecycle = dataclasses.field(default_factory=Lifecycle,
                                             repr=False, compare=False)
    # fleet compile-cache key of the staged executable (set by the compile
    # stage when a CompileCache is wired; snapshot/restore round-trips it)
    compile_key: Optional[str] = dataclasses.field(default=None,
                                                   compare=False)

    @property
    def arch_id(self) -> str:
        return self.cir.name

    @property
    def stage(self) -> str:
        return self.lifecycle.stage

    def wait(self, stage: str = "complete",
             timeout: Optional[float] = None) -> "ContainerInstance":
        """Block until ``stage`` is reached; returns self for chaining."""
        self.lifecycle.wait(stage, timeout)
        return self


# Entry keys the compile stage treats as per-mesh step functions.
_STEP_ENTRIES = ("train_step", "prefill", "decode_step")


class LazyBuilder:
    """The staged deployment pipeline: resolve → fetch → assemble → compile.

    The stages are no longer strict barriers: after resolution, a
    ``BuildOrchestrator`` drives fetch / assemble / compile off
    per-component readiness events (``BuildGraph`` gates), so assembly and
    jit-staging overlap the weight-asset tail and the instance is READY —
    deployable — before first-weight-use content has landed.  Every stage
    is still an explicit method so deployment services (FleetDeployer,
    launchers) can run, time and skip stages individually; a shared
    ``BuildPlanCache`` (created per-builder when not given) short-circuits
    the resolve stage for repeat deployments.
    """

    def __init__(self, service: UniformComponentService,
                 store: Optional[LocalComponentStore] = None,
                 link_bandwidth_bps: float = 500e6,
                 plan_cache: Optional[BuildPlanCache] = None,
                 fetch_workers: int = 8,
                 fetch_simulate_bps: Optional[float] = None,
                 build_graph: Optional[BuildGraph] = None,
                 peering: Optional[Any] = None,
                 fetch_transport: Optional[Any] = None,
                 compile_cache: Optional[CompileCache] = None,
                 signer: Optional[Signer] = None,
                 require_attestation: bool = False,
                 ir_components: bool = False):
        self.service = service
        # manifest-attestation policy (docs §12): a signer makes this
        # builder able to verify (and mint) attestations; require_attestation
        # hard-fails any build that arrives without one — verified at plan
        # time, before a single fetch is scheduled.
        self.signer = signer
        self.require_attestation = require_attestation
        if require_attestation and signer is None:
            raise ValueError("require_attestation=True needs a signer")
        self.store = store if store is not None else ChunkedComponentStore()
        self.link_bandwidth_bps = link_bandwidth_bps
        self.plan_cache = BuildPlanCache() if plan_cache is None else plan_cache
        # fleet-wide compiled-executable index (opt-in: None disables the
        # cache and the compile stage behaves exactly as before)
        self.compile_cache = compile_cache
        # performance-portable split (docs §13, opt-in): compile as a
        # shared platform-neutral IR module plus a per-platform artifact
        # tail + autotune table, instead of one monolithic executable.
        # Off by default so every pre-§13 accounting identity holds.
        self.ir_components = ir_components
        self.build_graph = build_graph if build_graph is not None \
            else BuildGraph()
        self.fetch_engine = FetchEngine(self.store, service,
                                        max_workers=fetch_workers,
                                        simulate_bps=fetch_simulate_bps,
                                        peering=peering,
                                        transport=fetch_transport)
        # per-component readiness listeners the orchestrator wires into
        # every build's ComponentReadiness (e.g. a fleet node announcing
        # proven-present content to the PeerIndex)
        self.readiness_listeners: List[Callable[[UniformComponent], None]] = []

    # -- stage 1: resolve (or replay a cached plan) ---------------------
    def _stage_resolve(self, cir: CIR, spec: SpecSheet,
                       ctx0: Dict[str, Any],
                       overrides: Optional[Mapping[str, Any]],
                       report: BuildReport,
                       use_plan_cache: bool) -> Tuple[Resolution, BuildPlan]:
        t0 = time.perf_counter()
        resolution: Optional[Resolution] = None
        plan: Optional[BuildPlan] = None
        cache = self.plan_cache if use_plan_cache else None

        if cache is not None:
            key = cache.key(cir, spec, self.service.catalog_epoch, overrides)
            plan = cache.get(key)
            if plan is not None:
                try:
                    resolution = resolution_from_pins(
                        plan.pins, self.service, ctx0, plan.digests)
                    report.plan_cache_hit = True
                except (ResolutionError, RegistryError):
                    # catalog changed under an epoch collision — drop + redo
                    cache.drop(key)
                    plan = None

        if resolution is None:
            resolution = uniform_dependency_resolution(
                cir.deps, self.service, ctx0,
                cached_digests=self.store.digests(),
                link_bandwidth=self.link_bandwidth_bps / 8.0)
            report.restarts = resolution.restarts
            plan = BuildPlan(
                cir_digest=cir.digest(), spec_digest=spec.digest(),
                catalog_epoch=self.service.catalog_epoch,
                pins=resolution.pins(), digests=resolution.pin_digests())
            if cache is not None:
                # key at the *post-resolution* epoch: upstream pulls during
                # resolution register components and bump the epoch
                cache.put(cache.key(cir, spec, plan.catalog_epoch, overrides),
                          plan)

        report.resolve_s = time.perf_counter() - t0
        report.n_components = len(resolution.components)
        return resolution, plan

    # -- stage 2: fetch runs through self.fetch_engine, driven by the
    # BuildOrchestrator so readiness events stream into the stage gates --

    # -- stage 3: assemble ----------------------------------------------
    def _stage_assemble(self, cir: CIR, spec: SpecSheet,
                        bundle: ComponentBundle, mesh: Any,
                        report: BuildReport, assemble: bool
                        ) -> Tuple[Any, Dict[str, Callable]]:
        t0 = time.perf_counter()
        model, entry = (None, {})
        if assemble:
            model, entry = self._assemble(cir, spec, bundle, mesh)
        report.assemble_s = time.perf_counter() - t0
        return model, entry

    # -- stage 4: compile (stage step entrypoints for the mesh) ---------
    def _stage_compile(self, entry: Dict[str, Callable],
                       report: BuildReport,
                       inst: Optional[ContainerInstance] = None
                       ) -> Dict[str, Callable]:
        """Wrap the step entrypoints in ``jax.jit``, consulting the fleet
        compile cache.

        Compilation itself stays lazy (first call traces + compiles for the
        actual argument shapes — AOT lowering needs them), but the staged
        callables are what launchers hand straight to the mesh.

        When a ``CompileCache`` is wired and the build exposes its lockfile
        (``inst``), the stage derives the fleet-wide cache key and either
        restores the compiled executable — landing its content-addressed
        artifact component from peers through the ordinary chunk path, and
        counting the skipped compiles in ``report.compile_skips`` — or pays
        the (virtual) compile cost and publishes the artifact for every
        peer of the platform class.  Both paths satisfy the COMPILED
        lifecycle stage; the resolved-content byte accounting is identical
        hit-vs-miss (artifact bytes live in their own report columns).
        """
        t0 = time.perf_counter()
        import jax
        out = dict(entry)
        names = tuple(n for n in _STEP_ENTRIES if callable(out.get(n)))

        cache = self.compile_cache
        if cache is not None and inst is not None and names:
            key = compile_cache_key(inst.lock, inst.spec, names)
            inst.compile_key = key
            art = cache.get(key)
            if art is not None and self._ingest_artifact(art, report):
                report.compile_cache_hit = True
                report.compile_skips += len(names)
                cache.stats.compile_skips += len(names)
                if self.ir_components:
                    self._ingest_autotune(art, report)
            elif self.ir_components:
                # §13 split: the per-platform tail can only be lowered
                # from the shared IR module, so the compile is gated on
                # IR-readiness — fetch the module from the fleet or
                # derive it locally before the tail compile may start
                self._ensure_ir(inst.lock, names, report)
                self._model_compile_cost(
                    len(names), TAIL_COMPILE_VIRTUAL_S_PER_ENTRY)
                auto = autotune_component(key, inst.spec, names)
                art = CompiledArtifact(
                    key=key,
                    component=artifact_component(key, names, tail=True),
                    entry_names=names,
                    compile_s=TAIL_COMPILE_VIRTUAL_S_PER_ENTRY * len(names),
                    autotune=auto)
                self._publish_artifact(art, report)
                self._model_compile_cost(
                    len(names), AUTOTUNE_VIRTUAL_S_PER_ENTRY)
                report.autotune_bytes_published += self._commit_local(auto)
                cache.put(art)
            else:
                # miss (or no reachable copy of the bytes): pay the
                # platform compile, then publish the executable fleet-wide
                self._model_compile_cost(len(names))
                art = CompiledArtifact(
                    key=key, component=artifact_component(key, names),
                    entry_names=names,
                    compile_s=COMPILE_VIRTUAL_S_PER_ENTRY * len(names))
                self._publish_artifact(art, report)
                cache.put(art)
            if self.ir_components:
                report.ir_enabled = True
                # every platform-specific byte this build moved or made:
                # the tail executable plus its autotune table
                report.platform_tail_bytes = (
                    report.artifact_bytes_fetched
                    + report.artifact_bytes_published
                    + report.autotune_bytes_fetched
                    + report.autotune_bytes_published)

        for name in names:
            out[name] = jax.jit(out[name])
            report.n_compiled += 1
        report.compile_s = time.perf_counter() - t0
        return out

    def _model_compile_cost(self, n_entries: int,
                            s_per_entry: float =
                            COMPILE_VIRTUAL_S_PER_ENTRY) -> None:
        """Advance the virtual clock by the modeled XLA compile cost.

        Only the discrete-event transport observes it (wall-clock builds
        measure the real jit wall instead), so real deployments and legacy
        benchmarks are unaffected.
        """
        tr = self.fetch_engine.transport
        if isinstance(tr, SimTransport):
            tr.backoff(s_per_entry * n_entries)

    def _ingest_peer_component(self, comp: UniformComponent,
                               stripe_method: str = "fetch_artifact_stripe"
                               ) -> Optional[Tuple[int, int]]:
        """Land a derived component's bytes locally, *peers only*.

        The shared body of every derived-component ingest (compiled
        executables, §13 platform tails, IR modules, autotune tables):
        resident content is a free hit; missing chunks are sourced from
        linked peers only — derived components are born on fleet nodes,
        the upstream registry never stores them — through the same claim /
        commit / abort singleflight protocol as every other component.
        ``stripe_method`` names the ``NodePeering`` transfer so each kind
        lands in its own ``NodeTraffic`` columns.  Returns
        ``(wire_bytes, chunks)`` — ``(0, 0)`` for resident content — or
        ``None`` when no reachable copy exists.
        """
        store = self.store
        if not isinstance(store, ChunkedComponentStore):
            return (0, 0) if store.has(comp) else None
        if store.has(comp) and not store.missing_chunks(comp):
            return (0, 0)
        peering = self.fetch_engine.peering
        fetch = getattr(peering, stripe_method, None)
        plan = store.plan_fetch(comp)
        fetched = (0, 0)
        try:
            if plan.claimed:
                if fetch is None or not fetch(comp, plan.claimed):
                    store.abort_chunks(plan.claimed, component=comp)
                    store.mark_incomplete(comp)
                    return None
                store.commit_chunks(plan.claimed, component=comp)
                fetched = (sum(ch.size for ch, _ev in plan.claimed),
                           len(plan.claimed))
        except BaseException:
            store.abort_chunks(plan.claimed, component=comp)
            raise
        for ev in [ev for _ch, ev in plan.waits] + list(plan.barriers):
            ev.wait(CLAIM_WAIT_TIMEOUT_S)
        if store.missing_chunks(comp):
            store.mark_incomplete(comp)
            return None
        if peering is not None:
            peering.announce_chunks(store.chunks_of(comp))
        return fetched

    def _ingest_artifact(self, art: CompiledArtifact,
                         report: BuildReport) -> bool:
        """Land a cached executable's bytes locally; False means recompile.

        Artifact wire bytes land in ``report.artifact_bytes_fetched``,
        never in the resolved-content columns.  A §13 platform tail
        (``context["tail"]``) rides the tail stripe so ``NodeTraffic``
        can additionally prove the bytes were platform-specific.
        """
        comp = art.component
        method = "fetch_tail_stripe" if comp.context.get("tail") \
            else "fetch_artifact_stripe"
        res = self._ingest_peer_component(comp, method)
        if res is None:
            return False
        report.artifact_bytes_fetched += res[0]
        report.artifact_chunks_fetched += res[1]
        return True

    def _commit_local(self, comp: UniformComponent) -> int:
        """Store a locally-produced component (a local ingest: no wire
        bytes) and announce its chunks so peers can source it.  Returns
        the bytes committed."""
        store = self.store
        if not isinstance(store, ChunkedComponentStore):
            return comp.size_bytes if store.put(comp) else 0
        plan = store.plan_fetch(comp)
        nbytes = 0
        try:
            if plan.claimed:
                store.commit_chunks(plan.claimed, component=comp)
                nbytes = sum(ch.size for ch, _ev in plan.claimed)
        except BaseException:
            store.abort_chunks(plan.claimed, component=comp)
            raise
        peering = self.fetch_engine.peering
        if peering is not None:
            peering.announce_chunks(store.chunks_of(comp))
        return nbytes

    def _publish_artifact(self, art: CompiledArtifact,
                          report: BuildReport) -> None:
        """Store the locally-compiled executable and announce its chunks
        so peers can source it."""
        report.artifact_bytes_published += self._commit_local(art.component)

    def _ensure_ir(self, lock: Lockfile, entry_names: Sequence[str],
                   report: BuildReport) -> UniformComponent:
        """The §13 IR-readiness gate: land the shared IR module locally.

        Resident IR is a free hit; otherwise linked peers are tried first
        (the module is lowered once fleet-wide and only ever copied
        afterwards, riding ``NodePeering.fetch_ir_stripe``); only when no
        reachable copy exists does this node pay the lowering cost and
        publish the module for the rest of the fleet.  Shared-IR bytes
        land in ``report.ir_shared_bytes`` / ``ir_bytes_published``,
        never in the resolved-content columns.
        """
        comp = ir_module_component(lock, entry_names)
        res = self._ingest_peer_component(comp, "fetch_ir_stripe")
        if res is not None:
            report.ir_shared_bytes += comp.size_bytes
            return comp
        self._model_compile_cost(len(entry_names),
                                 IR_LOWER_VIRTUAL_S_PER_ENTRY)
        report.ir_bytes_published += self._commit_local(comp)
        return comp

    def _ingest_autotune(self, art: CompiledArtifact,
                         report: BuildReport) -> None:
        """Land the restored tail's Pallas autotune table (§13).

        Peer-first like the tail itself; when no peer still holds the
        table the node re-tunes locally (a small virtual cost — tables
        are cheap to regenerate, unlike compiles) and re-publishes.
        """
        auto = art.autotune
        if auto is None:
            return
        res = self._ingest_peer_component(auto, "fetch_tail_stripe")
        if res is not None:
            report.autotune_bytes_fetched += res[0]
            return
        self._model_compile_cost(len(art.entry_names),
                                 AUTOTUNE_VIRTUAL_S_PER_ENTRY)
        report.autotune_bytes_published += self._commit_local(auto)

    # -- trust & integrity (core/integrity.py, docs §12) ----------------
    def _check_attestation(self, cir: CIR, lock: Lockfile,
                           attestation: Optional[Attestation],
                           report: BuildReport) -> None:
        """The plan-time attestation gate: runs after the lock is known and
        BEFORE the orchestrator schedules any fetch.  Hard-fails
        (``AttestationError``) on a missing-but-required or invalid
        envelope; sets ``report.attestation_verified`` on success."""
        if attestation is None:
            if self.require_attestation:
                raise AttestationError(
                    f"builder requires a signed manifest but none was "
                    f"supplied for {cir.name}@{lock.platform_id} — "
                    f"refusing to schedule fetch")
            return
        if self.signer is None:
            raise AttestationError(
                "an attestation was supplied but this builder has no "
                "signer to verify it with")
        verify_attestation(cir, lock, attestation, self.signer)
        report.attestation_verified = True

    def attest(self, inst: ContainerInstance) -> Attestation:
        """Sign an instance's manifest (its CIR + per-platform lock) with
        this builder's signer — the pre-build side of the §12 handshake."""
        if self.signer is None:
            raise AttestationError("builder has no signer configured")
        return _sign_manifest(inst.cir, inst.lock, self.signer)

    def sbom(self, inst: ContainerInstance) -> Dict[str, Any]:
        """CycloneDX-shaped SBOM of the instance's resolved dependency
        closure (R-096), with chunk counts from this builder's store when
        it is chunk-addressed."""
        counts: Dict[str, int] = {}
        if isinstance(self.store, ChunkedComponentStore):
            for c in inst.bundle.components():
                counts[c.digest()] = len(self.store.chunks_of(c))
        return make_sbom(inst.cir, inst.lock, inst.bundle.resolution,
                         chunk_counts=counts)

    # ------------------------------------------------------------------
    def build(self, cir: CIR, spec: SpecSheet,
              mesh: Any = None,
              overrides: Optional[Mapping[str, Any]] = None,
              assemble: bool = True,
              compile_steps: bool = False,
              use_plan_cache: bool = True,
              overlap: bool = True,
              block: bool = True,
              attestation: Optional[Attestation] = None
              ) -> ContainerInstance:
        """Run the full pipeline: resolve, then orchestrated
        fetch / assemble / compile off per-component readiness.

        ``overlap=False`` runs the legacy barrier pipeline (each stage
        waits for the previous to fully finish) — accounting is identical,
        only wall-clock differs.  ``block=False`` returns the instance as
        soon as its components are pinned (stage PLANNED/FETCHING); callers
        observe progress through ``instance.wait(stage)``, which also
        re-raises any build error.
        """
        t0 = time.perf_counter()
        report = BuildReport(cir_name=cir.name, platform_id=spec.platform_id,
                             bytes_cir=cir.size_bytes())

        # inspect platform → building context
        ctx0 = spec.context()
        ctx0["entrypoint"] = cir.entrypoint
        if overrides:
            ctx0.update(overrides)

        resolution, plan = self._stage_resolve(cir, spec, ctx0, overrides,
                                               report, use_plan_cache)
        lock = Lockfile(
            cir_digest=cir.digest(), platform_id=spec.platform_id,
            seed=cir.seed, pins=plan.pins, digests=plan.digests)
        # plan-time gate: the attested manifest must match what resolution
        # just produced — a hard fail here means nothing was fetched
        self._check_attestation(cir, lock, attestation, report)
        bundle = ComponentBundle(resolution)
        inst = ContainerInstance(cir=cir, spec=spec, bundle=bundle,
                                 model=None, entry={}, lock=lock,
                                 report=report)
        BuildOrchestrator(self, self.build_graph).start(
            inst, resolution, mesh=mesh, assemble=assemble,
            compile_steps=compile_steps, t0=t0, record_build=True,
            overlap=overlap, block=block)
        return inst

    # ------------------------------------------------------------------
    def build_from_lock(self, cir: CIR, lock: Lockfile, spec: SpecSheet,
                        mesh: Any = None,
                        assemble: bool = True,
                        compile_steps: bool = False,
                        overlap: bool = True,
                        block: bool = True,
                        attestation: Optional[Attestation] = None
                        ) -> ContainerInstance:
        """CIR-locked rebuild: CQ-only (no VS/ES), deterministic and
        bit-identical (paper §3.3, §5.4 CIR-locked)."""
        if lock.cir_digest != cir.digest():
            raise ValueError("lockfile does not match this CIR")
        if lock.platform_id != spec.platform_id:
            # locks are per-platform (paper §4.2): replaying one platform's
            # pins under another's host context would silently merge
            # incompatible context contributions the resolver would reject
            raise ValueError(
                f"lockfile is for platform {lock.platform_id!r}, "
                f"not {spec.platform_id!r} — re-run a full lazy-build")
        report = BuildReport(cir_name=cir.name, platform_id=spec.platform_id,
                             bytes_cir=cir.size_bytes(), locked=True)
        # locked rebuilds verify the attested lock verbatim — still before
        # any fetch is scheduled
        self._check_attestation(cir, lock, attestation, report)
        t0 = time.perf_counter()
        try:
            res = resolution_from_pins(
                lock.pins, self.service,
                {**spec.context(), "entrypoint": cir.entrypoint},
                lock.digests)
        except ResolutionError as e:
            raise ValueError(str(e)) from e
        report.resolve_s = time.perf_counter() - t0
        report.n_components = len(res.components)

        bundle = ComponentBundle(res)
        inst = ContainerInstance(cir=cir, spec=spec, bundle=bundle,
                                 model=None, entry={}, lock=lock,
                                 report=report)
        # locked rebuilds never record a new build id (they replay one)
        BuildOrchestrator(self, self.build_graph).start(
            inst, res, mesh=mesh, assemble=assemble,
            compile_steps=compile_steps, t0=t0, record_build=False,
            overlap=overlap, block=block)
        return inst

    # ------------------------------------------------------------------
    def retry(self, inst: ContainerInstance,
              mesh: Any = None,
              assemble: bool = True,
              compile_steps: bool = False,
              overlap: bool = True,
              block: bool = True) -> ContainerInstance:
        """Re-drive a failed instance's build after a transient fault.

        The instance keeps its resolution, lockfile and report; the
        lifecycle is re-armed (``Lifecycle.reset_for_retry``) so a retry
        that succeeds no longer reports the stale ``failed_stage`` from the
        faulted attempt.  Chunks the first attempt landed are ordinary
        local hits for the retry.
        """
        if inst.lifecycle.error is None and inst.lifecycle.reached("complete"):
            return inst
        BuildOrchestrator(self, self.build_graph).start(
            inst, inst.bundle.resolution, mesh=mesh, assemble=assemble,
            compile_steps=compile_steps, record_build=not inst.report.locked,
            overlap=overlap, block=block)
        return inst

    # ------------------------------------------------------------------
    def _assemble(self, cir: CIR, spec: SpecSheet, bundle: ComponentBundle,
                  mesh: Any) -> Tuple[Any, Dict[str, Callable]]:
        """Uniform Component Assembler: the OverlayFS-mount analogue.

        The model-family component's payload composes the layer/kernel
        components; runtime components wrap the model into step functions.
        """
        cfg = cir.arch_config()
        # the model family is whichever 'model' manager component was selected
        model_comps = [c for c in bundle.components() if c.manager == "model"]
        if not model_comps:
            raise ValueError("no model family component resolved")
        family = model_comps[0]
        model = bundle.payload_of(family)(cfg, bundle.context, bundle)

        entry: Dict[str, Callable] = {}
        for c in bundle.components():
            if c.manager not in ("runtime", "data"):
                continue
            builder = bundle.payload_of(c)
            built = builder(model, cfg, bundle.context, bundle, mesh=mesh)
            if isinstance(built, Mapping):
                entry.update(built)
        return model, entry
