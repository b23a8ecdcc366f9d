"""Multi-tenant taskgraph region server (the serving tier over replay).

The record-and-replay model exists so a region is orchestrated once and
replayed with near-zero management overhead; this module is the step from
"replay one region fast" to "serve many tenants fast". Following the
async-manager shape of Bosch et al. (arXiv:2009.03066) — clients enqueue
work, one manager thread owns dispatch — a :class:`RegionServer` accepts
requests against registered *tenants* (a named TDG + pinned kernel mode)
through an **admission queue** and serves them from shared compiled
executables:

* **Coalescing.** Concurrent requests whose TDGs canonicalize to the same
  ``tdg.structure_signature`` (and same payload identities, buffer shapes
  and kernel mode) are batched into ONE fused replay: buffers are stacked
  along a fresh leading axis and the canonical region function is
  ``vmap``-ed across *requests* — the same trick ``fuse._run_fused_class``
  plays across wave-mates, lifted across tenants. Buffers that are the
  *same object* in every member request (e.g. shared model params) are
  broadcast, not stacked. A batch whose payloads refuse to vmap falls back
  to per-request replay for that batch only.
* **Warm pool.** Batched callables live in an LRU-bounded
  :class:`~repro.serving.pool.WarmPool` keyed by structure + payload
  identities + kernel mode — never by tenant name — so N structurally
  identical tenants share one entry; AOT executables live there too,
  keyed per tenant (their compiled input specs name that tenant's
  slots/shapes). Single-request replay goes through
  ``lower.lower_tdg``'s global structural intern cache, so tenant
  #2..#N reuse tenant #1's jitted executable (``intern_stats()`` counts
  the hits). Cold tenants registered with a ``warm_path`` hydrate their
  compiled binary from the ``.aot`` sidecar (``serialize.load_warm``)
  instead of retracing.
* **Isolation.** Payload identities partition the coalescing key: two
  tenants with same-shaped graphs over *different* payload closures never
  share an executable or a batch. Each tenant's kernel substrate is
  resolved once at registration and re-entered as a
  ``kernel_mode_scope`` around every lowering and call (exactly
  ``ReplayExecutor``'s pinning), so a global ``REPRO_KERNELS`` flip cannot
  change what an already-registered tenant executes.
* **Continuous (iteration-level) batching.** The default scheduler is no
  longer run-to-completion: each structure class owns a *resident batch*
  that tenants join and leave **between** fused replay steps. New requests
  are admitted at step boundaries into the existing power-of-two occupancy
  buckets, finished sequences retire without draining their batch-mates,
  and membership churn re-slices the same pooled/interned executables —
  it never retraces. Multi-step decode work rides :meth:`RegionServer.
  submit_stream`: the member stays resident across steps, each step's
  outputs overwriting its same-named input slots (the repo's standard
  decode-carry idiom), so a K-step stream costs K fused steps and zero
  per-step client round-trips. ``continuous=False`` (or
  ``REPRO_CONTINUOUS=0``) restores the PR-6 run-to-completion dispatcher
  — kept as the benchmark baseline and kill switch.
* **QoS admission.** Per-tenant token buckets (:class:`~repro.serving.
  qos.TokenBucket`; ``rate=`` at registration or ``REPRO_TENANT_RATE``)
  refuse over-rate submissions with typed :class:`RateLimited`; priority
  tiers (``tier=`` / ``REPRO_TENANT_TIER``) drive smooth weighted
  round-robin admission at step boundaries (weight ``2**tier``) and
  compose with PR 7's bounded queue so **low-tier work sheds first**: at
  a full queue a higher-tier arrival evicts the newest lowest-tier waiter
  (its future fails ``QueueFull``) instead of being refused itself.
* **Metrics.** Queue depth, batch occupancy, pool hit rate, p50/p99
  replay latency — now per tier — plus a per-step execution-pattern
  trace ring (:class:`~repro.serving.metrics.ExecutionTraceRing`,
  :meth:`RegionServer.dump_trace`) — see :mod:`repro.serving.metrics`.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from ..core import costmodel as _costmodel
from ..core import lower as _lower
from ..core import serialize as _serialize
from ..core.tdg import TDG, buffers_signature, structure_signature
from ..kernels import registry as _kreg
from ..sharding import replay as _shreplay
from .metrics import ServerMetrics
from .pool import PoolEntry, WarmPool
from .qos import SmoothWRR, TokenBucket, tenant_rate_default, \
    tenant_tier_default, tier_weight

#: Admission-queue bound (requests). ``0`` / unset = unbounded (the
#: pre-backpressure behaviour). When the queue is at the bound, new
#: submissions are refused with :class:`QueueFull` instead of growing the
#: queue without limit under overload.
QUEUE_BOUND_ENV = "REPRO_QUEUE_BOUND"

#: Scheduler selector. Unset/``1`` = iteration-level (continuous)
#: batching; ``0``/``false``/``off`` = the PR-6 run-to-completion
#: dispatcher (benchmark baseline / kill switch).
CONTINUOUS_ENV = "REPRO_CONTINUOUS"


class QueueFull(RuntimeError):
    """Admission refused: the server's bounded queue is at capacity.

    This is the load-shedding signal — the submitter should back off or
    route elsewhere. Deliberately a *typed* error so the cluster frontend
    can tell backpressure (don't retry the same worker immediately) from a
    worker fault (retry a sibling)."""


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed before a result could be produced.

    Raised into the request future either at admission/dispatch time (the
    request was shed unexecuted — see ``deadline_sheds``) or by the cluster
    frontend's deadline sweep when a reply never arrived. Terminal: the
    retry machinery never retries past a deadline."""


class RateLimited(RuntimeError):
    """Admission refused: the tenant's token bucket is dry.

    Per-tenant backpressure, distinct from the server-wide
    :class:`QueueFull`: THIS tenant exceeded its configured rate
    (``register_tenant(rate=...)`` / ``REPRO_TENANT_RATE``) — its
    neighbours are unaffected. Typed so it crosses the cluster RPC wire
    by name (like ``QueueFull``/``DeadlineExceeded``) and is terminal:
    retrying a rate-limited request on a sibling would defeat the limit.
    """


def queue_bound_default() -> int:
    """The env-configured admission bound (0 = unbounded)."""
    raw = os.environ.get(QUEUE_BOUND_ENV, "").strip()
    return max(0, int(raw)) if raw else 0


def continuous_default() -> bool:
    """Env-configured scheduler choice (default: continuous batching on)."""
    raw = os.environ.get(CONTINUOUS_ENV, "").strip().lower()
    return raw not in ("0", "false", "off", "no")


@dataclasses.dataclass
class Tenant:
    """One registered tenant: a region (TDG) plus its pinned substrate.

    ``sig``/``slot_map``/``payloads`` are the canonical structure computed
    once at registration; ``kernel_mode`` is the *resolved* substrate
    (never ``"auto"``), chosen at registration exactly like
    ``ReplayExecutor`` pins it at construction. ``tier`` is the QoS
    priority (higher = more admission weight at step boundaries, sheds
    last under pressure); ``rate`` > 0 arms a per-tenant token bucket.
    """

    name: str
    tdg: TDG
    outputs: tuple[str, ...] | None
    kernel_mode: str
    sig: tuple
    slot_map: dict[str, str]
    payloads: tuple
    warm_path: str | None = None
    fuse: bool | str = "auto"
    #: The server's resolved replay mesh (a concrete Mesh or None), pinned
    #: at registration — every lowering for this tenant shards under it.
    mesh: Any = None
    aot_key: tuple | None = None
    aot_sig: tuple | None = None
    requests: int = 0
    tier: int = 0
    rate: float = 0.0

    def __post_init__(self) -> None:
        self.payload_ids = tuple(id(p) for p in self.payloads)
        self.from_canon = {c: a for a, c in self.slot_map.items()}
        self.input_slots = tuple(s for s in self.tdg.input_slots
                                 if s in self.slot_map)
        self.bucket = TokenBucket(self.rate) if self.rate > 0 else None
        self._fn: Callable[[dict], dict] | None = None
        self._fn_lock = threading.Lock()

    def replay_fn(self) -> Callable[[dict], dict]:
        """The (lazily built) single-request replay callable.

        Built via ``lower.lower_tdg`` under this tenant's pinned mode, so
        it lands in — or is served from — the global structural intern
        cache shared with every other structurally identical tenant.
        """
        with self._fn_lock:
            if self._fn is None:
                with _kreg.kernel_mode_scope(self.kernel_mode):
                    self._fn = _lower.lower_tdg(
                        self.tdg, fuse=self.fuse, mesh=self.mesh,
                        outputs=list(self.outputs)
                        if self.outputs is not None else None)
            return self._fn


class _Request:
    """One admitted unit of work — and, continuously, one batch *member*.

    Under the continuous scheduler a request with ``steps > 1`` is a
    resident stream: it stays in its class's batch across steps, each
    step's outputs overwriting its same-named input slots, and its future
    resolves with the FINAL step's outputs.
    """

    __slots__ = ("tenant", "buffers", "canon_buffers", "key", "future",
                 "t_submit", "served_aot", "deadline", "steps", "steps_done")

    def __init__(self, tenant: Tenant, buffers: dict, canon_buffers: dict,
                 key: tuple, deadline: float | None = None, steps: int = 1):
        self.tenant = tenant
        self.buffers = buffers
        self.canon_buffers = canon_buffers
        self.key = key
        self.future: Future = Future()
        self.t_submit = time.monotonic()
        self.served_aot = False
        self.deadline = deadline       # absolute time.monotonic(), or None
        self.steps = steps
        self.steps_done = 0


class _ClassState:
    """Continuous-scheduler state for one coalescing key (structure class).

    ``resident`` is the live batch stepped as one fused replay;
    ``pending`` holds admitted-but-not-yet-joined members, drained into
    ``resident`` at step boundaries by tier-weighted round robin.
    """

    __slots__ = ("key", "cid", "resident", "pending", "step", "wrr")

    def __init__(self, key: tuple, cid: int):
        self.key = key
        self.cid = cid
        self.resident: list[_Request] = []
        self.pending: list[_Request] = []
        self.step = 0
        self.wrr = SmoothWRR()         # tier selector for admission slots

    def busy(self) -> bool:
        return bool(self.resident or self.pending)


class RegionServer:
    """Admission-queued, batch-coalescing server over interned replay.

    Parameters
    ----------
    max_batch:
        Coalescing ceiling — how many structurally identical requests one
        fused replay may carry. ``1`` disables batching (serial
        per-request replay; the benchmark baseline).
    max_wait_ms:
        Admission window: after the first request of a batch arrives, how
        long the dispatcher waits for same-structure companions before
        dispatching a partial batch. Bounded head-of-line latency.
    pool_capacity:
        LRU bound on the warm-executable pool.
    queue_bound:
        Admission-queue bound (requests). ``None`` honours
        ``REPRO_QUEUE_BOUND``; ``0`` means unbounded. At the bound, new
        submissions are refused with :class:`QueueFull` (counted in the
        ``shed`` metric) instead of growing the queue under overload.
    fuse:
        Wave-fusion policy handed to every lowering this server performs
        (single-request AND batched paths): ``True`` / ``False`` /
        ``"auto"`` (honour ``REPRO_FUSE``), as in ``lower.lower_tdg``.
    autostart:
        Start the scheduler thread immediately. Tests pass ``False``,
        enqueue a known set of requests, then call :meth:`start` for a
        deterministic first batch / first step-boundary admission.
    continuous:
        ``True`` = iteration-level batching (resident per-class batches,
        step-boundary joins/leaves, streams); ``False`` = the PR-6
        run-to-completion dispatcher. ``None`` honours
        ``REPRO_CONTINUOUS`` (default: continuous).
    """

    def __init__(self, max_batch: int = 8, max_wait_ms: float = 2.0,
                 pool_capacity: int = 64, fuse: bool | str = "auto",
                 name: str = "region-server", autostart: bool = True,
                 queue_bound: int | None = None,
                 continuous: bool | None = None,
                 adaptive: bool | str = "auto",
                 mesh: Any = "auto"):
        self.name = name
        self.max_batch = max(1, int(max_batch))
        self.max_wait_s = max(0.0, float(max_wait_ms)) / 1e3
        self.queue_bound = (queue_bound_default() if queue_bound is None
                            else max(0, int(queue_bound)))
        self.continuous = (continuous_default() if continuous is None
                           else bool(continuous))
        self.fuse = fuse
        # Adaptive occupancy buckets ("auto" honours REPRO_ADAPTIVE): the
        # tuner starts on the pow-2 ladder and refits boundaries from the
        # live occupancy histogram under a bounded retrace budget; a refit
        # invalidates the pool's stale batched executables. adaptive=False
        # (or REPRO_ADAPTIVE=0) pins the static pow-2 ladder for good.
        self.adaptive = _costmodel.adaptive_enabled(adaptive)
        self.buckets = _costmodel.BucketTuner(self.max_batch,
                                              adaptive=self.adaptive)
        # Resolved ONCE at construction (like each tenant's kernel mode):
        # every lowering this server performs — single-request, batched,
        # warmup AOT — shards the coalesced batch axis under this mesh, and
        # its fingerprint partitions the WarmPool keys so 1-device and
        # N-device executables never collide. "auto" honours an ambient
        # use_mesh scope, then REPRO_MESH (sharding.replay.resolve_mesh).
        self.mesh = _shreplay.resolve_mesh(mesh)
        self.mesh_fp = _shreplay.mesh_fingerprint(self.mesh)
        self.pool = WarmPool(capacity=pool_capacity)
        self.metrics = ServerMetrics()
        self._tenants: dict[str, Tenant] = {}
        self._queue: collections.deque[_Request] = collections.deque()
        self._cv = threading.Condition()
        self._closed = False
        self._started = False
        # Continuous-scheduler state (unused by the legacy dispatcher).
        self._classes: dict[tuple, _ClassState] = {}
        self._next_cid = 0
        self._pending_count = 0        # members parked in class pendings
        self._class_wrr = SmoothWRR()  # which class steps next
        self._thread = threading.Thread(
            target=(self._scheduler_loop if self.continuous
                    else self._dispatch_loop),
            name=f"{name}-dispatch", daemon=True)
        if autostart:
            self.start()

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        """Start the dispatcher thread (idempotent)."""
        if not self._started:
            self._started = True
            self._thread.start()

    def close(self) -> None:
        """Drain the admission queue, then stop the dispatcher.

        Holds even for a never-started server (``autostart=False``) with
        requests already queued: the dispatcher is started just to drain
        them, so no pending future is ever silently abandoned.
        """
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            pending = bool(self._queue) or self._pending_count > 0
        if not self._started and pending:
            self.start()
        if self._started:
            self._thread.join()

    def __enter__(self) -> "RegionServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------- tenants
    def register_tenant(self, name: str, tdg: TDG | None = None, *,
                        outputs: tuple[str, ...] | None = None,
                        kernel_mode: str | None = None,
                        warm_path: str | None = None,
                        fn_registry: "_serialize.TaskFnRegistry | None" = None,
                        tier: int | None = None,
                        rate: float | None = None,
                        ) -> Tenant:
        """Register a tenant by TDG, or hydrate one from a warm artifact.

        Exactly one of ``tdg`` / ``warm_path`` selects the region source:
        ``warm_path`` names a TDG JSON written by
        ``serialize.warmup_and_save`` (payloads re-linked through
        ``fn_registry``); if its ``.aot`` sidecar is present and loadable,
        the compiled binary is installed in the warm pool so this tenant's
        first request replays without any retrace. A missing or corrupt
        sidecar degrades silently to the ordinary (interned, lazily
        traced) replay path — hydration is an optimization, never a
        correctness dependency.

        ``tier`` (QoS priority; higher wins contended admission slots and
        sheds last) and ``rate`` (sustained req/s through a token bucket;
        0 = unlimited) default to the per-tenant ``REPRO_TENANT_TIER`` /
        ``REPRO_TENANT_RATE`` environment specs.
        """
        if (tdg is None) == (warm_path is None):
            raise ValueError("pass exactly one of tdg= or warm_path=")
        aot = None
        sidecar_present = False
        if warm_path is not None:
            if fn_registry is None:
                raise ValueError("warm_path= requires fn_registry= to "
                                 "re-link task payloads")
            sidecar_present = os.path.exists(str(warm_path) + ".aot")
            tdg, aot = _serialize.load_warm(warm_path, fn_registry,
                                            mesh=self.mesh_fp)
        tdg.validate()
        mode = _kreg.resolved_mode(kernel_mode)
        sig, slot_map, payloads = structure_signature(
            tdg, list(outputs) if outputs is not None else None)
        tenant = Tenant(name=name, tdg=tdg,
                        outputs=tuple(outputs) if outputs is not None else None,
                        kernel_mode=mode, sig=sig, slot_map=slot_map,
                        payloads=payloads, warm_path=warm_path,
                        fuse=self.fuse, mesh=self.mesh,
                        tier=(tenant_tier_default(name) if tier is None
                              else max(0, int(tier))),
                        rate=(tenant_rate_default(name) if rate is None
                              else max(0.0, float(rate))))
        with self._cv:
            if name in self._tenants:
                raise ValueError(f"tenant {name!r} already registered")
            self._tenants[name] = tenant
        if aot is not None:
            self._install_aot(tenant, aot, hydrated=True)
        elif sidecar_present:
            # The sidecar was on disk but load_warm soft-fell back (corrupt,
            # truncated, platform/version mismatch, or a jax build without
            # executable serialization). The tenant still works — lazily
            # traced — but it is NOT warm, and pretending otherwise is how
            # cold-start regressions hide. Make the fallback loud in metrics.
            self.metrics.on_aot_hydrate_failure()
        return tenant

    def tenant(self, name: str) -> Tenant:
        with self._cv:
            if name not in self._tenants:
                raise KeyError(f"unknown tenant {name!r}; registered: "
                               f"{sorted(self._tenants)}")
            return self._tenants[name]

    def warmup(self, name: str, buffers: Mapping[str, Any]) -> dict:
        """Eagerly AOT-compile a tenant's replay executable into the pool.

        ``buffers`` may be concrete arrays or ``ShapeDtypeStruct`` specs.
        Returns the compile report (cost analysis, trace/compile seconds)
        so callers can budget warmup off the serving critical path.
        """
        tenant = self.tenant(name)
        with _kreg.kernel_mode_scope(tenant.kernel_mode):
            aot = _lower.aot_compile_tdg(
                tenant.tdg, buffers, fuse=tenant.fuse, mesh=tenant.mesh,
                outputs=list(tenant.outputs)
                if tenant.outputs is not None else None)
        self._install_aot(tenant, aot)
        return {"tenant": name, "fused": aot.fused,
                "cost_analysis": aot.cost_analysis,
                "trace_seconds": aot.trace_seconds,
                "compile_seconds": aot.compile_seconds}

    def install_aot(self, name: str, aot: "_lower.AotExecutable",
                    hydrated: bool = False) -> None:
        """Install an externally produced AOT executable for tenant ``name``.

        This is how the cluster tier's :class:`~repro.serving.cluster.
        WorkerNode` plants an executable hydrated from *shipped* artifact
        bytes (``serialize.executable_from_bytes``) — the worker never
        re-lowers what the frontend already compiled. ``hydrated=True``
        counts it in the pool's hydration counter.
        """
        self._install_aot(self.tenant(name), aot, hydrated=hydrated)

    def _install_aot(self, tenant: Tenant, aot: "_lower.AotExecutable",
                     hydrated: bool = False) -> None:
        aot_sig = buffers_signature(aot.input_specs)
        key = ("aot", tenant.name, aot_sig, tenant.kernel_mode, self.mesh_fp)
        self.pool.put(key, PoolEntry("aot", aot, tenant.payloads),
                      hydrated=hydrated)
        tenant.aot_key = key
        tenant.aot_sig = aot_sig

    # ------------------------------------------------------------ admission
    def _make_request(self, tenant_name: str, buffers: Mapping[str, Any],
                      deadline: float | None = None,
                      steps: int = 1) -> "_Request":
        """Validate + canonicalize one submission into a queue entry."""
        tenant = self.tenant(tenant_name)
        missing = [s for s in tenant.input_slots if s not in buffers]
        if missing:
            raise KeyError(f"request for tenant {tenant_name!r} is missing "
                           f"input slots {missing}")
        buffers = dict(buffers)
        canon = {tenant.slot_map[k]: v for k, v in buffers.items()
                 if k in tenant.slot_map}
        key = (tenant.sig, tenant.payload_ids, buffers_signature(canon),
               tenant.kernel_mode)
        return _Request(tenant, buffers, canon, key, deadline=deadline,
                        steps=steps)

    def _waiting_locked(self) -> int:
        """Admitted-but-not-resident requests: the bounded-queue population.

        Under the continuous scheduler, waiting work lives both in the
        raw admission queue and in per-class pending lists (parked for a
        step boundary) — the queue bound must count both or draining into
        pendings would quietly disable backpressure.
        """
        return len(self._queue) + self._pending_count

    def _evict_lower_tier_locked(self, tier: int) -> "_Request | None":
        """Pop the newest waiting request of the lowest tier below ``tier``.

        The low-tier-sheds-first half of tier QoS: at a full queue a
        higher-tier arrival displaces best-effort work instead of being
        refused. Newest-first within the victim tier, so the longest-
        waiting low-tier request keeps its FIFO claim on the next slot.
        """
        victim_tier = tier
        place: tuple | None = None
        for i in range(len(self._queue) - 1, -1, -1):
            if self._queue[i].tenant.tier < victim_tier:
                victim_tier = self._queue[i].tenant.tier
                place = (None, i)
        for cls in self._classes.values():
            for i in range(len(cls.pending) - 1, -1, -1):
                if cls.pending[i].tenant.tier < victim_tier:
                    victim_tier = cls.pending[i].tenant.tier
                    place = (cls, i)
        if place is None:
            return None
        cls, i = place
        if cls is None:
            victim = self._queue[i]
            del self._queue[i]
        else:
            victim = cls.pending.pop(i)
            self._pending_count -= 1
        return victim

    def _admit(self, req: "_Request") -> tuple[int, "_Request | None"]:
        """Admission control for one request: closed / rate / bound checks.

        Returns ``(queue depth, evicted victim or None)``; raises
        :class:`RateLimited` / :class:`QueueFull`. The victim's future is
        failed by the caller OUTSIDE the lock.
        """
        tenant = req.tenant
        with self._cv:
            if self._closed:
                raise RuntimeError(f"server {self.name!r} is closed")
            if tenant.bucket is not None and not tenant.bucket.take():
                self.metrics.on_rate_limited()
                raise RateLimited(
                    f"tenant {tenant.name!r} exceeded its rate limit "
                    f"({tenant.rate:g} req/s); request refused")
            victim = None
            if self.queue_bound and self._waiting_locked() >= self.queue_bound:
                victim = self._evict_lower_tier_locked(tenant.tier)
                if victim is None:
                    self.metrics.on_shed()
                    raise QueueFull(
                        f"server {self.name!r} admission queue is at its "
                        f"bound ({self.queue_bound}); request shed")
            self._queue.append(req)
            tenant.requests += 1
            depth = self._waiting_locked()
            self._cv.notify_all()
        if victim is not None:
            self.metrics.on_shed()
            victim.future.set_exception(QueueFull(
                f"server {self.name!r} admission queue is at its bound "
                f"({self.queue_bound}); shed for a tier-{tenant.tier} "
                f"arrival"))
        return depth, victim

    def submit(self, tenant_name: str, buffers: Mapping[str, Any],
               deadline: float | None = None) -> Future:
        """Enqueue one request; resolves to the region's output dict.

        ``deadline`` is an absolute ``time.monotonic()`` instant (or
        ``None`` for no deadline): a request still undispatched when it
        passes is shed (``DeadlineExceeded`` future, ``deadline_sheds``
        counter) instead of wasting a replay. Raises :class:`QueueFull`
        when the bounded admission queue is at capacity (unless a
        lower-tier waiter can be shed instead) and :class:`RateLimited`
        when the tenant's token bucket is dry.
        """
        req = self._make_request(tenant_name, buffers, deadline=deadline)
        depth, _ = self._admit(req)
        self.metrics.on_admit(depth)
        return req.future

    def submit_stream(self, tenant_name: str, buffers: Mapping[str, Any],
                      steps: int, deadline: float | None = None) -> Future:
        """Enqueue a ``steps``-step resident stream (continuous mode only).

        The member joins its structure class's resident batch at a step
        boundary and stays for ``steps`` fused replay steps; between
        steps, outputs overwrite same-named input slots (the decode-carry
        idiom — ``bufs.update(out)``), all server-side, with no per-step
        client round-trip. The future resolves with the FINAL step's
        outputs. Joining and leaving never retraces: membership churn
        re-slices the same pooled occupancy-bucketed executables.
        """
        if not self.continuous:
            raise RuntimeError(
                "submit_stream requires continuous batching "
                "(RegionServer(continuous=True) / REPRO_CONTINUOUS=1)")
        if int(steps) < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        req = self._make_request(tenant_name, buffers, deadline=deadline,
                                 steps=int(steps))
        depth, _ = self._admit(req)
        self.metrics.on_admit(depth)
        return req.future

    def submit_many(self, items: list[tuple]) -> list[Future]:
        """Admit a whole batch frame under ONE queue-lock acquisition.

        ``items`` entries are ``(tenant_name, buffers)`` or
        ``(tenant_name, buffers, deadline)`` (absolute monotonic, ``None``
        ok); the return list is positionally aligned with it. Per-entry
        validation failures (unknown tenant, missing input slots) come back
        as pre-failed futures — one bad entry in a wire batch must not
        reject its neighbours, and the cluster tier needs a per-entry error
        to route back to the right caller. Entries that do not fit under
        the queue bound come back pre-failed with :class:`QueueFull`; an
        entry whose deadline has *already* passed is shed at admission
        (pre-failed ``DeadlineExceeded``) without touching the queue.
        """
        results: list[Future] = []
        admitted: list[_Request] = []
        now = time.monotonic()
        n_expired = 0
        for item in items:
            tenant_name, buffers = item[0], item[1]
            deadline = item[2] if len(item) > 2 else None
            if deadline is not None and deadline <= now:
                fut: Future = Future()
                fut.set_exception(DeadlineExceeded(
                    f"deadline passed before admission for tenant "
                    f"{tenant_name!r}"))
                results.append(fut)
                n_expired += 1
                continue
            try:
                req = self._make_request(tenant_name, buffers,
                                         deadline=deadline)
            except Exception as exc:
                fut = Future()
                fut.set_exception(exc)
                results.append(fut)
                continue
            admitted.append(req)
            results.append(req.future)
        if n_expired:
            self.metrics.on_deadline_shed(n_expired)
        if admitted:
            overflow: list[_Request] = []
            limited: list[_Request] = []
            victims: list[_Request] = []
            n_in = 0
            with self._cv:
                if self._closed:
                    err = RuntimeError(f"server {self.name!r} is closed")
                    for req in admitted:
                        req.future.set_exception(err)
                    return results
                for req in admitted:
                    tenant = req.tenant
                    if tenant.bucket is not None and not tenant.bucket.take():
                        limited.append(req)
                        continue
                    if self.queue_bound and \
                            self._waiting_locked() >= self.queue_bound:
                        victim = self._evict_lower_tier_locked(tenant.tier)
                        if victim is None:
                            overflow.append(req)
                            continue
                        victims.append(victim)
                    self._queue.append(req)
                    tenant.requests += 1
                    n_in += 1
                depth = self._waiting_locked()
                self._cv.notify_all()
            for req in limited:
                req.future.set_exception(RateLimited(
                    f"tenant {req.tenant.name!r} exceeded its rate limit "
                    f"({req.tenant.rate:g} req/s); request refused"))
            if limited:
                self.metrics.on_rate_limited(len(limited))
            for req in overflow + victims:
                req.future.set_exception(QueueFull(
                    f"server {self.name!r} admission queue is at its bound "
                    f"({self.queue_bound}); request shed"))
            if overflow or victims:
                self.metrics.on_shed(len(overflow) + len(victims))
            if n_in:
                self.metrics.on_admit_many(n_in, depth)
        return results

    def serve(self, tenant_name: str, buffers: Mapping[str, Any],
              timeout: float | None = 60.0) -> dict:
        """Synchronous :meth:`submit` — blocks for this request's result."""
        return self.submit(tenant_name, buffers).result(timeout=timeout)

    def stats(self) -> dict:
        """Serving metrics + pool counters + the global intern counters."""
        with self._cv:
            tenants = {t.name: t.requests for t in self._tenants.values()}
        return {
            "server": self.name,
            "max_batch": self.max_batch,
            "queue_bound": self.queue_bound,
            "continuous": self.continuous,
            "adaptive": self.adaptive,
            "mesh": self.mesh_fp,
            "tenants": tenants,
            "metrics": self.metrics.snapshot(),
            "pool": self.pool.stats(),
            "buckets": self.buckets.summary(),
            "intern": _lower.intern_stats(),
        }

    def dump_trace(self, path: str) -> dict:
        """Write the execution-pattern trace ring to ``path`` as JSON."""
        return self.metrics.trace.dump(path, meta={"server": self.name})

    # ------------------------------------------------------------- dispatch
    def _take_matching(self, group: list[_Request], key: tuple) -> None:
        """Move queued requests with ``key`` into ``group`` (up to max_batch)."""
        kept: collections.deque[_Request] = collections.deque()
        while self._queue:
            r = self._queue.popleft()
            if r.key == key and len(group) < self.max_batch:
                group.append(r)
            else:
                kept.append(r)
        self._queue.extend(kept)

    def _dispatch_loop(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if not self._queue:     # closed and drained
                    return
                head = self._queue.popleft()
                group = [head]
                if self.max_batch > 1:
                    deadline = time.monotonic() + self.max_wait_s
                    while len(group) < self.max_batch:
                        self._take_matching(group, head.key)
                        if len(group) >= self.max_batch or self._closed:
                            break
                        if self._queue:
                            # Everything still queued is non-matching (all
                            # matches were just taken): holding the window
                            # open would head-of-line block other keys for
                            # up to max_wait for companions that may never
                            # come. Dispatch now; stragglers form the next
                            # group.
                            break
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
                    self._take_matching(group, head.key)
            self._execute_group(group)

    # ------------------------------------------- continuous (iteration-level)
    def _drain_queue_locked(self) -> None:
        """Park every queued request in its structure class's pending list."""
        while self._queue:
            req = self._queue.popleft()
            cls = self._classes.get(req.key)
            if cls is None:
                cls = self._classes[req.key] = _ClassState(req.key,
                                                           self._next_cid)
                self._next_cid += 1
            cls.pending.append(req)
            self._pending_count += 1

    def _pick_class_locked(self) -> "_ClassState | None":
        """Smooth-WRR over busy classes, weighted by their best member tier.

        A class hosting a tier-1 member gets ~2x the step slots of an
        all-tier-0 class, which is how tier priority shapes *step* order
        (admission order within a class is the per-class tier WRR).
        """
        weights: dict[tuple, int] = {}
        for key, cls in self._classes.items():
            if not cls.busy():
                continue
            w = 1
            for r in cls.resident:
                w = max(w, tier_weight(r.tenant.tier))
            for r in cls.pending:
                w = max(w, tier_weight(r.tenant.tier))
            weights[key] = w
        key = self._class_wrr.pick(weights)
        return None if key is None else self._classes[key]

    def _want_window_locked(self, cls: "_ClassState") -> bool:
        """Hold a coalescing window open for this class's first step?

        Only when the batch would otherwise start at occupancy 1 with the
        whole server idle: no residents yet, pending below max_batch,
        nothing queued, and no other class with work. A resident batch
        never waits — steps must keep their cadence for members already
        decoding — and a busy server never head-of-line blocks one class
        waiting on companions for another.
        """
        if self.max_batch <= 1 or self.max_wait_s <= 0 or self._closed:
            return False
        if cls.resident or len(cls.pending) >= self.max_batch:
            return False
        if self._queue:
            return False
        return not any(other is not cls and other.busy()
                       for other in self._classes.values())

    def _shed_expired_locked(self, cls: "_ClassState") -> list:
        """Pop members (resident or pending) whose deadline has passed."""
        now = time.monotonic()
        expired = []
        for lst in (cls.resident, cls.pending):
            for r in lst[:]:
                if r.deadline is not None and r.deadline <= now:
                    lst.remove(r)
                    if lst is cls.pending:
                        self._pending_count -= 1
                    expired.append(r)
        return expired

    def _admit_members_locked(self, cls: "_ClassState") -> int:
        """Fill free resident slots from pending, tier-weighted, FIFO in tier.

        Admission happens ONLY here — at a step boundary — so with
        ``autostart=False`` the membership of the first step is a pure
        function of what was submitted before :meth:`start`. The per-class
        :class:`SmoothWRR` picks which tier supplies each slot (weight
        ``2**tier``), and within a tier arrival order is preserved.
        """
        joins = 0
        while cls.pending and len(cls.resident) < self.max_batch:
            tiers: dict[int, int] = {}
            for r in cls.pending:
                tiers.setdefault(r.tenant.tier, 0)
                tiers[r.tenant.tier] += 1
            pick = cls.wrr.pick({t: tier_weight(t) for t in tiers})
            for i, r in enumerate(cls.pending):
                if r.tenant.tier == pick:
                    cls.resident.append(cls.pending.pop(i))
                    self._pending_count -= 1
                    joins += 1
                    break
        return joins

    def _scheduler_loop(self) -> None:
        """Continuous-batching scheduler: one fused replay step per wakeup.

        Each iteration drains the admission queue into per-class pending
        lists, picks the next class to step (tier-weighted smooth WRR),
        admits joiners / sheds expired members at the step boundary, and
        runs ONE step for that class's resident batch outside the lock.
        Members with ``steps_done < steps`` stay resident with outputs
        carried into same-named input slots; finished members retire
        without draining the batch.
        """
        while True:
            with self._cv:
                self._drain_queue_locked()
                cls = self._pick_class_locked()
                if cls is None:
                    if self._closed:
                        return
                    self._cv.wait()
                    continue
                if self._want_window_locked(cls):
                    deadline = time.monotonic() + self.max_wait_s
                    while True:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
                        self._drain_queue_locked()
                        if not self._want_window_locked(cls):
                            break
                expired = self._shed_expired_locked(cls)
                joins = self._admit_members_locked(cls)
                group = list(cls.resident)
                cls.step += 1
                step_idx = cls.step
            if expired:
                now = time.monotonic()
                self.metrics.on_deadline_shed(len(expired))
                for r in expired:
                    self.metrics.on_done(now - r.t_submit, failed=True)
                    r.future.set_exception(DeadlineExceeded(
                        f"deadline passed while queued for tenant "
                        f"{r.tenant.name!r}"))
            if group:
                self._execute_step(cls, group, step_idx,
                                   joins=joins, sheds=len(expired))

    def _execute_step(self, cls: "_ClassState", group: list, step_idx: int,
                      joins: int, sheds: int) -> None:
        """Run ONE fused replay step for a resident batch; settle membership.

        Reuses the request-level execution paths unchanged —
        ``_run_single`` for a lone resident, ``_run_batched`` (pooled
        occupancy-bucketed vmap executables, per-request serial fallback) for
        more — so membership churn hits the same intern/pool caches and
        never retraces. Afterwards: failures and finished members retire;
        survivors carry outputs into same-named input slots, and a member
        whose buffer signature drifted (shape change) migrates to the
        class that now matches instead of poisoning this batch's bucket.
        """
        t0 = time.monotonic()
        coalesced = False
        try:
            if len(group) == 1:
                results: list = [self._run_single(group[0])]
            else:
                results, coalesced = self._run_batched(group)
            jax.block_until_ready([r for r in results
                                   if not isinstance(r, Exception)])
        except Exception as exc:
            results = [exc] * len(group)
        wall_ms = (time.monotonic() - t0) * 1e3
        done: list = []
        failed: list = []
        leaves = 0
        with self._cv:
            for member, out in zip(group, results):
                if isinstance(out, Exception):
                    cls.resident.remove(member)
                    failed.append((member, out))
                    leaves += 1
                    continue
                member.steps_done += 1
                if member.steps_done >= member.steps:
                    cls.resident.remove(member)
                    done.append((member, out))
                    leaves += 1
                    continue
                tenant = member.tenant
                member.buffers = {**member.buffers,
                                  **{k: v for k, v in out.items()
                                     if k in member.buffers}}
                canon = {tenant.slot_map[k]: v
                         for k, v in member.buffers.items()
                         if k in tenant.slot_map}
                member.canon_buffers = canon
                new_key = (tenant.sig, tenant.payload_ids,
                           buffers_signature(canon), tenant.kernel_mode)
                if new_key != cls.key:
                    cls.resident.remove(member)
                    member.key = new_key
                    target = self._classes.get(new_key)
                    if target is None:
                        target = self._classes[new_key] = _ClassState(
                            new_key, self._next_cid)
                        self._next_cid += 1
                    target.pending.append(member)
                    self._pending_count += 1
                    leaves += 1
            self._cv.notify_all()
        now = time.monotonic()
        for member, exc in failed:
            self.metrics.on_done(now - member.t_submit, failed=True)
            member.future.set_exception(exc)
        for member, out in done:
            self.metrics.on_done(now - member.t_submit,
                                 aot=member.served_aot,
                                 tier=member.tenant.tier)
            member.future.set_result(out)
        self.metrics.on_batch(len(group), coalesced=coalesced)
        tiers: dict[str, int] = {}
        for member in group:
            label = str(member.tenant.tier)
            tiers[label] = tiers.get(label, 0) + 1
        # The tuner's ladder (already retuned by this step's own
        # observation, if it was going to) names the bucket the coalesced
        # path actually ran; pad lanes only exist when ONE fused call
        # served the step — the serial fallback runs nothing idle.
        bucket, padded = (1, 0) if len(group) < 2 \
            else self._bucket_and_pad(len(group))
        self.metrics.on_step({
            "step": step_idx,
            "class_id": cls.cid,
            "occupancy": len(group),
            "bucket": bucket,
            "joins": joins,
            "leaves": leaves,
            "sheds": sheds,
            "wall_ms": wall_ms,
            "coalesced": coalesced,
            "padded": padded if coalesced else 0,
            "tiers": tiers,
        })

    # ------------------------------------------------------------- execution
    def _execute_group(self, group: list[_Request]) -> None:
        # Shed members whose deadline already passed BEFORE spending a
        # replay on them: the submitter stopped waiting, so the only thing
        # executing buys is wasted compute in front of live requests.
        now = time.monotonic()
        expired = [r for r in group if r.deadline is not None
                   and r.deadline <= now]
        if expired:
            group = [r for r in group if r not in expired]
            self.metrics.on_deadline_shed(len(expired))
            for r in expired:
                self.metrics.on_done(now - r.t_submit, failed=True)
                r.future.set_exception(DeadlineExceeded(
                    f"deadline passed while queued for tenant "
                    f"{r.tenant.name!r}"))
            if not group:
                return
        coalesced = False
        try:
            if len(group) == 1:
                # A lone request (no coalescing partner inside the window)
                # takes the interned single-request path — never a K=1
                # specialization of the batched program.
                results = [self._run_single(group[0])]
            else:
                results, coalesced = self._run_batched(group)
            jax.block_until_ready(results)
        except Exception as exc:
            now = time.monotonic()
            for r in group:
                self.metrics.on_done(now - r.t_submit, failed=True)
                r.future.set_exception(exc)
            return
        self.metrics.on_batch(len(group), coalesced=coalesced)
        now = time.monotonic()
        for r, out in zip(group, results):
            if isinstance(out, Exception):      # per-request fallback failure
                self.metrics.on_done(now - r.t_submit, failed=True)
                r.future.set_exception(out)
            else:
                self.metrics.on_done(now - r.t_submit, aot=r.served_aot)
                r.future.set_result(out)

    def _run_single(self, req: _Request) -> dict:
        tenant = req.tenant
        aot = self._aot_for(req)
        if aot is not None:
            req.served_aot = True
            with _kreg.kernel_mode_scope(tenant.kernel_mode):
                return aot(req.buffers)
        fn = tenant.replay_fn()
        with _kreg.kernel_mode_scope(tenant.kernel_mode):
            return fn(dict(req.buffers))

    def _aot_for(self, req: _Request) -> "_lower.AotExecutable | None":
        """The tenant's warm AOT executable, iff shapes match this request.

        Pool-evicted AOT entries are re-hydrated from the tenant's
        ``warm_path`` sidecar when possible (cold tenants pay a disk read,
        not a retrace); irrecoverable sidecars permanently fall back to the
        interned lazy path.
        """
        tenant = req.tenant
        if tenant.aot_key is None:
            return None
        slots = self._aot_spec_slots(tenant)
        want = buffers_signature(
            {k: v for k, v in req.buffers.items() if k in slots})
        if want != tenant.aot_sig:
            return None
        entry = self.pool.get(tenant.aot_key)
        if entry is not None:
            return entry.fn
        if tenant.warm_path is not None:
            try:
                aot = _serialize.load_executable(str(tenant.warm_path) + ".aot",
                                                 mesh=self.mesh_fp)
            except Exception:
                tenant.aot_key = None       # unrecoverable: stop retrying
                self.metrics.on_aot_hydrate_failure()
                return None
            self._install_aot(tenant, aot, hydrated=True)
            return aot
        tenant.aot_key = None
        return None

    def _aot_spec_slots(self, tenant: Tenant) -> tuple:
        # aot_sig is (treedef of the spec dict, leaf specs): the dict's keys
        # are the slot set.
        if tenant.aot_sig is None:
            return ()
        return tuple(tenant.aot_sig[0].node_data()[1])

    def _run_batched(self, group: list[_Request]) -> tuple[list, bool]:
        """Serve a coalesced group; returns ``(results, coalesced)``.

        ``coalesced`` is True only when ONE fused vmap-batched call served
        the whole group, so the metrics never report fallback groups as
        real cross-request fusion.
        """
        try:
            return self._run_batched_fused(group), True
        except Exception:
            # A payload without a batching rule (or any trace-time failure
            # specific to the vmapped form) degrades THIS batch to serial
            # per-request replay; single-request bugs still surface from
            # _run_single with their real error — per request, so one
            # member's failure cannot poison its siblings' results.
            self.metrics.on_batch_fallback()
            results: list[dict | Exception] = []
            for r in group:
                try:
                    results.append(self._run_single(r))
                except Exception as exc:
                    results.append(exc)
            return results, False

    def _split_shared(self, group: list[_Request]) -> tuple[dict, tuple]:
        """(buffers every member shares by identity, varying slot names)."""
        canon = [r.canon_buffers for r in group]
        slots = sorted(canon[0])
        shared = frozenset(
            s for s in slots
            if all(cb[s] is canon[0][s] for cb in canon[1:]))
        return ({s: canon[0][s] for s in shared},
                tuple(s for s in slots if s not in shared))

    def _batched_entry(self, tenant0: Tenant, shared: frozenset) -> PoolEntry:
        key = ("batched", tenant0.sig, tenant0.payload_ids, shared,
               tenant0.kernel_mode, self.mesh_fp)
        entry = self.pool.get(key)
        if entry is None:
            entry = self.pool.put(key, PoolEntry(
                "batched", self._build_batched(tenant0), tenant0.payloads))
        return entry

    def compile_batched(self, name: str,
                        members: Sequence[Mapping[str, Any]]) -> Any:
        """The pooled batched program compiled as it serves ``members``.

        ``members`` are request buffers of tenant ``name`` (arrays or
        ``ShapeDtypeStruct`` specs under the tenant's slot names), one per
        batch member; a buffer passed as the same object to every member is
        shared, as in a coalesced step. The pooled ``"batched"`` callable is
        lowered at the occupancy bucket the server would pick and compiled,
        so the caller reads the program the server runs (its HLO, its
        memory). The bucket tuner is not fed.
        """
        group = [self._make_request(name, b) for b in members]
        tenant0 = group[0].tenant
        shared_bufs, varying = self._split_shared(group)
        if not varying:
            raise ValueError("every buffer is shared: the server replays one "
                             "member instead of a batched program")
        entry = self._batched_entry(tenant0, frozenset(shared_bufs))
        per_req = [{s: r.canon_buffers[s] for s in varying} for r in group]
        per_req.extend(per_req[-1:] * self._bucket_and_pad(len(per_req))[1])
        with _kreg.kernel_mode_scope(tenant0.kernel_mode):
            return entry.fn.lower(tuple(per_req), shared_bufs).compile()

    def _run_batched_fused(self, group: list[_Request]) -> list[dict]:
        tenant0 = group[0].tenant
        canon = [r.canon_buffers for r in group]
        shared_bufs, varying = self._split_shared(group)
        if not varying:
            # Every buffer is literally shared: one single-request replay
            # serves the whole batch (all members compute the same values).
            out0 = self._run_single(group[0])
            canon_out = {group[0].tenant.slot_map[s]: v
                         for s, v in out0.items()}
            return [{r.tenant.from_canon[c]: v for c, v in canon_out.items()}
                    for r in group]
        entry = self._batched_entry(tenant0, frozenset(shared_bufs))
        # Bucket occupancy (padding with a repeat of the last member,
        # dropped after the call): jit specializes the batched program per
        # pytree arity, so without bucketing every straggler-induced
        # occupancy K would pay a fresh trace+compile. Boundaries come from
        # the BucketTuner — the pow-2 ladder until the live occupancy
        # histogram justifies a refit (bounded retrace budget; static under
        # REPRO_ADAPTIVE=0). A refit retires the pool's batched entries:
        # their baked-in bucket sizes can never be requested again. Under a
        # mesh the bucket also rounds up to a batch-axis multiple so the
        # request axis always splits evenly across devices.
        per_req = [{s: cb[s] for s in varying} for cb in canon]
        if self.buckets.observe(len(per_req)):
            self.pool.invalidate(lambda k, e: e.kind == "batched")
            self.metrics.on_bucket_retune(self.buckets.boundaries)
        bucket, pad = self._bucket_and_pad(len(per_req))
        per_req.extend(per_req[-1:] * pad)
        self.metrics.on_pad(pad)
        with _kreg.kernel_mode_scope(tenant0.kernel_mode):
            outs = entry.fn(tuple(per_req), shared_bufs)
        return [{r.tenant.from_canon[c]: v for c, v in out_j.items()}
                for r, out_j in zip(group, outs)]

    def _bucket_and_pad(self, occupancy: int) -> tuple[int, int]:
        """(bucket, pad lanes) for ``occupancy`` under the current ladder.

        The tuner picks the boundary; a replay mesh then rounds up to a
        batch-axis multiple so the request axis always splits evenly.
        """
        bucket = self.buckets.bucket_for(occupancy)
        bucket += (-bucket) % _shreplay.batch_axis_size(self.mesh)
        return bucket, bucket - occupancy

    def _build_batched(self, tenant: Tenant) -> Callable[..., tuple]:
        """One jitted cross-request batch callable on canonical slot names.

        ``fn(per_request, shared) -> tuple[dict, ...]`` where
        ``per_request`` is a tuple of per-member buffer dicts. Stacking the
        request axis, ``vmap``-ing the canonical region function over it,
        and re-slicing the outputs per member ALL happen inside the one
        jitted program — a whole batch costs a single dispatch, which is
        where coalescing beats serial replay. Shared buffers enter as
        unbatched jit arguments closed over inside the vmap body, i.e.
        broadcast — the cross-request analogue of ``WaveClass.shared``
        argument handling. Occupancy is a pytree shape, so one callable
        serves every batch size via jit's per-structure specialization.
        """
        with _kreg.kernel_mode_scope(tenant.kernel_mode):
            # The inner region function stays single-device (mesh=None):
            # the request axis vmapped below is the batch dim this server
            # shards, and nesting a second wave-level shard inside it would
            # constrain axes vmap has already consumed.
            base = _lower.lower_tdg(
                tenant.tdg, jit=False, fuse=self.fuse, mesh=None,
                outputs=list(tenant.outputs)
                if tenant.outputs is not None else None)
        from_canon = tenant.from_canon
        slot_map = tenant.slot_map
        mesh = self.mesh

        def canon_base(cbufs: dict) -> dict:
            out = base({from_canon[c]: v for c, v in cbufs.items()})
            return {slot_map[s]: v for s, v in out.items()}

        def batched(per_req: tuple, shared_bufs: dict) -> tuple:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=0), *per_req)
            # Split the stacked request axis across the replay mesh; the
            # occupancy bucket above is always a batch-axis multiple, so
            # the constraint never degrades to replicated.
            stacked = _shreplay.shard_leading(stacked, mesh)

            def one(st: dict) -> dict:
                return canon_base({**st, **shared_bufs})

            out = jax.vmap(one)(stacked)
            return tuple(
                jax.tree_util.tree_map(lambda v, _j=j: v[_j], out)
                for j in range(len(per_req)))

        batched.__name__ = f"tdg_batched_{tenant.tdg.region}"
        return jax.jit(batched)
