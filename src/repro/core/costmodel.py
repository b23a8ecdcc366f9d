"""Cost-model-driven grain decisions: measured flops/bytes pick the batcher.

The paper's thesis is that a recorded TDG lets the *runtime* absorb task
management cost; Worksharing Tasks (arXiv 2004.03258) extends the argument
to grain size — how work is chunked should be a runtime decision, made from
observation, not a call-site constant. Until this module, the repo still
decided grain statically in two places: ``core/fuse.py`` batched every
fused wave class with ``vmap`` (or a caller-chosen ``lax.map``), and
``serving/server.py`` bucketed batch occupancy to fixed powers of two.
Meanwhile ``lower.aot_compile_tdg`` was already *capturing* XLA cost
analysis that nothing consumed.

This module closes the loop with two decision engines:

* :class:`CostModel` — per-wave-class batcher selection. Each fused class's
  payload is probed once (``jit(fn).lower(specs).compile()``) for XLA's
  ``flops`` / ``"bytes accessed"``; their ratio (arithmetic intensity,
  flops/byte) classifies the class:

  - **compute-bound** (intensity >= ``ridge``): ``vmap`` — one batched
    kernel amortizes fixed cost and exposes the batch dim to the compiler
    (and to mesh sharding).
  - **memory-bound** (intensity < ``ridge``) with a *cache-resident member
    but cache-overflowing batch* (``bytes <= map_member_bytes`` and
    ``size * bytes >= map_total_bytes``): ``lax.map`` — streaming lanes
    sequentially keeps the working set one member deep instead of
    materializing the whole stacked batch. Members too large to ever be
    cache-resident gain nothing from streaming (the scan's per-lane
    slice-in/slice-out copies only add traffic) and stay ``vmap``.
  - **below the fused-overhead break-even** (``size * flops <
    unroll_flops``): ``unrolled`` — for near-free bodies the stack/unstack
    machinery costs more than just inlining the handful of ops.

  The model also holds the cut break-even ``split_call_bytes``: the
  stacking copy a fused call must save before ``fuse`` cuts a class into
  sub-classes on a repeated argument.

  Unmeasurable payloads (no ``cost_analysis`` on this backend, probe
  failure, or XLA's ``-1`` "unknown flops" sentinel — CPU triangular solve
  reports this) fall back to ``vmap``, the static heuristic this model
  replaces, so adaptivity never makes an *unmeasured* bet.

* :class:`BucketTuner` — adaptive occupancy buckets for the serving tier.
  Observed batch occupancies accumulate into a histogram; every ``window``
  observations (or earlier, when the recent pad fraction drifts past
  ``drift_pad_fraction``) the tuner refits up to ``max_buckets`` bucket
  boundaries minimizing total pad lanes (exact small DP), replacing the
  fixed pow-2 ladder. Every *new* boundary value is one more jit
  specialization of the pooled batched executable, so a lifetime
  ``max_new_buckets`` budget bounds retracing; when it is spent, the
  boundaries freeze.

``REPRO_ADAPTIVE=0`` is the kill switch for BOTH engines: batcher
selection resolves back to static ``vmap`` and the tuner pins the pow-2
ladder. :func:`plan_key` fingerprints the active policy (thresholds and
all) for the intern/replay caches, so executables lowered under different
plans never collide — flipping the switch (or a threshold) re-lowers
instead of serving a stale plan.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
from typing import Any, Callable, Iterable, Mapping, Sequence

from . import spans as _spans

ADAPTIVE_ENV = "REPRO_ADAPTIVE"

#: Arithmetic-intensity ridge (flops/byte) separating compute-bound from
#: memory-bound classes. Deliberately modest: anything with real arithmetic
#: reuse (blocked matmul at >= 32x32) clears it, elementwise/stencil/BLAS-1
#: bodies (0.1-0.3 flops/byte) fall below.
DEFAULT_RIDGE = 1.0
#: ``lax.map`` upper bound on one member's bytes accessed: past this a
#: member can't be cache-resident, so streaming lanes buys nothing.
DEFAULT_MAP_MEMBER_BYTES = 512 * 1024
#: ``lax.map`` lower bound on the stacked class's total bytes: below this
#: the whole batch is cache-resident and one fused vmap kernel wins.
DEFAULT_MAP_TOTAL_BYTES = 128 * 1024
#: Unrolled break-even: classes whose TOTAL measured flops fall below this
#: are cheaper inlined than stacked/unstacked.
DEFAULT_UNROLL_FLOPS = 256.0
#: Cut break-even (``fuse._split``): bytes of stacking copy a fused call
#: must save to be worth adding, when a class is cut into sub-classes on a
#: repeated argument. A TPU v5e streams 256 KiB in about 0.3 us (819
#: GB/s), about what one more op in a program costs it: tiled linear
#: algebra (tiles of 64 KiB and up) is cut, a class of tiny members with
#: many distinct repeated slots stays whole.
DEFAULT_SPLIT_CALL_BYTES = 256 * 1024


def adaptive_enabled(arg: bool | str = "auto") -> bool:
    """Resolve an ``adaptive`` argument (True | False | "auto").

    "auto" honours ``REPRO_ADAPTIVE`` (0/false/off/no disables) and
    otherwise enables cost-model-driven decisions.
    """
    if arg is True or arg is False:
        return arg
    if arg != "auto":
        raise ValueError(f"adaptive must be True, False or 'auto', got {arg!r}")
    env = os.environ.get(ADAPTIVE_ENV)
    if env is not None:
        return env.strip().lower() not in ("0", "false", "off", "no")
    return True


def capture_cost_analysis(compiled: Any) -> dict | None:
    """``compiled.cost_analysis()`` as a plain dict, or None if there is none.

    The installed jax returns a dict, and raises ``NotImplementedError`` on
    a backend without an analysis (also exported as
    ``lower._capture_cost_analysis``).
    """
    try:
        ca = compiled.cost_analysis()
    except NotImplementedError:
        return None
    return dict(ca) if ca else None


@dataclasses.dataclass(frozen=True)
class ClassCost:
    """Measured per-member cost of one wave class's payload.

    ``flops`` / ``bytes_accessed`` are None when the backend offered no
    (usable) analysis — XLA's ``-1`` "unknown" sentinel is normalized to
    None here so downstream math never divides by a lie.
    """

    flops: float | None
    bytes_accessed: float | None
    source: str = "measured"        # "measured" | "unavailable"

    @property
    def intensity(self) -> float | None:
        """Arithmetic intensity in flops/byte, or None if unmeasured."""
        if self.flops is None or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed


UNMEASURED = ClassCost(flops=None, bytes_accessed=None, source="unavailable")


@dataclasses.dataclass(frozen=True)
class BatcherDecision:
    """One batcher choice plus the numbers that drove it."""

    batcher: str                    # "vmap" | "map" | "unrolled"
    reason: str                     # human-auditable, names the inputs
    cost: ClassCost
    size: int

    def describe(self) -> dict:
        """JSON-safe record for plan summaries and the cost report."""
        inten = self.cost.intensity
        return {
            "batcher": self.batcher,
            "size": self.size,
            "flops": self.cost.flops,
            "bytes": self.cost.bytes_accessed,
            "intensity": None if inten is None else round(inten, 4),
            "reason": self.reason,
        }


class CostModel:
    """Measured flops/bytes -> per-class batcher decisions (see module doc).

    Probe results are cached per (payload identity, arg signature, kernel
    mode) — a payload's cost is paid once per shape, not once per trace —
    with strong references pinning the payload exactly like the intern
    cache, so ``id()`` keys stay sound.
    """

    def __init__(self, ridge: float = DEFAULT_RIDGE,
                 map_member_bytes: int = DEFAULT_MAP_MEMBER_BYTES,
                 map_total_bytes: int = DEFAULT_MAP_TOTAL_BYTES,
                 unroll_flops: float = DEFAULT_UNROLL_FLOPS,
                 split_call_bytes: int = DEFAULT_SPLIT_CALL_BYTES,
                 cache_size: int = 512):
        self.ridge = float(ridge)
        self.map_member_bytes = int(map_member_bytes)
        self.map_total_bytes = int(map_total_bytes)
        self.unroll_flops = float(unroll_flops)
        self.split_call_bytes = int(split_call_bytes)
        self._lock = threading.Lock()
        self._cache_size = max(1, int(cache_size))
        # key -> (payload strong ref, ClassCost)
        self._cache: collections.OrderedDict[tuple, tuple] = \
            collections.OrderedDict()
        self.probes = 0
        self.probe_failures = 0

    def fingerprint(self) -> str:
        """Threshold fingerprint — part of the adaptive plan's cache key."""
        return (f"r{self.ridge:g}-m{self.map_member_bytes}"
                f"-t{self.map_total_bytes}-u{self.unroll_flops:g}"
                f"-c{self.split_call_bytes}")

    # -- measurement -------------------------------------------------------
    def measure(self, fn: Callable, arg_specs: Sequence[Any]) -> ClassCost:
        """Probe-compile ``fn`` for ``arg_specs`` and read XLA's analysis.

        ``arg_specs`` are ShapeDtypeStruct trees (ONE member's arguments,
        not the stacked batch). Probing is a real, tiny, independent
        compile — legal mid-trace because only abstract shapes cross into
        it — and every failure degrades to :data:`UNMEASURED`.
        """
        try:
            sig = tuple(_spec_signature(s) for s in arg_specs)
        except Exception:
            return UNMEASURED
        key = (id(fn), sig, _ambient_kernel_mode())
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                return hit[1]
        cost = self._probe(fn, arg_specs)
        with self._lock:
            self._cache[key] = (fn, cost)
            self._cache.move_to_end(key)
            while len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)
        return cost

    def _probe(self, fn: Callable, arg_specs: Sequence[Any]) -> ClassCost:
        import jax

        self.probes += 1
        _spans.count("taskgraph.costmodel.probes")
        try:
            with _spans.span("taskgraph.costmodel.probe",
                             payload=getattr(fn, "__name__", "?")):
                compiled = jax.jit(fn).lower(*arg_specs).compile()
        except Exception:
            self.probe_failures += 1
            return UNMEASURED
        ca = capture_cost_analysis(compiled) or {}
        flops = ca.get("flops")
        bytes_accessed = ca.get("bytes accessed")
        # XLA reports -1 for ops it cannot count (CPU triangular solve):
        # that is "unknown", not "free" — normalize to unmeasured.
        if flops is None or flops < 0:
            flops = None
        if bytes_accessed is None or bytes_accessed < 0:
            bytes_accessed = None
        if flops is None and bytes_accessed is None:
            return UNMEASURED
        return ClassCost(flops=flops, bytes_accessed=bytes_accessed)

    def clear_cache(self) -> None:
        with self._lock:
            self._cache.clear()

    # -- decision ----------------------------------------------------------
    def decide(self, cost: ClassCost, size: int) -> BatcherDecision:
        """Pick vmap | map | unrolled for a class of ``size`` members."""
        size = max(1, int(size))
        flops, nbytes, inten = cost.flops, cost.bytes_accessed, cost.intensity
        if flops is None and nbytes is None:
            return BatcherDecision(
                "vmap", "unmeasured payload: static fallback", cost, size)
        if flops is not None and size * flops < self.unroll_flops:
            return BatcherDecision(
                "unrolled",
                f"{size}x{flops:g} flops < break-even {self.unroll_flops:g}",
                cost, size)
        if inten is not None and inten < self.ridge and nbytes is not None:
            if (nbytes <= self.map_member_bytes
                    and size * nbytes >= self.map_total_bytes):
                return BatcherDecision(
                    "map",
                    f"memory-bound ({inten:.3g} flops/B < ridge "
                    f"{self.ridge:g}), member {nbytes:g}B cache-resident, "
                    f"batch {size * nbytes:g}B is not",
                    cost, size)
            return BatcherDecision(
                "vmap",
                f"memory-bound ({inten:.3g} flops/B) but "
                f"{'member too large to stream' if nbytes > self.map_member_bytes else 'whole batch cache-resident'}",
                cost, size)
        shown = "unknown" if inten is None else f"{inten:.3g}"
        return BatcherDecision(
            "vmap", f"compute-bound ({shown} flops/B >= ridge "
            f"{self.ridge:g})", cost, size)

    def decide_for(self, fn: Callable, arg_specs: Sequence[Any],
                   size: int) -> BatcherDecision:
        return self.decide(self.measure(fn, arg_specs), size)


_default_model = CostModel()


def default_model() -> CostModel:
    """The process-wide cost model (what ``batcher="auto"`` consults)."""
    return _default_model


def _spec_signature(spec: Any) -> tuple:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(spec)
    return (str(treedef), tuple((tuple(l.shape), str(l.dtype))
                                for l in leaves))


def _ambient_kernel_mode() -> str | None:
    try:
        from ..kernels import registry as _kreg

        return _kreg.resolved_mode()
    except Exception:  # pragma: no cover - kernels layer optional here
        return None


# -------------------------------------------------------- batcher resolution

_BATCHERS = ("vmap", "map", "auto")


def resolve_batcher(batcher: str) -> str:
    """Resolve a ``batcher`` argument to the active policy.

    ``"auto"`` stays ``"auto"`` when adaptivity is on and collapses to
    ``"vmap"`` (the static heuristic the model replaces) under
    ``REPRO_ADAPTIVE=0`` — the kill switch restores pre-adaptive behaviour
    exactly. Static policies pass through.
    """
    if batcher not in _BATCHERS:
        raise ValueError(f"batcher must be one of {_BATCHERS}, got {batcher!r}")
    if batcher == "auto" and not adaptive_enabled():
        return "vmap"
    return batcher


def plan_key(batcher: str) -> str:
    """Cache-key component naming the batcher *plan*, not just the arg.

    Two lowerings of one structure under different plans (static vmap vs
    adaptive, or adaptive under different thresholds) must never share an
    executable: the decisions are baked into the trace. The adaptive key
    carries the model's threshold fingerprint so even a threshold change
    re-lowers. A static plan carries the cut break-even (``c<bytes>``),
    which decides the classes under every batcher.
    """
    resolved = resolve_batcher(batcher)
    if resolved == "auto":
        return f"auto/{default_model().fingerprint()}"
    return f"{resolved}/c{default_model().split_call_bytes}"


# ------------------------------------------------------------ bucket fitting

def pow2_boundaries(max_batch: int) -> list[int]:
    """The static ladder: 2, 4, 8, ... up to (at least) ``max_batch``."""
    bounds = [2]
    while bounds[-1] < max(2, int(max_batch)):
        bounds.append(bounds[-1] * 2)
    return bounds


def fit_boundaries(histogram: Mapping[int, int], max_buckets: int,
                   floor: int = 2) -> list[int]:
    """Choose <= ``max_buckets`` bucket boundaries minimizing pad lanes.

    ``histogram`` maps observed occupancy -> count (occupancies below
    ``floor`` are ignored: a group of one never takes the batched path).
    Boundaries are drawn from the observed occupancies themselves — any
    other value only adds padding — and always include the maximum, so
    every observed occupancy has a bucket. Exact DP over the (small,
    <= max_batch) distinct-occupancy domain; deterministic.
    """
    vals = sorted(v for v, c in histogram.items() if v >= floor and c > 0)
    if not vals:
        return []
    cnts = [histogram[v] for v in vals]
    d = len(vals)
    k_max = max(1, min(int(max_buckets), d))

    def seg_cost(i: int, j: int) -> int:
        # members in vals[i..j] all pad up to vals[j]
        return sum(cnts[t] * (vals[j] - vals[t]) for t in range(i, j + 1))

    INF = float("inf")
    dp = [[INF] * d for _ in range(k_max + 1)]
    back: list[list[int]] = [[-1] * d for _ in range(k_max + 1)]
    for j in range(d):
        dp[1][j] = seg_cost(0, j)
    for k in range(2, k_max + 1):
        for j in range(k - 1, d):
            for i in range(k - 2, j):
                cand = dp[k - 1][i] + seg_cost(i + 1, j)
                if cand < dp[k][j]:
                    dp[k][j] = cand
                    back[k][j] = i
    best_k = min(range(1, k_max + 1), key=lambda k: dp[k][d - 1])
    bounds = []
    j, k = d - 1, best_k
    while j >= 0 and k >= 1:
        bounds.append(vals[j])
        j = back[k][j]
        k -= 1
    return sorted(bounds)


class BucketTuner:
    """Occupancy buckets fitted from the live histogram (serving tier).

    Starts on the pow-2 ladder (identical to the static server), observes
    every batched occupancy, and — when adaptive — refits boundaries every
    ``window`` observations, or early when the recent pad fraction drifts
    past ``drift_pad_fraction``. Each *new* boundary value is a fresh jit
    specialization of the pooled batched executable, so a lifetime
    ``max_new_buckets`` retrace budget bounds tuning; once spent, the
    boundaries freeze. Thread-safe (the server's scheduler thread and
    stats() callers race).
    """

    def __init__(self, max_batch: int, adaptive: bool | str = "auto",
                 window: int = 64, max_buckets: int = 8,
                 max_new_buckets: int = 16,
                 drift_pad_fraction: float = 0.35):
        self.max_batch = max(1, int(max_batch))
        self.adaptive = adaptive_enabled(adaptive)
        self.window = max(1, int(window))
        self.max_buckets = max(1, int(max_buckets))
        self.max_new_buckets = max(0, int(max_new_buckets))
        self.drift_pad_fraction = float(drift_pad_fraction)
        self._lock = threading.Lock()
        self.boundaries: list[int] = pow2_boundaries(self.max_batch)
        self._histogram: collections.Counter = collections.Counter()
        self._recent: collections.deque = collections.deque(maxlen=self.window)
        self.observations = 0
        self.retunes = 0
        self.new_buckets_spent = 0
        self.pad_lanes = 0
        self.lanes = 0

    def bucket_for(self, occupancy: int) -> int:
        """Smallest boundary >= occupancy (pow-2-extended past the ladder)."""
        n = max(1, int(occupancy))
        if n <= 1:
            return 1
        with self._lock:
            for b in self.boundaries:
                if b >= n:
                    return b
            top = self.boundaries[-1] if self.boundaries else 2
        while top < n:
            top *= 2
        return top

    def observe(self, occupancy: int) -> bool:
        """Record one batched occupancy; True iff boundaries just changed.

        The caller (the server) treats True as "stale pooled executables":
        old bucket sizes' specializations are dead weight and new ones
        would accrete beside them, so it invalidates the pooled batched
        entries and lets the next step rebuild against the new ladder.
        """
        n = int(occupancy)
        if n < 2:
            return False
        pad = self.bucket_for(n) - n
        with self._lock:
            self._histogram[n] += 1
            self._recent.append((n, pad))
            self.observations += 1
            self.pad_lanes += pad
            self.lanes += n + pad
            if not self.adaptive or self.new_buckets_spent >= self.max_new_buckets:
                return False
            due = self.observations % self.window == 0
            if not due and len(self._recent) >= self.window:
                recent_lanes = sum(o + p for o, p in self._recent)
                recent_pad = sum(p for _, p in self._recent)
                due = (recent_lanes > 0
                       and recent_pad / recent_lanes > self.drift_pad_fraction)
            if not due:
                return False
            fitted = fit_boundaries(self._histogram, self.max_buckets)
            if not fitted or fitted == self.boundaries:
                return False
            new = [b for b in fitted if b not in self.boundaries]
            budget_left = self.max_new_buckets - self.new_buckets_spent
            if len(new) > budget_left:
                # Keep the most frequent new boundaries within budget; the
                # rest of the fit is discarded rather than half-applied.
                new = sorted(new, key=lambda b: -self._histogram[b])[:budget_left]
                fitted = sorted(set(new) | {max(self._histogram)})
                if not new:
                    return False
            self.new_buckets_spent += len(new)
            self.boundaries = fitted
            self.retunes += 1
            self._recent.clear()
            return True

    def summary(self) -> dict:
        with self._lock:
            return {
                "adaptive": self.adaptive,
                "boundaries": list(self.boundaries),
                "observations": self.observations,
                "retunes": self.retunes,
                "new_buckets_spent": self.new_buckets_spent,
                "retrace_budget": self.max_new_buckets,
                "pad_lanes": self.pad_lanes,
                "pad_fraction": round(self.pad_lanes / self.lanes, 4)
                if self.lanes else 0.0,
                "histogram": {str(k): v for k, v in
                              sorted(self._histogram.items())},
            }
