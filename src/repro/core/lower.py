"""Lower a TDG to a single fused JAX executable (the replay path).

The vanilla runtime walks the graph dynamically: per task it pays creation,
dependency resolution, queue locking and dispatch. Replay instead emits the
whole region as ONE pure function and compiles it once; XLA then owns
instruction scheduling, buffer reuse (donation) and overlap. This is the
TPU-native equivalent of the paper's "execute_TDG": zero per-task
orchestration at run time.

Three layers keep that compile-once story cheap at scale, and are the
extension points for future GPU/multi-host PRs:

* **Wave fusion** (``fuse.fused_tdg_as_function``, default on): each topo
  wave's isomorphic tasks lower as one ``vmap``-batched call, so the traced
  program is O(wave-classes), not O(tasks). ``fuse=False`` (or the
  ``REPRO_FUSE=0`` env var) restores the fully unrolled form; an explicit
  ``order`` implies unrolled, since fusion fixes wave order.
* **Structural interning** (``intern=True`` default under ``jit``): lowered
  executables are cached globally by the TDG's canonical structure
  (``tdg.structure_signature``) + payload identities + kernel substrate, so
  two regions — or two instances, or a region and a ``ReplayExecutor`` —
  with identical structure share ONE jitted callable (and therefore one
  XLA compilation per shape signature), instead of recompiling per source
  location. ``intern_stats()`` exposes the hit/miss counters.
* **AOT compilation** (:func:`aot_compile_tdg`): eagerly ``lower().compile()``
  for concrete buffer shapes, capturing XLA cost analysis and trace/compile
  wall times. The result is serializable via ``serialize.save_executable``
  so a TDG recorded in one process replays in another without retracing.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
from typing import Any, Callable, Mapping, Sequence

import jax

from . import costmodel as _costmodel
from . import fuse as _fuse
from . import schedule as _schedule
from . import spans as _spans
# Canonical definition lives in costmodel (the consumer of the numbers);
# re-exported here because this module captures it on every AotExecutable
# and tests/serialize reach it as lower._capture_cost_analysis.
from .costmodel import capture_cost_analysis as _capture_cost_analysis
from .tdg import TDG, structure_signature
from ..sharding import replay as _shreplay

_FUSE_ENV = "REPRO_FUSE"


def tdg_as_function(tdg: TDG, order: Sequence[int] | None = None,
                    outputs: Sequence[str] | None = None) -> Callable[[dict], dict]:
    """Return ``f(buffers) -> {slot: value}`` executing the TDG in ``order``.

    The returned function is pure and traceable: it can be jitted, vmapped,
    differentiated, pjit-sharded, or embedded as a task of an outer TDG
    (regions compose; the paper forbids *recursive* taskgraph directives and
    so do we — an inner region is inlined, not dynamically nested). This is
    the fully *unrolled* form — one emitted call per task; see
    ``fuse.fused_tdg_as_function`` for the wave-batched form.
    """
    order = list(order) if order is not None else _schedule.topo_order(tdg)
    outputs = list(outputs) if outputs is not None else list(tdg.output_slots)
    if not _schedule.validate_execution_order(tdg, order):
        raise ValueError(f"order does not respect TDG edges for {tdg.region!r}")

    def run(buffers: Mapping[str, Any]) -> dict:
        env = dict(buffers)
        _fuse._run_unrolled(tdg, order, env)
        return {s: env[s] for s in outputs}

    run.__name__ = f"tdg_{tdg.region}"
    return run


def fuse_enabled(fuse: bool | str = "auto") -> bool:
    """Resolve a ``fuse`` argument (True | False | "auto") to a decision.

    "auto" honours the ``REPRO_FUSE`` env var (0/false/off disables) and
    otherwise fuses: classification happens per trace anyway, and
    heterogeneous graphs degrade to the unrolled form class by class.
    """
    if fuse is True or fuse is False:
        return fuse
    if fuse != "auto":
        raise ValueError(f"fuse must be True, False or 'auto', got {fuse!r}")
    env = os.environ.get(_FUSE_ENV)
    if env is not None:
        return env.strip().lower() not in ("0", "false", "off", "no")
    return True


def _base_function(tdg: TDG, outputs, fuse: bool, min_class_size: int,
                   batcher: str, mesh=None) -> Callable[[dict], dict]:
    if fuse:
        return _fuse.fused_tdg_as_function(tdg, outputs=outputs,
                                           min_class_size=min_class_size,
                                           batcher=batcher, mesh=mesh)
    # The unrolled form has no stacked batch axis to shard: mesh is a
    # fused-path feature, and unrolled lowering is the single-device
    # fallback by construction.
    return tdg_as_function(tdg, outputs=outputs)


def _jit_with_donation(fn: Callable[[dict], dict],
                       donate_slots: tuple[str, ...]) -> Callable[[dict], dict]:
    """jit ``fn`` donating the buffers named in ``donate_slots``.

    ``donate_slots`` are buffer slots whose input storage may be reused for
    outputs (e.g. optimizer state, KV caches): the paper's "no allocation
    during TDG execution" maps to XLA buffer donation.
    """
    if not donate_slots:
        return jax.jit(fn)

    def split_fn(donated: dict, kept: dict) -> dict:
        return fn({**kept, **donated})

    jitted = jax.jit(split_fn, donate_argnums=0)

    @functools.wraps(fn)
    def wrapper(buffers: Mapping[str, Any]) -> dict:
        donated = {k: buffers[k] for k in donate_slots if k in buffers}
        kept = {k: v for k, v in buffers.items() if k not in donated}
        return jitted(donated, kept)

    return wrapper


# ------------------------------------------------------------- interning

@dataclasses.dataclass
class _InternEntry:
    payloads: tuple            # strong refs: pins the id()s the key relies on
    fn: Callable[[dict], dict]  # jitted, canonical slot names


_intern_lock = threading.Lock()
# LRU-bounded: entries pin their payload closures (that's what makes id()
# keys sound), so an unbounded cache would leak in processes that rebuild
# TDGs with fresh closures forever. Oldest-used entries are evicted at the
# cap; jax.jit keeps its own per-callable compilation cache alive only as
# long as the entry does.
_INTERN_CAP = max(int(os.environ.get("REPRO_INTERN_CACHE_SIZE", "256")), 1)
_intern_cache: collections.OrderedDict[tuple, _InternEntry] = \
    collections.OrderedDict()
_intern_counters = {"hits": 0, "misses": 0, "evictions": 0}


def intern_stats() -> dict:
    """Hit/miss counters + size of the global structural executable cache."""
    with _intern_lock:
        return {**_intern_counters, "entries": len(_intern_cache)}


def clear_intern_cache() -> None:
    with _intern_lock:
        _intern_cache.clear()
        for k in _intern_counters:
            _intern_counters[k] = 0


def _kernel_registry():
    from ..kernels import registry as _kreg

    return _kreg


def _interned_lower(tdg: TDG, outputs, donate_slots: tuple[str, ...],
                    fuse: bool, min_class_size: int,
                    batcher: str, mesh=None) -> Callable[[dict], dict]:
    sig, slot_map, payloads = structure_signature(tdg, outputs)
    canon_donate = tuple(sorted(
        slot_map[s] for s in donate_slots if s in slot_map))
    # The kernel substrate is baked into the trace, so it must key the cache:
    # two executors pinned to different substrates over one structure must
    # not share an executable. The keyed mode is re-entered around every
    # call of the shared executable (jit traces lazily at first call), so a
    # caller invoking the lowered fn under a *different* ambient mode cannot
    # poison the cache with a wrong-substrate trace. The mesh fingerprint
    # keys the cache for the same reason: sharding constraints are baked
    # into the trace, so a 1-device and an N-device lowering of one
    # structure must never share an executable.
    # The batcher component is the *plan* key, not the raw argument:
    # "vmap"/"map" literals for pinned plans, "auto/<thresholds>" for the
    # adaptive policy (costmodel.plan_key). Two lowerings of one structure
    # under different plans bake different dispatch into the trace and must
    # never share an executable; under REPRO_ADAPTIVE=0, "auto" resolves to
    # "vmap" and deliberately SHARES the static entry — the kill switch
    # restores pre-adaptive behaviour including its cache hits.
    kreg = _kernel_registry()
    mode = kreg.resolved_mode()
    key = (sig, tuple(id(p) for p in payloads), canon_donate, fuse,
           min_class_size, _costmodel.plan_key(batcher), mode,
           _shreplay.mesh_fingerprint(mesh))

    with _intern_lock:
        entry = _intern_cache.get(key)
        if entry is not None:
            _intern_counters["hits"] += 1
            _intern_cache.move_to_end(key)
        else:
            _intern_counters["misses"] += 1
    if entry is None:
        actual_outputs = (list(outputs) if outputs is not None
                          else list(tdg.output_slots))
        base = _base_function(tdg, actual_outputs, fuse, min_class_size,
                              batcher, mesh=mesh)
        from_canon = {c: a for a, c in slot_map.items()}

        def canon_run(cbuffers: dict) -> dict:
            out = base({from_canon[c]: v for c, v in cbuffers.items()})
            return {slot_map[s]: v for s, v in out.items()}

        canon_run.__name__ = f"tdg_interned_{tdg.region}"
        entry = _InternEntry(payloads, _jit_with_donation(canon_run,
                                                          canon_donate))
        with _intern_lock:
            entry = _intern_cache.setdefault(key, entry)
            _intern_cache.move_to_end(key)
            while len(_intern_cache) > _INTERN_CAP:
                _intern_cache.popitem(last=False)
                _intern_counters["evictions"] += 1

    to_canon = dict(slot_map)
    from_canon = {c: a for a, c in slot_map.items()}
    shared = entry.fn

    def run(buffers: Mapping[str, Any]) -> dict:
        # Slots unknown to the structure (extra keys) are dropped — they
        # cannot influence the program.
        with kreg.kernel_mode_scope(mode):
            out = shared({to_canon[k]: v for k, v in buffers.items()
                          if k in to_canon})
        return {from_canon[c]: v for c, v in out.items()}

    run.__name__ = f"tdg_{tdg.region}"
    return run


# -------------------------------------------------------------- entry point

def lower_tdg(
    tdg: TDG,
    order: Sequence[int] | None = None,
    outputs: Sequence[str] | None = None,
    donate_slots: Sequence[str] = (),
    jit: bool = True,
    fuse: bool | str = "auto",
    intern: bool | str = "auto",
    min_class_size: int = 2,
    batcher: str = "auto",
    mesh: Any = "auto",
) -> Callable[[dict], dict]:
    """Lower + (optionally) jit the TDG.

    ``fuse`` selects wave-fused lowering (see module docstring); an explicit
    ``order`` forces the unrolled form. ``intern="auto"`` shares the jitted
    executable globally across structurally identical TDGs whenever
    ``jit=True`` and no custom ``order`` is given; an explicit
    ``intern=True`` raises if those preconditions don't hold rather than
    silently skipping the cache.

    ``batcher`` picks how each fused wave class dispatches: ``"vmap"`` /
    ``"map"`` pin one batcher for every class (the pre-cost-model
    behaviour), ``"auto"`` (default) selects per class from probe-measured
    flops/bytes — see ``core.costmodel``; ``REPRO_ADAPTIVE=0`` collapses
    ``"auto"`` back to ``"vmap"``. The resolved *plan* (not the raw
    argument) keys the intern cache so different plans never collide.

    ``mesh`` shards every fused class's stacked batch axis across devices:
    a concrete ``jax.sharding.Mesh``, ``None`` (single-device), or
    ``"auto"`` (honour an ambient ``sharding.partition.use_mesh`` scope,
    then the ``REPRO_MESH`` env knob — see ``sharding.replay.resolve_mesh``).
    The resolved mesh's fingerprint keys the intern cache, so 1-device and
    N-device executables of one structure never collide.
    """
    donate_slots = tuple(donate_slots)
    do_fuse = fuse_enabled(fuse) and order is None
    mesh = _shreplay.resolve_mesh(mesh) if do_fuse else None
    if intern == "auto":
        intern = jit and order is None
    elif intern and (not jit or order is not None):
        raise ValueError("intern=True requires jit=True and order=None "
                         "(interned executables are jitted and wave-ordered)")
    if intern and jit and order is None:
        return _interned_lower(tdg, outputs, donate_slots, do_fuse,
                               min_class_size, batcher, mesh=mesh)
    fn = _base_function(tdg, outputs, do_fuse, min_class_size, batcher,
                        mesh=mesh) \
        if order is None else tdg_as_function(tdg, order=order, outputs=outputs)
    if not jit:
        return fn
    return _jit_with_donation(fn, donate_slots)


# ------------------------------------------------------------------ AOT path

@dataclasses.dataclass
class AotExecutable:
    """An ahead-of-time compiled replay executable for fixed buffer shapes.

    ``compiled`` is the underlying ``jax.stages.Compiled`` (or loaded
    deserialized executable); calling the object runs it on a buffer dict
    (extra keys are dropped). ``cost_analysis`` is XLA's flops/bytes
    estimate captured at compile time, when available.
    """

    compiled: Any
    input_specs: dict
    fused: bool
    donate_slots: tuple[str, ...] = ()
    cost_analysis: dict | None = None
    trace_seconds: float = 0.0
    compile_seconds: float = 0.0
    #: ``sharding.replay.mesh_fingerprint`` of the mesh this executable was
    #: compiled under (``None`` = single-device). Rides the artifact's
    #: topology fingerprint so an 8-device binary is rejected loudly on a
    #: worker whose replay mesh differs.
    mesh_fp: str | None = None
    #: The ``fuse.FusionPlan`` the trace applied (``None`` when unfused):
    #: per-class batchers, pad lanes and any trace-time fallbacks.
    plan: Any = None

    @property
    def flops(self) -> float | None:
        return (self.cost_analysis or {}).get("flops")

    @property
    def bytes_accessed(self) -> float | None:
        return (self.cost_analysis or {}).get("bytes accessed")

    def __call__(self, buffers: Mapping[str, Any]) -> dict:
        args = {k: buffers[k] for k in self.input_specs}
        if self.donate_slots:
            donated = {k: args.pop(k) for k in self.donate_slots if k in args}
            return self.compiled(donated, args)
        return self.compiled(args)


def aot_compile_tdg(
    tdg: TDG,
    buffers: Mapping[str, Any],
    outputs: Sequence[str] | None = None,
    donate_slots: Sequence[str] = (),
    fuse: bool | str = "auto",
    min_class_size: int = 2,
    batcher: str = "auto",
    mesh: Any = "auto",
) -> AotExecutable:
    """Eagerly trace + compile the replay executable for ``buffers``' shapes.

    ``buffers`` may hold concrete arrays or ``ShapeDtypeStruct`` trees — no
    data is touched. Unlike the lazy ``jax.jit`` path, compilation happens
    here and now, so a warmup step (or another process, via
    ``serialize.save_executable``) can pay it off the critical path; XLA's
    cost analysis and the trace/compile wall times are captured on the
    result for benchmark and placement decisions. ``donate_slots`` buffers
    are donated exactly as in the lazy path.
    """
    from .tdg import abstract_leaf

    do_fuse = fuse_enabled(fuse)
    mesh = _shreplay.resolve_mesh(mesh) if do_fuse else None
    fn = _base_function(tdg, outputs, do_fuse, min_class_size, batcher,
                        mesh=mesh)
    specs = {k: jax.tree_util.tree_map(abstract_leaf, v)
             for k, v in buffers.items()}
    donate_slots = tuple(k for k in donate_slots if k in specs)
    with _spans.span("taskgraph.warmup.trace") as trace_span:
        if donate_slots:
            def split_fn(donated: dict, kept: dict) -> dict:
                return fn({**kept, **donated})

            donated_specs = {k: specs[k] for k in donate_slots}
            kept_specs = {k: v for k, v in specs.items()
                          if k not in donated_specs}
            lowered = jax.jit(split_fn, donate_argnums=0).lower(
                donated_specs, kept_specs)
        else:
            lowered = jax.jit(fn).lower(specs)
    with _spans.span("taskgraph.warmup.compile") as compile_span:
        compiled = lowered.compile()
    return AotExecutable(compiled=compiled, input_specs=specs, fused=do_fuse,
                         donate_slots=donate_slots,
                         cost_analysis=_capture_cost_analysis(compiled),
                         trace_seconds=trace_span.record.seconds,
                         compile_seconds=compile_span.record.seconds,
                         mesh_fp=_shreplay.mesh_fingerprint(mesh),
                         plan=getattr(fn, "last_plan", None))
