"""Record-and-replay + static TDG construction (the `taskgraph` directive).

``@taskgraph`` marks a *fully-taskified region*: a Python builder function
``fn(g, **buffers)`` whose only effects are ``g.task(...)`` spawns over named
buffer slots (plus deterministic, task-free control flow — the paper's
conformance requirements §4.1). The framework then chooses, exactly like
Algorithm 4.1 of the paper:

  * **static TDG** (``build_static``): if the region's control flow is
    computable from configuration alone, the TDG is built ahead of time by
    abstract evaluation (``jax.eval_shape`` stand-ins; no data touched) —
    the compile-time TDG of paper Fig. 4b/4d. Constants already bound in the
    tasks' closures play the role of "known data" (4d); everything else is
    ``fill_data`` at call time (4b).
  * **record** (first call): the region executes eagerly *while being
    recorded* — every task spawn resolves its depend clauses against the
    last-writer/readers table once, and runs.
  * **replay** (subsequent calls): the cached TDG is lowered to one fused
    executable and re-executed with zero per-task orchestration.

Regions are registered by *source location* (file, line) exactly as the
paper keys TDGs (§4.3.3). Instances of one region are sequentialized unless
``nowait=True`` (the paper's default semantics).

Replay executables are produced by ``lower.lower_tdg`` with wave fusion on
by default (``fuse`` parameter; see ``fuse.py``) and are *interned by
structure*: two regions with identical task/edge/payload structure share
one compiled executable via the global cache in ``lower.py``, so the
source-location registry keys region *identity* (instance sequencing,
stats) but no longer implies per-location recompilation. The per-region
``_replay_cache`` is keyed by ``(buffers_signature, resolved kernel
mode, mesh fingerprint, plan key)`` — flipping ``REPRO_KERNELS`` between
replays re-lowers instead of returning a stale-substrate executable. The
buffers' signature is one flatten of the whole buffer dict, so the key
costs one pass over the slots per replay; the counters
``taskgraph.replay.cache_hit`` and ``taskgraph.replay.cache_miss`` say
which replays found an executable. ``warmup()`` AOT-compiles a
signature off the critical path (and is what ``serialize.save_executable``
persists for cross-process no-retrace replay).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Mapping

import jax

from . import costmodel as _costmodel
from . import lower as _lower
from . import schedule as _schedule
from . import spans as _spans
from .tdg import TDG, Task, buffers_signature
from ..kernels import registry as _kreg
from ..sharding import replay as _shreplay

_REGISTRY: dict[tuple, "TaskGraphRegion"] = {}
_registry_lock = threading.Lock()


def registry() -> dict[tuple, "TaskGraphRegion"]:
    return dict(_REGISTRY)


def reset_registry() -> None:
    with _registry_lock:
        _REGISTRY.clear()


class GraphBuilder:
    """The ``g`` handle passed to region builder functions."""

    def __init__(self, tdg: TDG, env: dict | None, abstract: bool):
        self._tdg = tdg
        self._env = env
        self._abstract = abstract

    @property
    def tdg(self) -> TDG:
        return self._tdg

    def task(self, fn: Callable, ins=(), outs=(), inouts=(), name: str = "",
             cost_hint: float = 1.0, **metadata) -> Task:
        """Spawn a task (``#pragma omp task depend(...)``)."""
        task = self._tdg.add_task(fn, ins=ins, outs=outs, inouts=inouts,
                                  name=name, cost_hint=cost_hint, **metadata)
        if self._env is not None:
            args = [self._env[s] for s in task.ins]
            if self._abstract:
                out = jax.eval_shape(fn, *args)
            else:
                out = fn(*args)
            if len(task.outs) == 1:
                self._env[task.outs[0]] = out
            elif len(task.outs) > 1:
                for s, v in zip(task.outs, out):
                    self._env[s] = v
        return task

    def slots(self) -> list[str]:
        return list(self._env) if self._env is not None else []


@dataclasses.dataclass
class _CacheEntry:
    """One replay-cache entry: the executable, and the leaf counts of its
    arguments and outputs once a replay has counted them (the attributes of
    the ``taskgraph.replay`` span, counted once per entry)."""

    fn: Callable
    leaves: tuple[int, int] | None = None


class TaskGraphRegion:
    """A taskgraph region: static-or-recorded TDG + replay cache."""

    def __init__(self, build_fn: Callable, name: str | None = None,
                 nowait: bool = False, donate_slots: tuple[str, ...] = (),
                 recurrent: bool = True, outputs: tuple[str, ...] | None = None,
                 fuse: bool | str = "auto", batcher: str = "auto",
                 mesh: Any = "auto"):
        code = build_fn.__code__
        self.build_fn = build_fn
        self.outputs = tuple(outputs) if outputs is not None else None
        self.fuse = fuse
        # Like mesh below, kept unresolved: "auto" re-reads REPRO_ADAPTIVE
        # per replay via costmodel.plan_key, which keys the replay cache.
        self.batcher = batcher
        # Kept UNresolved ("auto" stays "auto"): regions are typically
        # constructed at import time by the decorator, and resolving an env
        # mesh builds device meshes — replay resolves per call instead
        # (mirroring resolved_mode below), keyed into the replay cache.
        self.mesh = mesh
        self.name = name or build_fn.__name__
        # paper §4.3.3: TDGs are identified by source location
        self.source_location = (code.co_filename, code.co_firstlineno, self.name)
        self.nowait = nowait
        self.donate_slots = tuple(donate_slots)
        self.recurrent = recurrent
        self.tdg: TDG | None = None
        self.static = False
        self._replay_cache: dict[tuple, _CacheEntry] = {}
        self.records = 0
        self.replays = 0
        with _registry_lock:
            if self.source_location in _REGISTRY:
                raise ValueError(
                    f"taskgraph region already registered at {self.source_location} "
                    "(the directive cannot be declared recursively, paper §4.1)")
            _REGISTRY[self.source_location] = self

    # -- TDG construction ---------------------------------------------------
    def build_static(self, **buffer_specs) -> TDG:
        """Compile-time TDG from abstract buffer shapes (paper Fig. 4b/4d)."""
        tdg = TDG(region=self.name)
        env = {k: _abstractify(v) for k, v in buffer_specs.items()}
        self.build_fn(GraphBuilder(tdg, env, abstract=True), **buffer_specs)
        tdg.validate()
        self.tdg = tdg
        self.static = True
        return tdg

    def record(self, **buffers) -> dict:
        """First execution: run eagerly while recording (paper §4.3.2)."""
        with _spans.span("taskgraph.record", region=self.name) as sp:
            tdg = TDG(region=self.name)
            env = dict(buffers)
            self.build_fn(GraphBuilder(tdg, env, abstract=False), **buffers)
            tdg.validate()
            self.tdg = tdg
            self.static = False
            self.records += 1
            out = {s: env[s] for s in (self.outputs or tdg.output_slots)}
            if not self.nowait:
                jax.block_until_ready(out)
            sp.set(tasks=tdg.num_tasks)
        return out

    # -- execution ------------------------------------------------------------
    def replay(self, **buffers) -> dict:
        if self.tdg is None:
            raise RuntimeError(f"region {self.name!r} has no TDG yet")
        # Pin the kernel substrate per executable: the cache key carries the
        # resolved mode (like ReplayExecutor), so flipping REPRO_KERNELS
        # between replays re-lowers instead of serving a stale substrate.
        # The replay mesh resolves (and keys) the same way, so flipping
        # REPRO_MESH between replays re-lowers too.
        with _spans.span("taskgraph.replay", region=self.name) as sp:
            with _spans.span("taskgraph.replay.key"):
                mode = _kreg.resolved_mode()
                mesh = _shreplay.resolve_mesh(self.mesh)
                sig = (buffers_signature(buffers), mode,
                       _shreplay.mesh_fingerprint(mesh),
                       _costmodel.plan_key(self.batcher))
                entry = self._replay_cache.get(sig)
            with _kreg.kernel_mode_scope(mode):
                if entry is None:
                    with _spans.span("taskgraph.replay.lower"):
                        _spans.count("taskgraph.replay.cache_miss")
                        entry = _CacheEntry(_lower.lower_tdg(
                            self.tdg, donate_slots=self.donate_slots,
                            outputs=self.outputs, fuse=self.fuse,
                            batcher=self.batcher, mesh=mesh))
                    self._replay_cache[sig] = entry
                else:
                    _spans.count("taskgraph.replay.cache_hit")
                with _spans.span("taskgraph.replay.dispatch"):
                    out = entry.fn(buffers)
            self.replays += 1
            if entry.leaves is None:
                entry.leaves = (len(jax.tree_util.tree_leaves(buffers)),
                                len(jax.tree_util.tree_leaves(out)))
            sp.set(args=entry.leaves[0], outputs=entry.leaves[1])
            if not self.nowait:
                with _spans.span("taskgraph.replay.wait"):
                    jax.block_until_ready(out)
        return out

    def warmup(self, **buffers) -> _lower.AotExecutable:
        """AOT-compile the replay executable for these buffer shapes.

        ``buffers`` may be real arrays or ``ShapeDtypeStruct`` specs (pair
        with ``build_static`` for a fully data-free warmup). The compiled
        executable is installed in the replay cache, so the next matching
        call replays without tracing or compiling anything — and the
        returned ``AotExecutable`` can be persisted for other processes via
        ``serialize.save_executable``.
        """
        if self.tdg is None:
            raise RuntimeError(
                f"region {self.name!r} has no TDG yet — call build_static() "
                "or record once before warming up")
        mode = _kreg.resolved_mode()
        mesh = _shreplay.resolve_mesh(self.mesh)
        with _spans.span("taskgraph.warmup", region=self.name), \
                _kreg.kernel_mode_scope(mode):
            aot = _lower.aot_compile_tdg(self.tdg, buffers,
                                         outputs=self.outputs,
                                         donate_slots=self.donate_slots,
                                         fuse=self.fuse, batcher=self.batcher,
                                         mesh=mesh)
        sig = (buffers_signature(buffers), mode,
               _shreplay.mesh_fingerprint(mesh),
               _costmodel.plan_key(self.batcher))
        self._replay_cache[sig] = _CacheEntry(aot)
        return aot

    def __call__(self, **buffers) -> dict:
        if self.tdg is None:
            if self.recurrent:
                return self.record(**buffers)
            # non-recurrent region: no point building a TDG (Algorithm 4.1
            # line 23: fall back to plain task instantiation) — run eagerly.
            tdg = TDG(region=self.name)
            env = dict(buffers)
            self.build_fn(GraphBuilder(tdg, env, abstract=False), **buffers)
            return {s: env[s] for s in (self.outputs or tdg.output_slots)}
        return self.replay(**buffers)

    # -- introspection ----------------------------------------------------------
    def as_function(self) -> Callable[[dict], dict]:
        """The replayable pure function (for grad / pjit / outer-TDG embedding)."""
        if self.tdg is None:
            raise RuntimeError(f"region {self.name!r} has no TDG yet")
        return _lower.tdg_as_function(self.tdg, outputs=self.outputs)

    def schedule_summary(self, n_workers: int = 8) -> dict:
        assert self.tdg is not None
        from . import fuse as _fuse

        waves = _schedule.topo_waves(self.tdg)
        return {
            "fusion": _fuse.plan(self.tdg).summary(),
            "tasks": self.tdg.num_tasks,
            "edges": self.tdg.num_edges,
            "roots": len(self.tdg.roots()),
            "waves": len(waves),
            "max_wave_width": max((len(w) for w in waves), default=0),
            "parallelism": _schedule.parallelism(self.tdg),
            "dep_lookups_at_record": self.tdg.dep_lookups(),
        }


def taskgraph(fn: Callable | None = None, *, name: str | None = None,
              nowait: bool = False, donate_slots: tuple[str, ...] = (),
              recurrent: bool = True, outputs: tuple[str, ...] | None = None,
              fuse: bool | str = "auto", batcher: str = "auto",
              mesh: Any = "auto"):
    """Decorator form: ``@taskgraph`` / ``@taskgraph(nowait=True)``."""

    def wrap(f: Callable) -> TaskGraphRegion:
        return TaskGraphRegion(f, name=name, nowait=nowait,
                               donate_slots=donate_slots, recurrent=recurrent,
                               outputs=outputs, fuse=fuse, batcher=batcher,
                               mesh=mesh)

    if fn is not None:
        return wrap(fn)
    return wrap


def _abstractify(x: Any):
    from .tdg import abstract_leaf

    return jax.tree_util.tree_map(abstract_leaf, x)
