"""Wave-fused lowering: worksharing-style batching of isomorphic tasks.

The unrolled replay path (``lower.tdg_as_function``) emits every task body
into the traced program one call at a time, so trace+compile cost — and
jaxpr size — scale with *task count* even when the graph is just a few
waves of isomorphic work (a 16x64 pipeline TDG traces 2048 bodies for ~80
distinct waves). That re-introduces, at the tracing layer, exactly the
per-task fixed cost the paper eliminates at the orchestration layer.

Following Worksharing Tasks (Maroñas et al., 2020), this module batches
fine-grained tasks back into coarse dispatches:

* :func:`classify_wave` groups one topo-wave's tasks into **isomorphism
  classes** — same payload function (by identity), same input arity/shapes/
  dtypes, same output arity. Tasks in one wave are mutually independent by
  construction, so any class can execute as a single batched call.
* :func:`fused_tdg_as_function` lowers each class of size >=
  ``min_class_size`` as ONE ``jax.vmap``-batched call (or a sequential
  ``lax.map`` for memory-bound cases, ``batcher="map"``) over arguments
  stacked along axis 0, with argument positions whose slot is shared by
  every member broadcast instead of stacked. The traced program shrinks
  from O(tasks) body instances to O(wave-classes).

Fusion is semantics-preserving and *best-effort*: heterogeneous waves
degrade to per-task unrolled calls, and any class whose batched trace
fails (a payload without a batching rule, say) falls back to the unrolled
form for that class only. Classification happens at trace time, where
argument shapes are known from the tracers, so one lowered function stays
shape-polymorphic exactly like the unrolled path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from . import costmodel as _costmodel
from . import schedule as _schedule
from .tdg import TDG, abstract_leaf as _as_spec
from ..sharding import replay as _shreplay

STACK_AXIS = 0


# ------------------------------------------------------------------ analysis

def value_signature(v: Any) -> tuple:
    """Abstract (treedef, per-leaf shape/dtype) signature of one value."""
    leaves, treedef = jax.tree_util.tree_flatten(v)
    return (treedef,
            tuple((tuple(getattr(l, "shape", ())),
                   str(getattr(l, "dtype", type(l).__name__))) for l in leaves))


@dataclasses.dataclass(frozen=True)
class WaveClass:
    """One isomorphism class inside one wave.

    ``batcher``/``reason``/``flops``/``bytes_accessed`` record how the
    class was (or would be) dispatched and the measured numbers behind the
    choice — "static" reason means a caller-pinned batcher, no cost model
    consulted. ``padded`` counts mesh-alignment pad lanes actually added
    at trace time (repeating the last member; computed, never read back).
    """

    wave: int
    tids: tuple[int, ...]
    fused: bool                      # lowered as one batched call?
    shared: tuple[bool, ...]         # arg position uses one slot for all tids
    batcher: str = "vmap"            # "vmap" | "map" | "unrolled"
    reason: str = "static"           # what drove the batcher choice
    flops: float | None = None       # measured per-member flops (if probed)
    bytes_accessed: float | None = None  # measured per-member bytes accessed
    padded: int = 0                  # pad lanes added for mesh alignment

    @property
    def size(self) -> int:
        return len(self.tids)

    def decision(self) -> dict:
        """JSON-safe audit record (plan summaries / the cost report)."""
        inten = (self.flops / self.bytes_accessed
                 if self.flops is not None and self.bytes_accessed else None)
        return {
            "wave": self.wave,
            "size": self.size,
            "fused": self.fused,
            "batcher": self.batcher,
            "flops": self.flops,
            "bytes": self.bytes_accessed,
            "intensity": None if inten is None else round(inten, 4),
            "padded": self.padded,
            "reason": self.reason,
        }


@dataclasses.dataclass
class FusionPlan:
    """Result of the wave analysis pass over a whole TDG."""

    region: str
    num_tasks: int
    classes: list[WaveClass]
    min_class_size: int

    @property
    def num_waves(self) -> int:
        return 1 + max((c.wave for c in self.classes), default=-1)

    @property
    def num_classes(self) -> int:
        return len(self.classes)

    @property
    def fused_classes(self) -> int:
        return sum(1 for c in self.classes if c.fused)

    @property
    def fused_tasks(self) -> int:
        return sum(c.size for c in self.classes if c.fused)

    @property
    def fused_fraction(self) -> float:
        return self.fused_tasks / max(self.num_tasks, 1)

    @property
    def padded_lanes(self) -> int:
        return sum(c.padded for c in self.classes)

    @property
    def pad_fraction(self) -> float:
        """Pad lanes over total batched lanes (real + pad) — idle-work rate."""
        lanes = sum(c.size + c.padded for c in self.classes if c.fused)
        return self.padded_lanes / lanes if lanes else 0.0

    def summary(self) -> dict:
        batchers: dict[str, int] = {}
        for c in self.classes:
            if c.fused:
                batchers[c.batcher] = batchers.get(c.batcher, 0) + 1
        return {
            "region": self.region,
            "tasks": self.num_tasks,
            "waves": self.num_waves,
            "classes": self.num_classes,
            "fused_classes": self.fused_classes,
            "fused_tasks": self.fused_tasks,
            "fused_fraction": round(self.fused_fraction, 4),
            "batchers": batchers,
            "padded_lanes": self.padded_lanes,
            "pad_fraction": round(self.pad_fraction, 4),
            "decisions": [c.decision() for c in self.classes],
        }


def classify_wave(tdg: TDG, wave_index: int, wave: Sequence[int],
                  sig_of: Callable[[str], Any] | None,
                  min_class_size: int = 2) -> list[WaveClass]:
    """Group one wave's tasks into isomorphism classes.

    ``sig_of`` maps a slot name to an abstract value signature (or ``None``
    for purely structural grouping by payload identity + arity, used when no
    shape information is available yet). Classes are returned in order of
    first member, members in tid order — deterministic for a given TDG.
    """
    groups: dict[tuple, list[int]] = {}
    for tid in sorted(wave):
        t = tdg.tasks[tid]
        key: tuple = (id(t.fn), len(t.ins), len(t.outs))
        if sig_of is not None:
            key += tuple(sig_of(s) for s in t.ins)
        groups.setdefault(key, []).append(tid)
    classes = []
    for tids in groups.values():
        arity = len(tdg.tasks[tids[0]].ins)
        shared = tuple(
            all(tdg.tasks[t].ins[i] == tdg.tasks[tids[0]].ins[i] for t in tids)
            for i in range(arity))
        classes.append(WaveClass(wave=wave_index, tids=tuple(tids),
                                 fused=len(tids) >= min_class_size,
                                 shared=shared))
    return classes


def _decide_class(tdg: TDG, cls: WaveClass, batcher: str,
                  spec_of: Callable[[str], Any] | None) -> WaveClass:
    """Attach a batcher decision (and the numbers behind it) to one class.

    ``batcher="auto"`` consults the process cost model: the class payload
    is probe-compiled for ONE member's argument specs and the measured
    flops/bytes pick vmap vs ``lax.map`` vs unrolled (see ``costmodel``).
    A static batcher passes through untouched — no probe, reason "static".
    """
    if not cls.fused:
        return dataclasses.replace(
            cls, batcher="unrolled",
            reason=f"class size {cls.size} below min_class_size")
    if batcher != "auto":
        return dataclasses.replace(cls, batcher=batcher, reason="static")
    model = _costmodel.default_model()
    t = tdg.tasks[cls.tids[0]]
    arg_specs = None
    if spec_of is not None:
        try:
            arg_specs = [spec_of(s) for s in t.ins]
        except Exception:
            arg_specs = None
    if arg_specs is None:
        d = model.decide(_costmodel.UNMEASURED, cls.size)
    else:
        d = model.decide_for(t.fn, arg_specs, cls.size)
    return dataclasses.replace(
        cls, batcher=d.batcher, fused=d.batcher != "unrolled",
        reason=d.reason, flops=d.cost.flops,
        bytes_accessed=d.cost.bytes_accessed)


def plan(tdg: TDG, buffers: Mapping[str, Any] | None = None,
         min_class_size: int = 2, batcher: str = "vmap") -> FusionPlan:
    """Offline wave analysis (for stats, tests and benchmark reporting).

    With ``buffers`` (arrays or ``ShapeDtypeStruct`` trees for the region's
    input slots), slot shapes are propagated through the graph by abstract
    evaluation so classes match exactly what trace-time fusion will do;
    without them, grouping is structural (payload identity + arity) — an
    upper bound on fusion opportunity. ``batcher="auto"`` additionally runs
    the cost model over each class (requires ``buffers`` for measured
    numbers; without them every class is "unmeasured" -> vmap fallback).
    """
    batcher = _costmodel.resolve_batcher(batcher)
    sig_of = spec_of = None
    if buffers is not None:
        env: dict[str, Any] = {
            k: jax.tree_util.tree_map(_as_spec, v) for k, v in buffers.items()}
        for tid in _schedule.topo_order(tdg):
            t = tdg.tasks[tid]
            out = jax.eval_shape(t.fn, *[env[s] for s in t.ins])
            _bind_outs(t, out, env)
        sig_of = lambda s: value_signature(env[s])  # noqa: E731
        spec_of = lambda s: env[s]  # noqa: E731 (already abstract specs)
    classes: list[WaveClass] = []
    for wi, wave in enumerate(_schedule.topo_waves(tdg)):
        classes.extend(
            _decide_class(tdg, c, batcher, spec_of)
            for c in classify_wave(tdg, wi, wave, sig_of, min_class_size))
    return FusionPlan(region=tdg.region, num_tasks=tdg.num_tasks,
                      classes=classes, min_class_size=min_class_size)


# ----------------------------------------------------------------- execution

def _bind_outs(task, out, env: dict) -> None:
    """Write one task's return value into the env (same rules as lower)."""
    if len(task.outs) == 1:
        env[task.outs[0]] = out
    elif len(task.outs) > 1:
        if not isinstance(out, (tuple, list)) or len(out) != len(task.outs):
            raise ValueError(
                f"task {task.label()} declared {len(task.outs)} outputs, "
                f"returned {type(out).__name__}")
        for s, v in zip(task.outs, out):
            env[s] = v


def _run_unrolled(tdg: TDG, tids: Sequence[int], env: dict,
                  wave: int | None = None) -> None:
    """Run tasks one by one, each under a named scope of its label (prefixed
    ``w<wave>.`` inside a fused program), so the program's op metadata
    names the task."""
    prefix = "" if wave is None else f"w{wave}."
    for tid in tids:
        t = tdg.tasks[tid]
        try:
            args = [env[s] for s in t.ins]
        except KeyError as e:  # pragma: no cover - defensive
            raise KeyError(f"task {t.label()} reads unbound slot {e} "
                           f"(region inputs: {tdg.input_slots})") from None
        with jax.named_scope(prefix + t.label()):
            _bind_outs(t, t.fn(*args), env)


def _class_scope(tdg: TDG, cls: WaveClass, batcher: str) -> str:
    """Named scope of one fused class: ``w<wave>.<payload>.<batcher>``."""
    fn = tdg.tasks[cls.tids[0]].fn
    return f"w{cls.wave}.{getattr(fn, '__name__', 'task')}.{batcher}"


def _run_fused_class(tdg: TDG, cls: WaveClass, env: dict, batcher: str,
                     mesh=None) -> int:
    """Execute one isomorphism class as a single batched call; return #pads.

    With a ``mesh``, the vmap-batched form pads the class to a multiple of
    the mesh's batch-axis size (repeating the last member — padded lanes
    are computed and dropped, never read) and constrains the stacked
    arguments over the mesh so GSPMD splits the batch across devices.
    ``batcher="map"`` is deliberately single-device: ``lax.map`` is a
    sequential scan, so sharding its carried axis buys nothing. The return
    value is the pad-lane count actually added (0 without a mesh), surfaced
    through ``FusionPlan.summary()`` as ``padded_lanes``/``pad_fraction``.
    """
    tasks = [tdg.tasks[t] for t in cls.tids]
    fn = tasks[0].fn
    arity = len(tasks[0].ins)
    varying = [i for i in range(arity) if not cls.shared[i]]

    if not varying:
        # Every member reads identical slots: one evaluation serves all
        # (distinct out slots are guaranteed — a WAW pair cannot share a wave).
        out = fn(*[env[tasks[0].ins[i]] for i in range(arity)])
        for t in tasks:
            _bind_outs(t, out, env)
        return 0

    if batcher != "vmap":
        mesh = None
    shared_args = {i: env[tasks[0].ins[i]] for i in range(arity)
                   if cls.shared[i]}
    members = {i: [env[t.ins[i]] for t in tasks] for i in varying}
    padded = 0
    for i in varying:
        padded = _shreplay.pad_group(members[i], mesh)
    stacked = {
        i: jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, STACK_AXIS), *members[i])
        for i in varying}
    if mesh is not None:
        stacked = {i: _shreplay.shard_leading(v, mesh)
                   for i, v in stacked.items()}

    if batcher == "vmap":
        in_axes = tuple(None if cls.shared[i] else STACK_AXIS
                        for i in range(arity))
        args = [shared_args[i] if cls.shared[i] else stacked[i]
                for i in range(arity)]
        out = jax.vmap(fn, in_axes=in_axes)(*args)
    elif batcher == "map":
        def body(var_args):
            it = iter(var_args)
            return fn(*[shared_args[i] if cls.shared[i] else next(it)
                        for i in range(arity)])
        out = jax.lax.map(body, tuple(stacked[i] for i in varying))
    else:
        raise ValueError(f"unknown batcher {batcher!r} (vmap | map)")

    n_outs = len(tasks[0].outs)
    for j, t in enumerate(tasks):
        take = lambda x: jax.lax.index_in_dim(  # noqa: E731
            x, j, axis=STACK_AXIS, keepdims=False)
        if n_outs == 1:
            env[t.outs[0]] = jax.tree_util.tree_map(take, out)
        else:
            if not isinstance(out, (tuple, list)) or len(out) != n_outs:
                raise ValueError(
                    f"task {t.label()} declared {n_outs} outputs, "
                    f"returned {type(out).__name__}")
            for oi, s in enumerate(t.outs):
                env[s] = jax.tree_util.tree_map(take, out[oi])
    return padded


def fused_tdg_as_function(tdg: TDG, outputs: Sequence[str] | None = None,
                          min_class_size: int = 2,
                          batcher: str = "vmap",
                          mesh=None) -> Callable[[dict], dict]:
    """Return ``f(buffers) -> {slot: value}`` with wave-fused task dispatch.

    Drop-in replacement for ``lower.tdg_as_function`` (pure, traceable,
    jittable, differentiable); tasks execute in wave order, which refines
    the same partial order as any topological order. After each call (or
    trace), ``f.last_plan`` holds the :class:`FusionPlan` actually applied,
    including trace-time fallbacks.

    ``batcher`` is ``"vmap"`` / ``"map"`` (one pinned dispatch for every
    fused class, no cost model) or ``"auto"`` — per-class cost-model
    selection from probe-measured flops/bytes (``core.costmodel``). The
    ``REPRO_ADAPTIVE=0`` kill switch is resolved *here* so a function built
    before the flag flip still honours it at trace time.

    ``mesh`` (a concrete :class:`jax.sharding.Mesh` or ``None``; resolution
    of ``"auto"`` happens in ``lower.lower_tdg``) shards every fused
    class's stacked batch axis across devices — see
    :func:`_run_fused_class`. Classes that fall back to the unrolled form
    stay single-device, which is the per-class fallback for unbatchable
    payloads.
    """
    waves = _schedule.topo_waves(tdg)
    outputs = list(outputs) if outputs is not None else list(tdg.output_slots)

    def run(buffers: Mapping[str, Any]) -> dict:
        env = dict(buffers)
        resolved = _costmodel.resolve_batcher(batcher)
        applied: list[WaveClass] = []
        for wi, wave in enumerate(waves):
            def sig_of(s):
                try:
                    return value_signature(env[s])
                except KeyError:
                    raise KeyError(
                        f"unbound slot {s!r} (region inputs: "
                        f"{tdg.input_slots})") from None
            def spec_of(s):
                return jax.tree_util.tree_map(_as_spec, env[s])
            for cls in classify_wave(tdg, wi, wave, sig_of, min_class_size):
                cls = _decide_class(tdg, cls, resolved, spec_of)
                if not cls.fused:
                    _run_unrolled(tdg, cls.tids, env, wave=wi)
                    applied.append(cls)
                    continue
                try:
                    # One named scope over the stacking, the batched call
                    # and the slicing: the program's op metadata carries it.
                    with jax.named_scope(_class_scope(tdg, cls, cls.batcher)):
                        padded = _run_fused_class(tdg, cls, env, cls.batcher,
                                                  mesh=mesh)
                    applied.append(dataclasses.replace(cls, padded=padded))
                except Exception:
                    # Payload not batchable (no vmap rule, data-dependent
                    # control flow, ...): this class only degrades to the
                    # unrolled form. A payload broken under tracing per se
                    # re-raises from here with its real error.
                    _run_unrolled(tdg, cls.tids, env, wave=wi)
                    applied.append(dataclasses.replace(
                        cls, fused=False, batcher="unrolled",
                        reason="trace fallback: payload not batchable"))
        run.last_plan = FusionPlan(region=tdg.region, num_tasks=tdg.num_tasks,
                                   classes=applied,
                                   min_class_size=min_class_size)
        return {s: env[s] for s in outputs}

    run.last_plan = None
    run.__name__ = f"tdg_fused_{tdg.region}"
    return run
