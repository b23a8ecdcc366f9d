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

A fused class's result stays stacked: the slot environment holds each
member's output as (that class's stacked output, row), and a row is sliced
out alone only for a reader that needs it alone (an unrolled task, a trace
fallback, the program's outputs). A later class builds each varying
argument in the cheapest of four ways:

* **whole** — its members' slots are exactly one stacked output, in order:
  the argument is that array, with no op;
* **slice** — they are a contiguous run of one: one ``lax.slice_in_dim``;
* **broadcast** — one slot for every member (``WaveClass.shared``):
  ``vmap`` gets ``in_axes=None``;
* **stacked** — anything else (region inputs, rows of several stacks):
  copied into a new stack, as every argument once was.

An argument that **repeats** across members (fewer distinct slots than
members) would copy each repeated slot once per reader. :func:`_split`
weighs cutting the class into sub-classes on such an argument, which is
then broadcast in each: it scores every cut, and the uncut class, by the
bytes the class would copy into stacks, plus the bytes the next readers
of its outputs would copy (a reader, whole or under its own best cut,
reads a view only where it lines up with one of the cut's stacks), plus
the cost model's cut break-even (``CostModel.split_call_bytes``) for each
call the cut adds; the cheapest wins, the uncut class on a tie.
In tiled Cholesky it cuts each step's gemm class by ``L[j,k]``: the
sub-class's ``A`` is the whole output of the same sub-class a step
earlier, its ``L[i,k]`` a slice of the trsm's output, and the trsm's
``A[i,k]`` the whole output of sub-class ``j=k``. A class with neither a
repeated argument nor a stacked producer is planned as before.

Under a replay mesh the mechanism is off: arguments are always stacked,
padded and constrained (``sharding.replay``) and no class is split. A
producer's stack carries its own pad lanes and lane-to-device layout, so
a view at another offset or length would move rows between devices
anyway, and sub-classes would pad far more lanes than one class.

Fusion is semantics-preserving and *best-effort*: heterogeneous waves
degrade to per-task unrolled calls, and any class whose batched trace
fails (a payload without a batching rule, say) falls back to the unrolled
form for that class only. Classification happens at trace time, where
argument shapes are known from the tracers, so one lowered function stays
shape-polymorphic exactly like the unrolled path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from . import costmodel as _costmodel
from . import schedule as _schedule
from . import spans as _spans
from .tdg import TDG, abstract_leaf as _as_spec
from ..sharding import replay as _shreplay

STACK_AXIS = 0

#: How a fused class builds one argument (see the module docstring).
ARG_KINDS = ("whole", "slice", "broadcast", "stacked")


# ------------------------------------------------------------------ analysis

def value_signature(v: Any) -> tuple:
    """Abstract (treedef, per-leaf shape/dtype) signature of one value."""
    leaves, treedef = jax.tree_util.tree_flatten(v)
    return (treedef,
            tuple((tuple(getattr(l, "shape", ())),
                   str(getattr(l, "dtype", type(l).__name__))) for l in leaves))


def _nbytes(tree: Any) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


@dataclasses.dataclass(frozen=True)
class WaveClass:
    """One isomorphism class inside one wave.

    ``batcher``/``reason``/``flops``/``bytes_accessed`` record how the
    class was (or would be) dispatched and the measured numbers behind the
    choice — "static" reason means a caller-pinned batcher, no cost model
    consulted. ``padded`` counts mesh-alignment pad lanes actually added
    at trace time (repeating the last member; computed, never read back).
    ``split`` is ``(argument position, index among the sub-classes)`` for a
    sub-class cut from a larger class on a repeated argument. ``args``
    gives, per argument position of a fused class, how it was built (one
    of :data:`ARG_KINDS`), and ``stacked_bytes`` the bytes copied into
    stacks for it (pad lanes included).
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
    split: tuple[int, int] | None = None  # (arg position, sub-class index)
    args: tuple[str, ...] = ()       # per arg position: one of ARG_KINDS
    stacked_bytes: int = 0           # bytes copied into argument stacks

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
            "split": None if self.split is None else list(self.split),
            "args": list(self.args),
            "stacked_bytes": self.stacked_bytes,
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

    @property
    def split_classes(self) -> int:
        """Classes cut into sub-classes on a repeated argument."""
        return sum(1 for c in self.classes if c.split and c.split[1] == 0)

    @property
    def split_off(self) -> int:
        """Sub-classes beyond the first of each cut class."""
        return sum(1 for c in self.classes if c.split and c.split[1] > 0)

    @property
    def arg_kinds(self) -> dict[str, int]:
        """Fused classes' arguments by how each was built."""
        kinds = dict.fromkeys(ARG_KINDS, 0)
        for c in self.classes:
            for k in c.args:
                kinds[k] += 1
        return kinds

    @property
    def stacked_bytes(self) -> int:
        """Bytes still copied into argument stacks per call of the program."""
        return sum(c.stacked_bytes for c in self.classes)

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
            "split_classes": self.split_classes,
            "split_off": self.split_off,
            "args": self.arg_kinds,
            "stacked_bytes": self.stacked_bytes,
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
    return [_make_class(tdg, wave_index, tids, min_class_size)
            for tids in groups.values()]


def _make_class(tdg: TDG, wave: int, tids: Sequence[int], min_class_size: int,
                split: tuple[int, int] | None = None) -> WaveClass:
    first = tdg.tasks[tids[0]].ins
    shared = tuple(all(tdg.tasks[t].ins[i] == first[i] for t in tids)
                   for i in range(len(first)))
    return WaveClass(wave=wave, tids=tuple(tids),
                     fused=len(tids) >= min_class_size, shared=shared,
                     split=split)


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


# ------------------------------------------------------------ slot layout

def _row_of(x: Any, row: int) -> Any:
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
    return jax.lax.index_in_dim(x, row, axis=STACK_AXIS, keepdims=False)


class _SlotEnv:
    """The slot environment of one fused trace.

    A slot holds a value alone (``values``), or is row ``r`` of stacked
    output ``stacks[sid]`` (``rows[slot] == (sid, r)``). Reading a row's
    slot alone slices it out once and keeps the slice beside the row.
    """

    def __init__(self, buffers: Mapping[str, Any], inputs: Sequence[str] = ()):
        self.values: dict[str, Any] = dict(buffers)
        self.rows: dict[str, tuple[int, int]] = {}
        self.stacks: list[tuple[Any, int]] = []   # (stacked tree, members)
        self.inputs = inputs

    def _value(self, slot: str) -> Any:
        try:
            return self.values[slot]
        except KeyError:
            raise KeyError(f"unbound slot {slot!r} (region inputs: "
                           f"{list(self.inputs)})") from None

    def __getitem__(self, slot: str) -> Any:
        if slot not in self.rows:
            return self._value(slot)
        if slot in self.values:
            return self.values[slot]
        sid, row = self.rows[slot]
        v = jax.tree_util.tree_map(lambda x: _row_of(x, row),
                                   self.stacks[sid][0])
        self.values[slot] = v
        return v

    def __setitem__(self, slot: str, value: Any) -> None:
        self.values[slot] = value
        self.rows.pop(slot, None)

    def place(self, slots: Sequence[str], stacked: Any) -> None:
        """Bind ``slots[r]`` to row ``r`` of ``stacked``."""
        sid = len(self.stacks)
        self.stacks.append((stacked, len(slots)))
        for r, s in enumerate(slots):
            self.rows[s] = (sid, r)
            self.values.pop(s, None)

    def spec(self, slot: str) -> Any:
        """One slot's abstract value, without slicing a row out."""
        if slot in self.values or slot not in self.rows:
            return jax.tree_util.tree_map(_as_spec, self._value(slot))
        return jax.tree_util.tree_map(lambda x: _row_of(_as_spec(x), 0),
                                      self.stacks[self.rows[slot][0]][0])

    def signature(self, slot: str) -> tuple:
        if slot in self.values or slot not in self.rows:
            return value_signature(self._value(slot))
        return value_signature(self.spec(slot))

    def view(self, slots: Sequence[str]) -> tuple[str, int, int] | None:
        """``("whole" | "slice", stack id, first row)`` when ``slots`` are
        consecutive rows of one stacked output, in order; else ``None``."""
        first = self.rows.get(slots[0])
        if first is None:
            return None
        sid, start = first
        for k, s in enumerate(slots):
            if self.rows.get(s) != (sid, start + k):
                return None
        whole = start == 0 and len(slots) == self.stacks[sid][1]
        return ("whole" if whole else "slice"), sid, start

    def batched(self, slots: Sequence[str]) -> Any:
        """A view's array: the whole stacked output or one slice of it."""
        _, sid, start = self.view(slots)
        stacked, n = self.stacks[sid]
        if start == 0 and len(slots) == n:
            return stacked
        stop = start + len(slots)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.slice_in_dim(x, start, stop, axis=STACK_AXIS),
            stacked)


def _arg_kinds(tdg: TDG, cls: WaveClass, env: _SlotEnv,
               views: bool) -> tuple[str, ...]:
    """How a fused class builds each argument (one of :data:`ARG_KINDS`)."""
    kinds = []
    for i, shared in enumerate(cls.shared):
        if shared:
            kinds.append("broadcast")
            continue
        v = env.view([tdg.tasks[t].ins[i] for t in cls.tids]) if views else None
        kinds.append("stacked" if v is None else v[0])
    return tuple(kinds)


# ------------------------------------------------------- cutting a class

class _Readers:
    """Who reads each task's outputs, for :func:`_split`'s look ahead.

    ``producer[(tid, i)]`` is the ``(task, output position)`` whose value
    task ``tid`` reads at argument ``i`` (record order, the versions a
    replay sees), ``readers[tid]`` the ``(task, position)`` pairs reading
    any of ``tid``'s outputs, and ``peers[tid]`` the tasks of ``tid``'s
    wave with its payload and arity, in tid order: the class it will
    (structurally) join.
    """

    def __init__(self, tdg: TDG, waves: Sequence[Sequence[int]]):
        self.producer: dict[tuple[int, int], tuple[int, int]] = {}
        self.readers: dict[int, list[tuple[int, int]]] = {}
        last: dict[str, tuple[int, int]] = {}
        for t in tdg.tasks:
            for i, s in enumerate(t.ins):
                w = last.get(s)
                if w is not None:
                    self.producer[(t.tid, i)] = w
                    self.readers.setdefault(w[0], []).append((t.tid, i))
            for o, s in enumerate(t.outs):
                last[s] = (t.tid, o)
        groups: dict[tuple, list[int]] = {}
        for wi, wave in enumerate(waves):
            for tid in sorted(wave):
                t = tdg.tasks[tid]
                groups.setdefault((wi, id(t.fn), len(t.ins), len(t.outs)),
                                  []).append(tid)
        self.peers = {tid: tuple(g) for g in groups.values() for tid in g}


def _cuts(tdg: TDG, tids: Sequence[int]
          ) -> list[tuple[int | None, list[list[int]]]]:
    """A class's candidate cuts: ``(None, [tids])`` for the class whole,
    then ``(i, groups)`` for each argument ``i`` whose slots repeat without
    being one slot for all, grouped by slot (groups in order of first
    member, members in tid order)."""
    tasks = [tdg.tasks[t] for t in tids]
    cuts: list[tuple[int | None, list[list[int]]]] = [(None, [list(tids)])]
    for i in range(len(tasks[0].ins)):
        groups: dict[str, list[int]] = {}
        for t in tasks:
            groups.setdefault(t.ins[i], []).append(t.tid)
        if 1 < len(groups) < len(tasks):
            cuts.append((i, list(groups.values())))
    return cuts


def _stack_bytes(tdg: TDG, tids: Sequence[int], env: _SlotEnv,
                 min_class_size: int) -> int:
    """Bytes a fused call over ``tids`` would copy into argument stacks."""
    if len(tids) < min_class_size:
        return 0
    total = 0
    for i in range(len(tdg.tasks[tids[0]].ins)):
        slots = [tdg.tasks[t].ins[i] for t in tids]
        if all(s == slots[0] for s in slots) or env.view(slots):
            continue
        total += sum(_nbytes(env.spec(s)) for s in slots)
    return total


def _handoff_bytes(tdg: TDG, groups: Sequence[Sequence[int]],
                   out_bytes: Sequence[int], readers: _Readers,
                   min_class_size: int) -> int:
    """Bytes the next readers of these groups' outputs would copy.

    A reader is the class of its peers, whole or under its own best cut
    (taken per argument, so the estimate leans low): a group of it reads a
    view only where its members read consecutive rows of one of these
    groups' stacked outputs, or one slot for all.
    """
    row: dict[tuple[int, int], tuple[int, int, int]] = {}
    ours: set[int] = set()
    for gi, g in enumerate(groups):
        ours.update(g)
        if len(g) >= min_class_size:
            for r, t in enumerate(g):
                for o in range(len(out_bytes)):
                    row[(t, o)] = (gi, o, r)

    def copied(reader_group: Sequence[int], i: int) -> int:
        if len(reader_group) < min_class_size:
            return 0
        prods = [readers.producer.get((p, i)) for p in reader_group]
        if all(w == prods[0] for w in prods):
            return 0                        # one slot for all: broadcast
        rows = [row.get(w) for w in prods]
        first = rows[0]
        if first is not None and all(r == (first[0], first[1], first[2] + k)
                                     for k, r in enumerate(rows)):
            return 0                        # a view of one group's output
        return sum(out_bytes[w[1]] for w in prods
                   if w is not None and w[0] in ours)

    seen: set[tuple[int, int]] = set()
    total = 0
    for t in sorted(ours):
        for rt, i in readers.readers.get(t, ()):
            peers = readers.peers[rt]
            if (peers[0], i) in seen:
                continue
            seen.add((peers[0], i))
            total += min(sum(copied(g, i) for g in cut)
                         for _, cut in _cuts(tdg, peers))
    return total


def _split(tdg: TDG, cls: WaveClass, env: _SlotEnv, readers: _Readers,
           min_class_size: int) -> list[WaveClass]:
    """Cut ``cls`` on a repeated argument where that copies fewer bytes.

    Candidates are the class whole and, for each varying argument with
    fewer distinct slots than members, the class grouped by that slot
    (sub-classes in order of first member, members in tid order). Each is
    scored as the module docstring says; the cheapest wins, ties going to
    the earlier candidate (the class whole first).
    """
    cuts = _cuts(tdg, cls.tids)
    if len(cuts) == 1:
        return [cls]
    first = tdg.tasks[cls.tids[0]]
    out = jax.eval_shape(first.fn, *[env.spec(s) for s in first.ins])
    out_bytes = [_nbytes(o) for o in
                 ([out] if len(first.outs) == 1 else out)]
    call_bytes = _costmodel.default_model().split_call_bytes

    def score(groups: list[list[int]]) -> int:
        return (sum(_stack_bytes(tdg, g, env, min_class_size) for g in groups)
                + _handoff_bytes(tdg, groups, out_bytes, readers,
                                 min_class_size)
                + (len(groups) - 1) * call_bytes)

    scores = [score(groups) for _, groups in cuts]
    best = min(range(len(cuts)), key=scores.__getitem__)
    pos, groups = cuts[best]
    if pos is None:
        return [cls]
    return [_make_class(tdg, cls.wave, g, min_class_size, split=(pos, k))
            for k, g in enumerate(groups)]


def _plan_wave(tdg: TDG, wi: int, wave: Sequence[int], env: _SlotEnv,
               readers: _Readers | None, min_class_size: int,
               batcher: str) -> list[WaveClass]:
    """One wave's classes, cut (``readers`` given) and decided, in order."""
    classes = []
    for cls in classify_wave(tdg, wi, wave, env.signature, min_class_size):
        subs = ([cls] if readers is None or not cls.fused
                else _split(tdg, cls, env, readers, min_class_size))
        classes.extend(_decide_class(tdg, c, batcher, env.spec) for c in subs)
    return classes


def plan(tdg: TDG, buffers: Mapping[str, Any] | None = None,
         min_class_size: int = 2, batcher: str = "vmap") -> FusionPlan:
    """Offline wave analysis (for stats, tests and benchmark reporting).

    With ``buffers`` (arrays or ``ShapeDtypeStruct`` trees for the region's
    input slots), the single-device fused function is traced abstractly
    (``jax.eval_shape``, which adds to the ``taskgraph.fuse.*`` counters
    like any trace) and the plan it applied is returned: classes, cuts,
    argument kinds and fallbacks are the trace's own. Without them,
    grouping is structural (payload identity + arity) and uncut — an
    upper bound on fusion opportunity. ``batcher="auto"`` additionally runs
    the cost model over each class (requires ``buffers`` for measured
    numbers; without them every class is "unmeasured" -> vmap fallback).
    """
    batcher = _costmodel.resolve_batcher(batcher)
    if buffers is not None:
        f = fused_tdg_as_function(tdg, outputs=(),
                                  min_class_size=min_class_size,
                                  batcher=batcher)
        jax.eval_shape(f, {k: jax.tree_util.tree_map(_as_spec, v)
                           for k, v in buffers.items()})
        return f.last_plan
    classes: list[WaveClass] = []
    for wi, wave in enumerate(_schedule.topo_waves(tdg)):
        classes.extend(
            _decide_class(tdg, c, batcher, None)
            for c in classify_wave(tdg, wi, wave, None, min_class_size))
    return FusionPlan(region=tdg.region, num_tasks=tdg.num_tasks,
                      classes=classes, min_class_size=min_class_size)


# ----------------------------------------------------------------- execution

def _bind_outs(task, out, env) -> None:
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


def _place_outs(tasks, out, env: _SlotEnv) -> None:
    """Keep a fused class's stacked result: member r's outputs are row r."""
    n_outs = len(tasks[0].outs)
    if n_outs == 1:
        env.place([t.outs[0] for t in tasks], out)
    elif n_outs > 1:
        if not isinstance(out, (tuple, list)) or len(out) != n_outs:
            raise ValueError(
                f"task {tasks[0].label()} declared {n_outs} outputs, "
                f"returned {type(out).__name__}")
        for oi in range(n_outs):
            env.place([t.outs[oi] for t in tasks], out[oi])


def _run_unrolled(tdg: TDG, tids: Sequence[int], env,
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


def _run_fused_class(tdg: TDG, cls: WaveClass, env: _SlotEnv, batcher: str,
                     mesh=None) -> WaveClass:
    """Execute one isomorphism class as a single batched call.

    Each varying argument is a view of a stacked output where it lines up
    with one, else a stacked copy (see the module docstring); the result
    stays stacked in ``env``. With a ``mesh``, the vmap-batched form pads
    the class to a multiple of the mesh's batch-axis size (repeating the
    last member — padded lanes are computed and dropped, never read) and
    constrains the stacked arguments over the mesh so GSPMD splits the
    batch across devices; every argument is then a stacked copy.
    ``batcher="map"`` is deliberately single-device: ``lax.map`` is a
    sequential scan, so sharding its carried axis buys nothing. Returns
    ``cls`` with the pad lanes actually added (surfaced through
    ``FusionPlan.summary()`` as ``padded_lanes``/``pad_fraction``), the
    argument kinds and the bytes stacked.
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
        return dataclasses.replace(cls, args=("broadcast",) * arity)

    kinds = _arg_kinds(tdg, cls, env, views=mesh is None)
    if batcher != "vmap":
        mesh = None
    shared_args = {i: env[tasks[0].ins[i]] for i in range(arity)
                   if cls.shared[i]}
    padded = stacked_bytes = 0
    batched = {}
    for i in varying:
        slots = [t.ins[i] for t in tasks]
        if kinds[i] != "stacked":
            batched[i] = env.batched(slots)
            continue
        members = [env[s] for s in slots]
        padded = _shreplay.pad_group(members, mesh)
        stacked_bytes += sum(_nbytes(m) for m in members)
        batched[i] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, STACK_AXIS), *members)
        if mesh is not None:
            batched[i] = _shreplay.shard_leading(batched[i], mesh)

    if batcher == "vmap":
        in_axes = tuple(None if cls.shared[i] else STACK_AXIS
                        for i in range(arity))
        args = [shared_args[i] if cls.shared[i] else batched[i]
                for i in range(arity)]
        out = jax.vmap(fn, in_axes=in_axes)(*args)
    elif batcher == "map":
        def body(var_args):
            it = iter(var_args)
            return fn(*[shared_args[i] if cls.shared[i] else next(it)
                        for i in range(arity)])
        out = jax.lax.map(body, tuple(batched[i] for i in varying))
    else:
        raise ValueError(f"unknown batcher {batcher!r} (vmap | map)")

    _place_outs(tasks, out, env)
    return dataclasses.replace(cls, padded=padded, args=kinds,
                               stacked_bytes=stacked_bytes)


def _count(cls: WaveClass) -> None:
    """Trace-time counters of what one class's plan did (``core.spans``)."""
    if cls.split and cls.split[1] > 0:
        _spans.count("taskgraph.fuse.split_off")
    for k in cls.args:
        _spans.count(f"taskgraph.fuse.arg_{k}")
    if cls.stacked_bytes:
        _spans.count("taskgraph.fuse.stacked_bytes", cls.stacked_bytes)


def fused_tdg_as_function(tdg: TDG, outputs: Sequence[str] | None = None,
                          min_class_size: int = 2,
                          batcher: str = "vmap",
                          mesh=None) -> Callable[[dict], dict]:
    """Return ``f(buffers) -> {slot: value}`` with wave-fused task dispatch.

    Drop-in replacement for ``lower.tdg_as_function`` (pure, traceable,
    jittable, differentiable); tasks execute in wave order, which refines
    the same partial order as any topological order. After each call (or
    trace), ``f.last_plan`` holds the :class:`FusionPlan` actually applied,
    including cuts and trace-time fallbacks. Each trace adds the plan's
    cuts and argument kinds to the ``core.spans`` counters
    ``taskgraph.fuse.split_off``, ``taskgraph.fuse.arg_<kind>`` and
    ``taskgraph.fuse.stacked_bytes``.

    ``batcher`` is ``"vmap"`` / ``"map"`` (one pinned dispatch for every
    fused class, no cost model) or ``"auto"`` — per-class cost-model
    selection from probe-measured flops/bytes (``core.costmodel``). The
    ``REPRO_ADAPTIVE=0`` kill switch is resolved *here* so a function built
    before the flag flip still honours it at trace time.

    ``mesh`` (a concrete :class:`jax.sharding.Mesh` or ``None``; resolution
    of ``"auto"`` happens in ``lower.lower_tdg``) shards every fused
    class's stacked batch axis across devices — see
    :func:`_run_fused_class`; no class is cut under a mesh (module
    docstring). Classes that fall back to the unrolled form stay
    single-device, which is the per-class fallback for unbatchable
    payloads.
    """
    waves = _schedule.topo_waves(tdg)
    outputs = list(outputs) if outputs is not None else list(tdg.output_slots)
    readers = _Readers(tdg, waves) if mesh is None else None

    def run(buffers: Mapping[str, Any]) -> dict:
        env = _SlotEnv(buffers, tdg.input_slots)
        resolved = _costmodel.resolve_batcher(batcher)
        applied: list[WaveClass] = []
        for wi, wave in enumerate(waves):
            for cls in _plan_wave(tdg, wi, wave, env, readers,
                                  min_class_size, resolved):
                if not cls.fused:
                    _run_unrolled(tdg, cls.tids, env, wave=wi)
                    _count(cls)
                    applied.append(cls)
                    continue
                try:
                    # One named scope over the stacking, the batched call
                    # and the slicing: the program's op metadata carries it.
                    with jax.named_scope(_class_scope(tdg, cls, cls.batcher)):
                        cls = _run_fused_class(tdg, cls, env, cls.batcher,
                                               mesh=mesh)
                except Exception:
                    # Payload not batchable (no vmap rule, data-dependent
                    # control flow, ...): this class only degrades to the
                    # unrolled form. A payload broken under tracing per se
                    # re-raises from here with its real error.
                    _run_unrolled(tdg, cls.tids, env, wave=wi)
                    cls = dataclasses.replace(
                        cls, fused=False, batcher="unrolled",
                        reason="trace fallback: payload not batchable")
                _count(cls)
                applied.append(cls)
        run.last_plan = FusionPlan(region=tdg.region, num_tasks=tdg.num_tasks,
                                   classes=applied,
                                   min_class_size=min_class_size)
        return {s: env[s] for s in outputs}

    run.last_plan = None
    run.__name__ = f"tdg_fused_{tdg.region}"
    return run
