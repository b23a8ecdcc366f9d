"""Task Dependency Graph (TDG) — the paper's core data structure.

A TDG is a DAG whose nodes are *task instances* (pure JAX callables bound to
named buffer slots) and whose edges are data dependencies among them,
materialized once (at record/static-build time) from OpenMP-style
``depend(in/out/inout)`` clauses via a last-writer/readers table — the
JAX analogue of the runtime dependency-tracking hash table that vanilla
OpenMP consults on *every* task creation (and that this framework consults
exactly once per region).

Edges are RAW (read-after-write), WAR (write-after-read) and WAW
(write-after-write), matching OpenMP 5.x depend-clause semantics.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Iterable, Mapping, Sequence


class DepKind(enum.Enum):
    IN = "in"
    OUT = "out"
    INOUT = "inout"


class EdgeKind(enum.Enum):
    RAW = "raw"  # true (flow) dependence
    WAR = "war"  # anti dependence
    WAW = "waw"  # output dependence


@dataclasses.dataclass(frozen=True)
class Edge:
    src: int
    dst: int
    kind: EdgeKind
    slot: str


@dataclasses.dataclass
class Task:
    """One task instance.

    ``fn`` is a pure function taking the values of ``ins`` (in order) and
    returning the values of ``outs`` (a single value if ``len(outs) == 1``,
    else a tuple in order). Constants ("known data", paper Fig. 4d) are
    simply closed over in ``fn``.
    """

    tid: int
    fn: Callable[..., Any]
    ins: tuple[str, ...]
    outs: tuple[str, ...]
    name: str = ""
    cost_hint: float = 1.0
    metadata: dict = dataclasses.field(default_factory=dict)

    def label(self) -> str:
        return self.name or getattr(self.fn, "__name__", f"task{self.tid}")


class DependencyTable:
    """Last-writer/readers table — the record-time 'dependency hash table'.

    The vanilla runtime pays an exclusive-access lookup here per depend
    clause on every execution; the Taskgraph framework pays it once, while
    recording, and never again (paper §4.3.2: entries are never freed so
    edges to already-finished tasks can still be established).
    """

    def __init__(self) -> None:
        self._last_writer: dict[str, int] = {}
        self._readers: dict[str, list[int]] = {}
        self.lookups = 0  # instrumentation: how many clause resolutions

    def resolve(self, tid: int, ins: Sequence[str], outs: Sequence[str]) -> list[Edge]:
        edges: list[Edge] = []
        seen: set[tuple[int, int]] = set()

        def _add(src: int, kind: EdgeKind, slot: str) -> None:
            if src == tid:
                return
            key = (src, tid)
            if key in seen:
                return
            seen.add(key)
            edges.append(Edge(src, tid, kind, slot))

        for slot in ins:
            self.lookups += 1
            w = self._last_writer.get(slot)
            if w is not None:
                _add(w, EdgeKind.RAW, slot)
            self._readers.setdefault(slot, []).append(tid)
        for slot in outs:
            self.lookups += 1
            w = self._last_writer.get(slot)
            if w is not None:
                _add(w, EdgeKind.WAW, slot)
            for r in self._readers.get(slot, ()):  # anti deps
                _add(r, EdgeKind.WAR, slot)
            self._last_writer[slot] = tid
            self._readers[slot] = []
        return edges


class TDG:
    """The task dependency graph for one region instance."""

    def __init__(self, region: str = "<anonymous>") -> None:
        self.region = region
        self.tasks: list[Task] = []
        self.edges: list[Edge] = []
        self.preds: dict[int, set[int]] = {}
        self.succs: dict[int, set[int]] = {}
        self._dep_table = DependencyTable()
        # slots read before ever written inside the region = region inputs;
        # slots written = region outputs (its externally visible effect).
        self._written: set[str] = set()
        self.input_slots: list[str] = []
        self.output_slots: list[str] = []

    # -- construction -----------------------------------------------------
    def add_task(
        self,
        fn: Callable[..., Any],
        ins: Sequence[str] = (),
        outs: Sequence[str] = (),
        inouts: Sequence[str] = (),
        name: str = "",
        cost_hint: float = 1.0,
        **metadata: Any,
    ) -> Task:
        ins = tuple(ins) + tuple(inouts)
        outs = tuple(outs) + tuple(inouts)
        tid = len(self.tasks)
        task = Task(tid, fn, tuple(ins), tuple(outs), name=name,
                    cost_hint=cost_hint, metadata=dict(metadata))
        self.tasks.append(task)
        self.preds[tid] = set()
        self.succs[tid] = set()
        for slot in ins:
            if slot not in self._written and slot not in self.input_slots:
                self.input_slots.append(slot)
        for slot in outs:
            self._written.add(slot)
            if slot not in self.output_slots:
                self.output_slots.append(slot)
        for e in self._dep_table.resolve(tid, task.ins, task.outs):
            self.edges.append(e)
            self.preds[tid].add(e.src)
            self.succs[e.src].add(tid)
        return task

    # -- queries -----------------------------------------------------------
    @property
    def num_tasks(self) -> int:
        return len(self.tasks)

    @property
    def num_edges(self) -> int:
        return len(self.edges)

    def roots(self) -> list[int]:
        """Tasks without input dependencies (paper §4.3.1)."""
        return [t.tid for t in self.tasks if not self.preds[t.tid]]

    def leaves(self) -> list[int]:
        return [t.tid for t in self.tasks if not self.succs[t.tid]]

    def is_acyclic(self) -> bool:
        # By construction every edge goes from a lower tid to a higher tid
        # (record order), so the graph is acyclic; verify anyway.
        return all(e.src < e.dst for e in self.edges)

    def validate(self) -> None:
        if not self.is_acyclic():
            raise ValueError(f"TDG {self.region!r} has a cycle")
        for e in self.edges:
            if not (0 <= e.src < self.num_tasks and 0 <= e.dst < self.num_tasks):
                raise ValueError(f"dangling edge {e}")

    def dep_lookups(self) -> int:
        return self._dep_table.lookups

    # -- pretty -------------------------------------------------------------
    def summary(self) -> str:
        kinds: dict[EdgeKind, int] = {}
        for e in self.edges:
            kinds[e.kind] = kinds.get(e.kind, 0) + 1
        kind_s = ", ".join(f"{k.value}={v}" for k, v in sorted(kinds.items(), key=lambda kv: kv[0].value))
        return (f"TDG({self.region!r}: {self.num_tasks} tasks, {self.num_edges} edges"
                f"{' [' + kind_s + ']' if kind_s else ''}, {len(self.roots())} roots)")

    def to_dot(self) -> str:
        lines = [f'digraph "{self.region}" {{']
        for t in self.tasks:
            lines.append(f'  t{t.tid} [label="{t.label()}"];')
        for e in self.edges:
            style = {"raw": "solid", "war": "dashed", "waw": "dotted"}[e.kind.value]
            lines.append(f'  t{e.src} -> t{e.dst} [style={style}, label="{e.slot}"];')
        lines.append("}")
        return "\n".join(lines)

    def __repr__(self) -> str:  # pragma: no cover
        return self.summary()


def chain_series(tdg: TDG, fns: Iterable[Callable], slot: str = "x") -> None:
    """Helper: a linear chain of tasks over one slot (paper Listing 1 column)."""
    for i, fn in enumerate(fns):
        tdg.add_task(fn, inouts=[slot], name=f"{slot}.{i}")


def abstract_leaf(v: Any):
    """One value leaf -> ``jax.ShapeDtypeStruct`` (no data touched).

    The single source of truth for value abstraction, shared by
    ``record._abstractify``, ``fuse`` and the AOT path in ``lower``.
    """
    import jax

    if isinstance(v, jax.ShapeDtypeStruct):
        return v
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return jax.ShapeDtypeStruct(v.shape, v.dtype)
    import numpy as np

    arr = np.asarray(v)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def structure_signature(tdg: TDG, outputs: Sequence[str] | None = None
                        ) -> tuple[tuple, dict[str, str], tuple]:
    """Canonical structural signature of a TDG, for executable interning.

    Two TDGs with the same signature AND the same payload functions compute
    the same program modulo slot *names*: slots are renamed ``s0, s1, ...``
    by first appearance (scanning tasks in tid order, ins before outs) and
    payloads are numbered by first appearance, so structurally identical
    regions built at different source locations — or two instances of one
    region — canonicalize to one key.

    Returns ``(sig, slot_map, payloads)`` where ``sig`` is a hashable
    structure key (tasks, edges, canonical output order), ``slot_map`` maps
    actual slot name -> canonical name, and ``payloads`` is the tuple of
    distinct payload functions in first-appearance order. ``sig`` carries
    payload *indices* only; an interning cache must additionally key on the
    identities in ``payloads`` (and keep them alive) because two graphs of
    identical shape over different payloads are different programs.
    """
    slot_map: dict[str, str] = {}
    payload_index: dict[int, int] = {}
    payloads: list[Callable] = []

    def canon(slot: str) -> str:
        if slot not in slot_map:
            slot_map[slot] = f"s{len(slot_map)}"
        return slot_map[slot]

    task_rows = []
    for t in tdg.tasks:
        fid = id(t.fn)
        if fid not in payload_index:
            payload_index[fid] = len(payloads)
            payloads.append(t.fn)
        task_rows.append((payload_index[fid],
                          tuple(canon(s) for s in t.ins),
                          tuple(canon(s) for s in t.outs)))
    edge_rows = tuple(sorted(
        (e.src, e.dst, e.kind.value, slot_map[e.slot]) for e in tdg.edges))
    out_slots = list(outputs) if outputs is not None else list(tdg.output_slots)
    sig = ("tdg-structure-v1", len(tdg.tasks), tuple(task_rows), edge_rows,
           tuple(canon(s) for s in out_slots))
    return sig, slot_map, tuple(payloads)


def _leaf_signature(leaf: Any) -> tuple:
    # A jax.Array's ``shape`` and ``dtype`` each fetch its aval: fetch it once.
    aval = getattr(leaf, "aval", None)
    if aval is not None:
        return aval.shape, aval.dtype
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        # A leaf without a dtype (a Python scalar) stands in by its type. The
        # 1-tuple never equals a (shape, dtype) pair, where a bare type could:
        # ``np.dtype("float64") == float`` holds.
        return (type(leaf),)
    return getattr(leaf, "shape", ()), dtype


def buffers_signature(buffers: Mapping[str, Any]) -> tuple:
    """Abstract signature of a buffer dict (for replay-cache keying).

    ``(treedef, per-leaf signatures)`` from one flatten of the whole dict:
    the treedef holds the sorted slot names and each slot's structure, and
    each leaf gives ``(shape, dtype)``, or ``(type,)`` without a dtype.
    Arrays and ``ShapeDtypeStruct`` specs of the same slots, shapes and
    dtypes give equal signatures; weak type and slot order are ignored.
    """
    import jax

    if type(buffers) is not dict:
        buffers = dict(buffers)
    leaves, treedef = jax.tree_util.tree_flatten(buffers)
    return treedef, tuple(map(_leaf_signature, leaves))
