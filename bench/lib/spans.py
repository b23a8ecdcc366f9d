"""Per-layer metrics from the program's own spans (``repro.core.spans``).

The program records each call of ``TaskGraphRegion.replay`` as an outermost
``taskgraph.replay`` span with children ``.key``, ``.lower`` (on a cache
miss), ``.dispatch`` and ``.wait``, and set-up as ``taskgraph.record`` and
``taskgraph.warmup.trace``/``.compile`` spans, in a bounded ring.

The window's replays are the last ``replays`` outermost ``taskgraph.replay``
spans of the ring: set-up replays come before the window, and the driver
makes none after it. Where the ring holds fewer, or the program has no
spans at all, every reader returns ``None`` rather than a partial number.
"""
from __future__ import annotations

from typing import Sequence

REPLAY = "taskgraph.replay"


def ring() -> list | None:
    """The program's span records, oldest first; None if it keeps none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.recent()


def _ns(r) -> int:
    return r.t1_ns - r.t0_ns


def window_replays(records: Sequence | None, replays: int | None
                   ) -> list[tuple] | None:
    """``(root, children)`` of the window's replays, or None."""
    if records is None or not replays:
        return None
    roots = [r for r in records if r.name == REPLAY and r.parent is None]
    if len(roots) < replays:
        return None
    roots = roots[-replays:]
    index = {r.id: i for i, r in enumerate(roots)}
    children: list[list] = [[] for _ in roots]
    for r in records:
        if r.parent in index:
            children[index[r.parent]].append(r)
    return list(zip(roots, children))


def child_ms(records, replays, name: str) -> float | None:
    """Mean time per window replay in direct children called ``name``."""
    win = window_replays(records, replays)
    if win is None:
        return None
    ns = sum(_ns(c) for _, kids in win for c in kids if c.name == name)
    return ns / len(win) * 1e-6


def replay_ms(records, replays) -> float | None:
    """Mean duration of the window's ``taskgraph.replay`` spans."""
    win = window_replays(records, replays)
    if win is None:
        return None
    return sum(_ns(root) for root, _ in win) / len(win) * 1e-6


def self_ms(records, replays) -> float | None:
    """Mean self time of the window's replay spans: each one's duration
    less what its direct children cover."""
    win = window_replays(records, replays)
    if win is None:
        return None
    ns = sum(_ns(root) - sum(_ns(c) for c in kids) for root, kids in win)
    return ns / len(win) * 1e-6


def outside_ms(records, replays, window_s: float) -> float | None:
    """Window time per replay spent outside the replay call: the caller's
    loop, and freeing the previous replay's outputs."""
    inside = replay_ms(records, replays)
    if inside is None:
        return None
    return window_s / replays * 1e3 - inside


def last_s(records, name: str) -> float | None:
    """Duration of the last span called ``name``, in seconds."""
    if records is None:
        return None
    hits = [r for r in records if r.name == name]
    return _ns(hits[-1]) * 1e-9 if hits else None
