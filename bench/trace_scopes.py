"""Reductions of a profiler trace that read what the program marks inside
itself: device seconds by the packed step's named scope (``scope_seconds``)
and idle device time split over the host spans in flight
(``idle_by_span``).

``load`` reads the trace as ``trace_reduce.load`` does, and each device's
``XLA Modules`` line besides, so every operation can be told by the compiled
module it ran in. An operation of a module that has an op-to-scope map
(``Engine.step_op_scopes``) counts under its scope; one the map does not
name under ``unscoped``; one the maps of several step shapes name
differently under ``ambiguous``; an operation of any other module under the
module's name.
"""
from __future__ import annotations

import heapq
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Mapping, Sequence, Tuple

from bench import trace_reduce as T

MODULES_LINE = "XLA Modules"
# the packed step's named scopes (``repro.core.packed_step.STEP_SCOPES``)
STEP_SCOPES = ("embed", "qkv", "kv_pool", "attention", "o_proj", "mlp",
               "lm_head")
UNSCOPED, AMBIGUOUS = "unscoped", "ambiguous"
NO_MODULE, NO_SPAN = "no module", "no host span"

Span = Tuple[int, int, str]  # [start_ns, end_ns), name


@dataclass
class ScopedEvents(T.Events):
    modules: Dict[str, List[Span]] = field(default_factory=dict)  # plane -> modules


def module_name(text: str) -> str:
    """``jit_paged_step(4711)`` -> ``jit_paged_step``: the module's name
    without its program fingerprint."""
    return text.split("(", 1)[0]


def load(path) -> ScopedEvents:
    from jax.profiler import ProfileData

    ev = T.load(path)
    modules: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name in ev.device_ops:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = sorted(
                        (int(e.start_ns), int(e.end_ns), module_name(e.name))
                        for e in line.events)
    return ScopedEvents(ev.window, ev.device_ops, ev.host_spans, modules)


def merge_op_scopes(maps: Iterable[Mapping[str, str]]) -> Dict[str, str]:
    """One op-to-scope map from the maps of several step shapes: a name the
    maps give different scopes becomes ``ambiguous``."""
    out: Dict[str, str] = {}
    for m in maps:
        for op, scope in m.items():
            out[op] = scope if out.get(op, scope) == scope else AMBIGUOUS
    return out


def _innermost(items: Sequence[Tuple[int, int, int, str]], window) -> List[Span]:
    """Split ``window`` into pieces ``(start, end, label)``, each under the
    covering item that started last (items are ``(start, end, rank,
    label)``; at equal starts the lower rank wins). Uncovered time gets no
    piece."""
    lo, hi = window
    items = sorted((max(a, lo), min(b, hi), r, lab) for a, b, r, lab in items
                   if b > lo and a < hi and b > a)
    edges = sorted({x for a, b, _, _ in items for x in (a, b)})
    out: List[Span] = []
    live: list = []  # heap, latest start on top; ended items leave lazily
    i = 0
    for t, nxt in zip(edges, edges[1:]):
        while i < len(items) and items[i][0] <= t:
            a, b, r, lab = items[i]
            heapq.heappush(live, (-a, r, b, lab))
            i += 1
        while live and live[0][2] <= t:
            heapq.heappop(live)
        if live:
            out.append((t, nxt, live[0][3]))
    return out


def scope_seconds(ev: ScopedEvents,
                  op_scopes: Mapping[str, Mapping[str, str]]) -> Dict[str, float]:
    """Device seconds in the traced window by scope, mean over devices:
    ``op_scopes`` maps a module name to its {op name: scope}. Each instant
    of busy time goes to the innermost operation running (a loop's own
    interval only where none of its body's runs), so the values sum to the
    busy time of ``trace_reduce.reduce``."""
    total: Dict[str, int] = {}
    for plane, ops in ev.device_ops.items():
        mods = ev.modules.get(plane, [])
        starts = [m[0] for m in mods]
        items = []
        for a, b, name in ops:
            j = bisect_right(starts, a) - 1
            mod = mods[j][2] if j >= 0 and a < mods[j][1] else NO_MODULE
            scopes = op_scopes.get(mod)
            label = mod if scopes is None else scopes.get(name, UNSCOPED)
            items.append((a, b, int(T.op_kind(name) in T.CONTAINERS), label))
        for a, b, lab in _innermost(items, ev.window):
            total[lab] = total.get(lab, 0) + b - a
    n = max(len(ev.device_ops), 1)
    return dict(sorted(((k, v / 1e9 / n) for k, v in total.items()),
                       key=lambda kv: -kv[1]))


def idle_by_span(ev: T.Events) -> Dict[str, float]:
    """Idle seconds of the first device in the traced window, each idle
    instant under the innermost host span then in flight (``no host span``
    where none is): a gap that outlasts one span is split over the spans it
    overlaps, unlike ``trace_reduce.reduce``'s label at the gap's midpoint."""
    if not ev.device_ops:
        return {}
    w = ev.window
    ops = sorted(ev.device_ops.items())[0][1]
    busy = T.merge(T.clip([(a, b) for a, b, _ in ops], w))
    edges = [w[0]] + [x for ab in busy for x in ab] + [w[1]]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    pieces = _innermost([(a, b, 0, n) for a, b, n in ev.host_spans], w)
    out: Dict[str, int] = {}
    j = 0
    for ga, gb in gaps:  # both lists are sorted and disjoint within
        covered = 0
        while j < len(pieces) and pieces[j][1] <= ga:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < gb:
            a, b, lab = pieces[k]
            d = min(b, gb) - max(a, ga)
            out[lab] = out.get(lab, 0) + d
            covered += d
            k += 1
        if gb - ga > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0) + gb - ga - covered
    return dict(sorted(((k, v / 1e9) for k, v in out.items()),
                       key=lambda kv: -kv[1]))
