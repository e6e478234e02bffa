"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device busy time, per-kernel device time, the device
operations that took most time, and the idle gaps labelled by the host span
in flight.

Planes named ``/device:TPU:<n>`` are devices; their ``XLA Ops`` line holds one
event per executed operation. Host spans are the ``TraceAnnotation`` events
the harness writes on the ``/host:CPU`` plane (the same clock). The traced
window is the host span ``bench.traced``, which the harness opens right after
the profiler starts and closes before it stops.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.traced"
HOST_PREFIXES = ("bench.", "driver.", "sched.", "engine.")
OPS_LINE = "XLA Ops"
# control-flow ops whose interval holds other ops: counted in busy time, not
# ranked among the operations
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[int, int]  # [start_ns, end_ns)


@dataclass
class Events:
    window: Interval
    device_ops: Dict[str, List[Tuple[int, int, str]]]  # plane -> (start, end, name)
    host_spans: List[Tuple[int, int, str]] = field(default_factory=list)


def load(path) -> Events:
    """Read the trace file into plain intervals (nanoseconds)."""
    from jax.profiler import ProfileData

    return events(ProfileData.from_file(str(path)), path)


def events(pd, path="the trace") -> Events:
    """Plain intervals (nanoseconds) of a parsed trace (``ProfileData``)."""
    ops: Dict[str, List[Tuple[int, int, str]]] = {}
    spans: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [(int(e.start_ns), int(e.end_ns),
                                        op_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        spans.append((int(e.start_ns), int(e.end_ns), e.name))
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return Events(window=(win[0][0], win[0][1]), device_ops=ops,
                  host_spans=[s for s in spans if s[2] != WINDOW_SPAN])


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the HLO
    instruction's name without its text."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``: the name without its instance number."""
    head, _, tail = name.partition(".")
    return head if head and tail[:1].isdigit() else name


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], w: Interval) -> List[Interval]:
    return [(max(a, w[0]), min(b, w[1])) for a, b in intervals
            if b > w[0] and a < w[1]]


def _label(spans: Sequence[Tuple[int, int, str]], t: int) -> str:
    """The innermost host span covering time t (latest start wins)."""
    best = None
    for a, b, name in spans:
        if a <= t < b and (best is None or a > best[0]):
            best = (a, name)
    return best[1] if best else "no host span"


def reduce(ev: Events, kernels: Sequence[str] = (), top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, kernel_s {name: summed device
    seconds over devices / devices}, device_ops [[name, s]] and idle_gaps
    [[host span, s]] (summed, largest first, device 0). An operation counts
    toward each name in ``kernels`` that its own name contains."""
    w = ev.window
    n = max(len(ev.device_ops), 1)
    busy = 0
    kernel_ns: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, int] = defaultdict(int)
    gaps_ns: Dict[str, int] = defaultdict(int)
    for i, (plane, ops) in enumerate(sorted(ev.device_ops.items())):
        within = [(a, b, nm) for a, b, nm in ops if b > w[0] and a < w[1]]
        merged = merge(clip([(a, b) for a, b, _ in within], w))
        busy += sum(b - a for a, b in merged)
        for a, b, nm in within:
            d = min(b, w[1]) - max(a, w[0])
            if op_kind(nm) not in CONTAINERS:
                op_ns[nm] += d
            for k in kernels:
                if k in nm:
                    kernel_ns[k] += d
        if i == 0:
            edges = [w[0]] + [x for ab in merged for x in ab] + [w[1]]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps_ns[_label(ev.host_spans, (a + b) // 2)] += b - a
    rank = lambda d: sorted(([k, v / 1e9 / n] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w[1] - w[0]) / 1e9,
        "busy_s": busy / 1e9 / n,
        "devices": len(ev.device_ops),
        "kernel_s": {k: v / 1e9 / n for k, v in kernel_ns.items()},
        "device_ops": rank(op_ns),
        "idle_gaps": sorted(([k, v / 1e9] for k, v in gaps_ns.items()),
                            key=lambda kv: -kv[1])[:top],
    }
