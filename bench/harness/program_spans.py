"""Reduces the program's own host spans (``graftdb.*``) in a traced window.

The program opens a ``jax.profiler.TraceAnnotation`` around each step of
its work (``repro/core/tracing.py``; names in README, "Tracing a
session"), so they sit on the host plane of the same ``.xplane.pb`` as the
device planes, on the same clock. ``trace.load`` keeps only the host spans
that the benchmark labels idle gaps with; ``load`` here keeps them too,
together with the program's, grouped by host line (one line per thread),
because nesting is only meaningful on one thread. ``reduce`` then gives:

* ``program_self_s``: each span name's self time inside the window, its
  duration less the part that its child spans on the same thread cover;
* ``idle_gaps``: each device idle gap put down to the innermost span over
  its midpoint, program spans included, over all threads the one that
  started last; a gap under none is the engine's unlabelled host work;
* ``layers_s``: the self time of each program layer (``LAYERS``).

The innermost span at an instant is found by one sweep per thread over its
spans in start order with a stack of the open ones, so a parent with any
number of children still labels the gaps between them.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

from . import trace as trace_mod
from .trace import COMPILE, ENGINE, WINDOW, Span

PROGRAM = "graftdb."

#: the program's layers (PERF.md §3), by the spans whose self time each
#: sums; a name ending in "." takes every span under it
LAYERS = {
    "admit": ("graftdb.admit", "graftdb.graft"),
    "runtime_host": ("graftdb.schedule", "graftdb.unit", "graftdb.scan", "graftdb.plan",
                     "graftdb.filter", "graftdb.join", "graftdb.build"),
    "agg_host": ("graftdb.aggregate", "graftdb.complete"),
    "backend_host": ("graftdb.backend.", "graftdb.h2d", "graftdb.d2h"),
    "device_wait": ("graftdb.device_wait",),
}


def keep(name: str) -> bool:
    """A host span this reduction reads: the program's and the benchmark's
    labelling spans, and compile events."""
    return name.startswith(PROGRAM) or trace_mod._labelling(name)


def load(path: str) -> Dict[str, object]:
    """``trace.load``'s record, plus ``"threads"``, every kept host span by
    host line, ``{"<plane>#<line index>": [Span]}``, and ``"meta"``, the
    ids a program span carries (``qid``, ``scan``, ``part``, ``morsel``),
    ``{(line, start ns, name): {id: value}}``."""
    from jax.profiler import ProfileData

    out = trace_mod.load(path)
    threads: Dict[str, List[Span]] = {}
    meta: Dict[Tuple[str, int, str], Dict[str, object]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            key = f"{plane.name}#{i}"
            spans = []
            for e in line.events:
                if not keep(e.name):
                    continue
                sp = trace_mod._span(e, e.name)
                spans.append(sp)
                if e.name.startswith(PROGRAM):
                    stats = {k: v for k, v in e.stats}
                    if stats:
                        meta[(key, sp[0], sp[2])] = stats
            if spans:
                threads[key] = spans
    return dict(out, threads=threads, meta=meta)


def _nesting(spans: Sequence[Span]) -> List[Span]:
    """Parents before their children: by start, then the longer first."""
    return sorted(spans, key=lambda sp: (sp[0], -sp[1]))


def self_times(threads: Dict[str, Sequence[Span]], lo: int, hi: int) -> Dict[str, float]:
    """Seconds of each span name's self time inside ``[lo, hi)``: the span
    clipped to the window, less what its direct children on the same
    thread, clipped alike, cover."""
    out: Dict[str, int] = defaultdict(int)

    def close(entry):
        s, e, name, child = entry
        own = max(0, min(e, hi) - max(s, lo))
        out[name] += own - min(child, own)

    for spans in threads.values():
        stack: List[list] = []  # [start, end, name, child ns]
        for s, e, name in _nesting(spans):
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            if stack:
                parent = stack[-1]
                cs, ce = max(s, parent[0], lo), min(e, parent[1], hi)
                if ce > cs:
                    parent[3] += ce - cs
            stack.append([s, e, name, 0])
        while stack:
            close(stack.pop())
    return {k: v / 1e9 for k, v in out.items() if v}


def innermost(threads: Dict[str, Sequence[Span]], points: Iterable[int],
              default: str) -> List[str]:
    """For each instant in ``points``, the name of the innermost span over
    it: per thread the deepest open span of a sweep in start order, over
    the threads the one that started last; ``default`` under none."""
    points = list(points)
    order = sorted(range(len(points)), key=points.__getitem__)
    best: List[Tuple[int, str]] = [(-1, default)] * len(points)
    for spans in threads.values():
        ordered = _nesting(spans)
        stack: List[Span] = []
        i = 0
        for k in order:
            t = points[k]
            while i < len(ordered) and ordered[i][0] <= t:
                sp = ordered[i]
                while stack and stack[-1][1] <= sp[0]:
                    stack.pop()
                stack.append(sp)
                i += 1
            while stack and stack[-1][1] < t:
                stack.pop()
            if stack and stack[-1][0] > best[k][0]:
                best[k] = (stack[-1][0], stack[-1][2])
    return [name for _, name in best]


def layer_seconds(self_s: Dict[str, float]) -> Dict[str, float]:
    """Self seconds of each of ``LAYERS``."""

    def member(name: str, spans: Sequence[str]) -> bool:
        return any(name.startswith(p) if p.endswith(".") else name == p for p in spans)

    return {layer: sum(v for k, v in self_s.items() if member(k, spans))
            for layer, spans in LAYERS.items()}


def reduce(trace: Dict[str, object], seconds: float, top: int = 30) -> Dict[str, object]:
    """Self times and program-labelled idle gaps of the window that starts
    at the host event ``bench.window`` and runs ``seconds``."""
    windows = [s for s, _, n in trace["host"] if n == WINDOW]
    if not windows:
        raise ValueError(f"no host event {WINDOW!r} in the trace")
    lo = windows[0]
    hi = lo + int(seconds * 1e9)
    threads = {
        k: [(s, e, COMPILE if "ompile" in n else n) for s, e, n in v if n != WINDOW]
        for k, v in trace["threads"].items()
    }
    self_s = self_times(threads, lo, hi)
    busy_ns = 0
    gap_ns: Dict[str, int] = defaultdict(int)
    devices = [d for d in trace["devices"].values() if trace_mod._inside(d["ops"], lo, hi)]
    for dev in devices:
        busy = trace_mod.union((s, e) for s, e, _ in trace_mod._inside(dev["ops"], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), label in zip(gaps, innermost(threads, [(a + b) // 2 for a, b in gaps], ENGINE)):
            gap_ns[label] += b - a
    n = max(len(devices), 1)
    ranked = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "n_devices": len(devices),
        "program_self_s": self_s,
        "layers_s": layer_seconds(self_s),
        "idle_gaps": [[k, v / n / 1e9] for k, v in ranked],
    }
