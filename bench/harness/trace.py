"""Reduces a JAX profiler trace of the window to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes. On a TPU
plane, the ``XLA Modules`` line holds one event per program execution,
named ``jit_<function>(<id>)``, and the ``XLA Ops`` line the HLO ops inside
them, named by their HLO text. The TPU trace carries no named scope on an
op, so a program is known by its jitted function's name. The host plane
gives the benchmark's own spans (``bench.*``, ``backend.*``) and JAX's
compile events. ``reduce`` then works on plain tuples, so a test can check
it by hand:

* the window runs ``seconds`` from the start of the host event
  ``bench.window``;
* busy time is the union of a device's op intervals inside the window,
  averaged over the devices that ran an op in it;
* program time sums each program's executions inside the window;
* each idle gap between busy intervals is labelled with the innermost
  labelling host span over the gap's midpoint; a gap with none is the
  engine's own host work.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Span = Tuple[int, int, str]  # start ns, end ns, name

WINDOW = "bench.window"
ENGINE = "engine host work"
COMPILE = "compile"


def latest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def program_name(module: str) -> str:
    """``jit_graft_chain(7348603012640228264)`` -> ``graft_chain``."""
    return re.sub(r"\(\d+\)$", "", module).removeprefix("jit_")


def op_name(hlo: str) -> str:
    """``%fusion.12 = u32[32768]... fusion(...)`` -> ``%fusion.12``."""
    return hlo.split(" = ", 1)[0]


def load(path: str) -> Dict[str, object]:
    """``{"devices": {plane: {"ops": [Span], "programs": [Span]}}, "host": [Span]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Span]]] = {}
    host: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "programs": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] += [_span(e, op_name(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    dev["programs"] += [_span(e, program_name(e.name)) for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [_span(e, e.name) for e in line.events if _labelling(e.name)]
    return {"devices": devices, "host": host}


def _span(event, name: str) -> Span:
    s = int(event.start_ns)
    return (s, s + int(event.duration_ns), name)


def _labelling(name: str) -> bool:
    return name.startswith(("bench.", "backend.")) or "ompile" in name


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _inside(spans: Sequence[Span], lo: int, hi: int) -> List[Span]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in spans if e > lo and s < hi]


class _Innermost:
    """The span over an instant that started last: for nested spans, the
    innermost one."""

    def __init__(self, spans: Sequence[Span], default: str):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.default = default

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        for s, e, name in reversed(self.spans[max(0, i - 256) : i + 1]):
            if e >= t:
                return name
        return self.default


def reduce(
    trace: Dict[str, object], programs: Dict[str, Sequence[str]], seconds: float, top: int = 10
) -> Dict[str, object]:
    """``programs`` maps a name to the jitted functions it sums, by prefix."""
    windows = [s for s, _, n in trace["host"] if n == WINDOW]
    if not windows:
        raise ValueError(f"no host event {WINDOW!r} in the trace")
    lo = windows[0]
    hi = lo + int(seconds * 1e9)
    devices = {k: v for k, v in trace["devices"].items() if _inside(v["ops"], lo, hi)}
    if not devices:
        raise ValueError("no TPU op inside the window")
    labels = _Innermost(
        [(s, e, COMPILE if "ompile" in n else n)
         for s, e, n in _inside(trace["host"], lo, hi) if n != WINDOW],
        ENGINE,
    )
    busy_ns = 0
    program_ns = {k: 0 for k in programs}
    op_ns: Dict[str, int] = defaultdict(int)
    gap_ns: Dict[str, int] = defaultdict(int)
    for dev in devices.values():
        ops = _inside(dev["ops"], lo, hi)
        runs = _inside(dev["programs"], lo, hi)
        busy = union((s, e) for s, e, _ in ops)
        busy_ns += sum(e - s for s, e in busy)
        for s, e, prog in runs:
            for k, prefixes in programs.items():
                if prog.startswith(tuple(prefixes)):
                    program_ns[k] += e - s
        owner = _Innermost(runs, "?")
        for s, e, name in ops:
            op_ns[f"{owner.at(s)}/{name}"] += e - s
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gap_ns[labels.at((a + b) // 2)] += b - a
    n = len(devices)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "n_devices": n,
        "program_s": {k: v / n / 1e9 for k, v in program_ns.items()},
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gap_ns),
    }
