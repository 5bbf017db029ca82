"""One run of one cell: data, session, warm-up, the timed window, the check.

The order matters. Set-up (data from the seed, the session, the warm-up on
the cell's own traffic) ends where the window starts. The window runs the
cell's traffic for ``seconds`` on the host clock, traced when asked. After
it come the drain of what the window's deadline cut, the device's peak
memory, the session's release, and only then the reference, so that
neither its time nor its memory counts.
"""

from __future__ import annotations

import contextlib
import shutil
import time
from pathlib import Path
from typing import Dict, Optional

from . import check, trace as trace_mod
from .compiles import CompileLog
from .spec import ROOT, resolve

#: the engine's device programs, by the named scope each holds, and the
#: jitted functions (by name prefix) that carry it; the TPU trace names
#: programs, not scopes
PROGRAMS = {"graft_chain": ("graft_chain",), "graft_probe": ("hash_probe_lens",)}
#: backend calls that get a host span in a traced run
BACKEND_CALLS = ("probe", "probe_visible", "probe_visible_multi", "probe_chain", "segment_sum")
OUT = ROOT / "bench_runs"


def open_session(config: Dict, tables):
    """The system under test on this data: the program's ``Database`` over
    the benchmark's arrays, and a session with the configuration's settings."""
    import graftdb
    from graftdb import EngineConfig
    from repro.relational.table import Database, Table

    db = Database(
        {name: Table(name, dict(cols), dict(dicts)) for name, (cols, dicts) in tables.items()},
        config["scale_factor"],
    )
    return db, graftdb.connect(db, EngineConfig(**config["engine"]))


def _annotate_backend(backend) -> None:
    import jax

    for name in BACKEND_CALLS:
        fn = getattr(backend, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _label=f"backend.{name}", **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **kw)

        setattr(backend, name, wrapped)


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _delta(after: Dict, before: Dict) -> Dict:
    return {k: v - before.get(k, 0) for k, v in after.items() if isinstance(v, (int, float))}


def run(cell: str, seed: int, seconds: float, traced: bool, t_process: float,
        log=print, root: Path = ROOT, stand_in=None) -> Dict:
    """Runs the cell once; returns the run record (see ``bench/metrics``).

    ``stand_in(tables, template, params)``, for the control, answers each
    compared query in the program's place; the record then also keeps the
    program's own readings under ``program_readings``."""
    import jax

    spec = resolve(cell, root)
    config, mix, kind = spec["config"], spec["mix"], spec["kind"]
    devices = jax.devices()[: spec["entry"]["chips"]]
    compiles = CompileLog()
    tables = spec["data"].generate(config["scale_factor"], seed)
    fingerprint = spec["data"].fingerprint(tables)
    log(f"data: {config['name']} seed {seed} {fingerprint}")
    db, session = open_session(config, tables)

    def make_query(template, params, arrival):
        return spec["plans"].make_query(db, template, params, arrival=arrival)

    sample = spec["params"].sample
    warm = kind.Loop(session, make_query, kind.streams(mix, seed, kind.WARMUP, sample),
                     per_client=mix["warmup_per_client"]).run()
    log(f"warm-up: {len(warm.sent)} queries, {len(compiles.events)} compilations "
        f"({compiles.cache_hits} from the persistent cache) by "
        f"{time.perf_counter() - t_process:.3f} s")

    span = lambda name: contextlib.nullcontext()  # noqa: E731
    trace_dir = OUT / f"trace-{cell}-{seed}"
    if traced:
        _annotate_backend(session.backend)
        span = jax.profiler.TraceAnnotation
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    counters0 = dict(session.counters)
    backend0 = dict(session.backend.stats())
    loop = kind.Loop(session, make_query, kind.streams(mix, seed, kind.WINDOW, sample),
                     seconds=seconds, span=span)
    with span(trace_mod.WINDOW):
        loop.run()
    t_drained = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    counters = _delta(session.counters, counters0)
    backend_stats = _delta(session.backend.stats(), backend0)
    window_compiles = compiles.between(loop.t_start, loop.t_end)
    compiles.close()
    done = loop.completed()
    status = [s.future.status for s in loop.sent]
    n_failed = sum(st in ("failed", "cancelled") for st in status)
    cut = len(loop.sent) - len(done) - n_failed
    log(f"window: {len(done)} completed, {cut} cut by the window's end, {n_failed} failed; "
        f"drained {t_drained - loop.t_end:.3f} s after the close")
    for e in window_compiles:
        log(f"compiled in the window: {e['fun']} {e['seconds']:.4f} s "
            f"{'(cache read) ' if e['cached'] else ''}{e['shapes']}")
    peak = _peak_bytes(devices)
    answers = [{"template": s.template, "params": s.params, "result": s.future.result()}
               for s in done]
    session.close()
    del session, warm
    reduced = None
    if traced:
        reduced = trace_mod.reduce(trace_mod.load(trace_mod.latest_xplane(str(trace_dir))),
                                   PROGRAMS, seconds)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ref = spec["reference"]
    t_ref = time.perf_counter()
    read = check.readings(answers, lambda t, p: ref.answer(tables, t, p), n_failed)
    read["reference_s"] = time.perf_counter() - t_ref
    program_read = None
    if stand_in is not None:
        program_read = read
        answers = [dict(a, result=stand_in(tables, a["template"], a["params"])) for a in answers]
        read = check.readings(answers, lambda t, p: ref.answer(tables, t, p), n_failed)
    checks = check.verdict(read, config["limits"])
    return {
        "cell": cell,
        "seed": seed,
        "seconds": seconds,
        "traced": traced,
        "setup_s": loop.t_start - t_process,
        "fingerprint": fingerprint,
        "completed": len(done),
        "cut": cut,
        "failed": n_failed + read["wrong_shape"],
        "latencies": [s.t_done - s.t_submit for s in done],
        "queries": [
            {"client": s.client, "template": s.template, "params": s.params,
             "latency_s": None if s.t_done is None else s.t_done - s.t_submit,
             "in_window": s.t_done is not None and s.t_done <= loop.t_end, "status": st}
            for s, st in zip(loop.sent, status)
        ],
        "counters": counters,
        "backend": backend_stats,
        "window_compiles": window_compiles,
        "setup_compiles": sum(e["t_end"] < loop.t_start for e in compiles.events),
        "trace": reduced,
        "memory_peak_bytes": peak,
        "readings": read,
        "program_readings": program_read,
        "checks": checks,
        "correct": check.is_correct(checks, read["compared"]),
    }
