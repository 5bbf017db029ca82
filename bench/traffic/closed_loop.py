"""Closed-loop traffic: each client keeps one query outstanding.

A mix file (``bench/traffic/<mix>.json``) with ``"kind": "closed_loop"``
gives ``clients``, the ``templates`` it runs, ``order`` (a data file under
``bench/traffic/`` whose ``streams`` list query numbers in the order each
query stream runs them), ``first_stream``, ``warmup_stream``,
``warmup_per_client`` and ``params`` (the module under ``bench/traffic/``
that draws a template's substitution parameters).

Client c is query stream ``first_stream + c``: it runs that stream's order,
kept to the mix's templates, one pass after another, so every seed runs the
same templates in the same order. The seed draws every parameter. The
warm-up deals the order of ``warmup_stream`` round-robin to the clients,
``warmup_per_client`` queries each, so that it runs every template once
whenever clients * warmup_per_client covers them; its parameters come from
a separate stream of the same seed.
"""

from __future__ import annotations

import contextlib
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, ContextManager, Dict, Iterator, List, Optional, Tuple

import numpy as np

WINDOW, WARMUP = 0, 1  # stream phases
HERE = Path(__file__).resolve().parent


def stream_orders(mix: Dict) -> List[List[str]]:
    """Each stream's template order, kept to the mix's templates."""
    with open(HERE / f"{mix['order']}.json") as f:
        rows = json.load(f)["streams"]
    keep = set(mix["templates"])
    return [[f"q{n}" for n in row if f"q{n}" in keep] for row in rows]


def client_templates(mix: Dict, phase: int) -> List[List[str]]:
    """The templates each client runs, in order: endless for the window,
    ``warmup_per_client`` long for the warm-up."""
    orders, n = stream_orders(mix), mix["clients"]
    if phase == WINDOW:
        return [orders[(mix["first_stream"] + c) % len(orders)] for c in range(n)]
    warm = orders[mix["warmup_stream"]]
    k = mix["warmup_per_client"]
    return [[warm[(c + j * n) % len(warm)] for j in range(k)] for c in range(n)]


def streams(mix: Dict, seed: int, phase: int, sample_params) -> List[Iterator[Tuple[str, Dict]]]:
    """One (template, params) stream per client; a function of ``seed`` and
    ``phase`` alone."""

    def client(c: int, seq: List[str]):
        rng = np.random.default_rng(np.random.SeedSequence([seed, phase, 1 + c]))
        i = 0
        while phase == WINDOW or i < len(seq):
            t = seq[i % len(seq)]
            yield t, sample_params(t, rng)
            i += 1

    return [client(c, seq) for c, seq in enumerate(client_templates(mix, phase))]


@dataclass
class Sent:
    """One query the loop submitted, timed on the host clock."""

    client: int
    template: str
    params: Dict
    future: object
    t_submit: float
    t_done: Optional[float] = None


@dataclass
class Loop:
    """Drives ``session`` with the clients' streams until each client has
    completed ``per_client`` queries, or, with ``seconds``, until the window
    closes. Window queries carry the window's end as their deadline on the
    session clock, so the engine stops them at its next morsel boundary.
    ``span(name)`` wraps each submission and completion callback, for a
    trace."""

    session: object
    make_query: Callable[[str, Dict, float], object]
    streams: List[Iterator[Tuple[str, Dict]]]
    per_client: Optional[int] = None
    seconds: Optional[float] = None
    span: Callable[[str], ContextManager] = lambda name: contextlib.nullcontext()
    sent: List[Sent] = field(default_factory=list)
    t_start: float = 0.0
    t_end: float = float("inf")

    def _submit(self, c: int) -> None:
        template, params = next(self.streams[c])
        t = time.perf_counter()  # the query's plan is built on its clock
        q = self.make_query(template, params, self.session.now)
        deadline = None
        if self.seconds is not None:
            deadline = self.session.now + (self.t_end - time.perf_counter())
        with self.span("bench.submit"):
            fut = self.session.submit(q, deadline=deadline)
        self._by_qid[q.qid] = len(self.sent)
        self.sent.append(Sent(c, template, params, fut, t))

    def _on_complete(self, fut) -> None:
        now = time.perf_counter()
        s = self.sent[self._by_qid[fut.qid]]
        s.t_done = now
        done = self._done[s.client] = self._done[s.client] + 1
        if now < self.t_end and (self.per_client is None or done < self.per_client):
            with self.span("bench.complete"):
                self._submit(s.client)

    def run(self) -> "Loop":
        self._by_qid: Dict[int, int] = {}
        self._done = [0] * len(self.streams)
        self.t_start = time.perf_counter()
        if self.seconds is not None:
            self.t_end = self.t_start + self.seconds
        for c in range(len(self.streams)):
            self._submit(c)
        self.session.run(on_complete=self._on_complete)
        return self

    def completed(self) -> List[Sent]:
        """Queries that completed inside the window."""
        return [s for s in self.sent if s.t_done is not None and s.t_done <= self.t_end]
