"""Every XLA compilation of the run, with when it ended and what it was.

``/jax/core/compile/backend_compile_duration`` fires once per compilation,
persistent-cache reads included, with the function's name; a read from the
persistent cache fires ``/jax/compilation_cache/cache_hits`` just before.
JAX's lowering log names the argument shapes before either; it is captured
at DEBUG level on a handler of its own, so nothing is printed.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, List

EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
_LOWERING_LOGGER = "jax._src.interpreters.pxla"


class _ShapeLog(logging.Handler):
    def __init__(self):
        super().__init__(logging.DEBUG)
        self.last = ""

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.last = msg.split(". Argument mapping")[0][:400]


class CompileLog:
    def __init__(self):
        import jax

        self.events: List[Dict] = []
        self.cache_hits = 0
        self._hit = False
        self._jax = jax
        self._shapes = _ShapeLog()
        self._logger = logging.getLogger(_LOWERING_LOGGER)
        self._old = (self._logger.level, self._logger.propagate)
        self._logger.setLevel(logging.DEBUG)
        self._logger.propagate = False
        self._logger.addHandler(self._shapes)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == EVENT:
            self.events.append({
                "t_end": time.perf_counter(),
                "seconds": secs,
                "fun": kw.get("fun_name", ""),
                "cached": self._hit,
                "shapes": self._shapes.last,
            })
            self._hit = False

    def _on_event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1
            self._hit = True

    def between(self, t0: float, t1: float) -> List[Dict]:
        """Compilations that ended inside [t0, t1] on the host clock."""
        return [e for e in self.events if t0 <= e["t_end"] <= t1]

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on_duration)
        self._jax.monitoring.unregister_event_listener(self._on_event)
        self._logger.removeHandler(self._shapes)
        self._logger.setLevel(self._old[0])
        self._logger.propagate = self._old[1]
