"""Host spans on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: a TraceMe
event that lands on the host plane of the ``.xplane.pb`` that
``jax.profiler`` writes, on the same clock as the device planes, so a gap
in the device's work can be put down to the innermost span around it.
``meta`` (query, scan, partition and morsel ids) rides on the event as its
stats. Tracing is on exactly when a profiler session is, e.g. under
``jax.profiler.trace(dir)`` around ``Session.run``.

Outside a profiler session, and whenever JAX has not been imported,
``span`` returns one shared no-op context, so a span costs its Python call
and nothing more; it imports nothing, so NumPy-only sessions (reference
backend, no mesh) never load JAX. README ("Tracing a session") lists the
span names.
"""

from __future__ import annotations

import functools
import sys
from contextlib import nullcontext

_NOOP = nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def span(name: str, **meta):
    """A context manager timing ``name`` on the profiler's host plane."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _NOOP
        from jax.profiler import TraceAnnotation as _annotation
    if not _annotation.is_enabled():
        return _NOOP
    return _annotation(name, **meta)


def spanned(name: str):
    """Decorator: each call of the function is one ``span(name)``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return inner

    return wrap
