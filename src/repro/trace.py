"""The program's spans and counters: off by default, one branch when off.

    from repro import trace

    trace.enable()
    with trace.span("loader.sample", batch=3):
        ...
    trace.count("loader.sampled_edges", 5120)
    trace.totals()  # {"spans": {name: [n, seconds]}, "counters": {name: n}}

With tracing on, each span is also a ``jax.profiler.TraceAnnotation``: under
a ``jax.profiler`` trace it lands on the profiler's host plane, on the same
clock as the device's operations, with its ids (``batch=3``) as metadata.
The totals are kept in memory under a lock (spans come from the loader's
threads) and written nowhere. With tracing off, :func:`span` returns one
shared no-op context and :func:`count` returns at once.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict

import jax

_NOOP = contextlib.nullcontext()
_lock = threading.Lock()
_enabled = False
_spans: Dict[str, list] = {}
_counters: Dict[str, int] = {}


class _Span:
    __slots__ = ("name", "annotation", "t0")

    def __init__(self, name: str, ids: Dict[str, Any]):
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        with _lock:
            tot = _spans.setdefault(self.name, [0, 0.0])
            tot[0] += 1
            tot[1] += dt
        return False


def span(name: str, **ids: Any):
    """A context that times ``name`` (and marks it on the profiler's host
    plane with ``ids``) when tracing is on; a shared no-op when off."""
    if not _enabled:
        return _NOOP
    return _Span(name, ids)


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` when tracing is on."""
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enabled() -> bool:
    """Whether tracing is on: a guard for counts that cost work to make."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Clear the totals (tracing stays on or off as it was)."""
    with _lock:
        _spans.clear()
        _counters.clear()


def totals() -> Dict[str, Dict[str, Any]]:
    """``{"spans": {name: [n, seconds]}, "counters": {name: n}}``, a copy."""
    with _lock:
        return {"spans": {k: list(v) for k, v in _spans.items()},
                "counters": dict(_counters)}
