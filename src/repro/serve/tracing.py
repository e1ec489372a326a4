"""Spans of the serving path, recorded only while a JAX profile is captured.

An operator who captures a profile (``jax.profiler.trace``) sees where a
request's time goes beside the device operations it ran:

  ``repro.pump.queue``     a request waiting in the pump's queue, from
                           ``submit()`` until the pump takes it
  ``repro.pump.batch``     one pump micro-batch, taken to every ticket
                           resolved
  ``repro.engine.launch``  the call into the compiled search: the query
                           block copied to the device, then the dispatch
  ``repro.engine.wait``    waiting for the device to finish the batch
  ``repro.engine.fetch``   the answers copied back to the host

Every span of one micro-batch carries its ``batch`` id (a queue span also
carries its ``ticket``).  ``span`` spans land in the profile's host
trace through ``jax.profiler.TraceAnnotation``; every span, ``record``'s
cross-thread queue spans too, is also kept in a bounded in-memory log
(``recorded()``) stamped with ``time.perf_counter_ns()``, the clock of
``Ticket`` and the pump.  With no profile running a span site makes one
``TraceAnnotation.is_enabled()`` check, enters a shared empty context
and records nothing (0.3 us a site on a TPU v5e host; 3.2 us a span with
the profile on).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time

import jax

#: the in-memory log holds at most this many spans; past it, spans are
#: counted in ``dropped()`` instead of kept
CAPACITY = 1 << 20

_enabled = jax.profiler.TraceAnnotation.is_enabled
_log: list = []
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count()
_local = threading.local()
_NULL = contextlib.nullcontext()      # every site's context while off


def _keep(name: str, start_ns: int, end_ns: int, ids: dict) -> None:
    global _dropped
    with _lock:
        if len(_log) < CAPACITY:
            _log.append((name, start_ns, end_ns, ids))
        else:
            _dropped += 1


class _Span:
    __slots__ = ("name", "ids", "annotation", "start")

    def __init__(self, name: str, ids: dict):
        batch = getattr(_local, "batch", None)
        if batch is not None:
            ids.setdefault("batch", batch)
        self.name, self.ids = name, ids

    def __enter__(self):
        self.annotation = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self.annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _keep(self.name, self.start, end, self.ids)
        return False


def span(name: str, **ids):
    """Context manager: a span of this thread, in the profile and the log,
    tagged with ``ids`` and the thread's open ``batch`` id."""
    if not _enabled():
        return _NULL
    return _Span(name, ids)


def record(name: str, start_ns: int, end_ns: int, **ids) -> None:
    """Log a span whose ends lie on different threads (a request queued by
    a client and taken by the pump); ``perf_counter_ns`` stamps."""
    if _enabled():
        _keep(name, int(start_ns), int(end_ns), ids)


class _Batch:
    __slots__ = ("id", "outer")

    def __enter__(self) -> int:
        self.outer = getattr(_local, "batch", None)
        self.id = next(_ids)
        _local.batch = self.id
        return self.id

    def __exit__(self, *exc):
        _local.batch = self.outer
        return False


def batch():
    """Context manager: a fresh micro-batch id (returned by ``with``) that
    tags every span this thread opens inside it; ``None`` when off."""
    if not _enabled():
        return _NULL
    return _Batch()


def recorded() -> list:
    """The logged spans, oldest first: ``(name, start_ns, end_ns, ids)``."""
    with _lock:
        return list(_log)


def dropped() -> int:
    """Spans not logged because the log was full."""
    return _dropped


def clear() -> None:
    """Empty the log and its drop count (between two profiles of a
    long-running server, so that the log does not fill)."""
    global _dropped
    with _lock:
        _log.clear()
        _dropped = 0
