"""The engine's spans and its process-wide record of compiles.

Spans are ``jax.profiler.TraceAnnotation`` host spans.  A profiler session
records them on the ``/host:CPU`` plane, one line per host thread, on the
clock of the device's ``XLA Ops``; with no session running one costs a few
hundred nanoseconds.  Every engine span is named ``froid.<layer>[.<step>]``.

Importing the module registers one pair of ``jax.monitoring`` listeners.
They count JAX's own events into one process-wide record: tracing to a
jaxpr, lowering to MLIR, the backend compile (which covers a load from the
persistent compilation cache), that cache's hits and misses, and the
engine's own catalog loads (:data:`CATALOG_EVENT`).  The record covers the
compiles that happen lazily inside ``jax.jit`` on a first call, which no
session cache counter sees.  Timed events also keep their interval on
``time.perf_counter``'s clock, so :func:`busy_seconds` can say how long
the process spent compiling up to a given moment.  Importing the module
also makes op metadata part of the persistent compilation cache's key,
with source files named without their directory.
"""
from __future__ import annotations

import collections
import math
import threading
import time

import jax
from jax.profiler import TraceAnnotation

#: the duration event :meth:`repro.core.Session.create_table` records
CATALOG_EVENT = "/froid/catalog/create_table_duration"

#: the events counted, by the short name they are counted under
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    CATALOG_EVENT: "catalog",
}
#: the phases of compiling a program; a persistent cache load happens
#: inside ``backend_compile``, so ``cache_retrieval`` is not added to them
COMPILE = ("jaxpr_trace", "mlir_module", "backend_compile")

_lock = threading.Lock()
_counts = {name: [0, 0.0] for name in EVENTS.values()}   # [count, seconds]
#: (name, start, end) of the newest timed events, on perf_counter's clock
_intervals: collections.deque = collections.deque(maxlen=1 << 16)


def span(name: str, **attrs) -> TraceAnnotation:
    """A host span ``name`` (``froid.<layer>[.<step>]``) with ``attrs``,
    recorded only while a profiler session runs."""
    return TraceAnnotation(name, **attrs)


def totals() -> dict[str, tuple[int, float]]:
    """``{short name: (count, seconds)}`` of every event since import."""
    with _lock:
        return {k: (n, s) for k, (n, s) in _counts.items()}


def busy_seconds(names=COMPILE, until: float = math.inf) -> float:
    """Seconds before ``until`` (``time.perf_counter``) in which an event
    of ``names`` ran, overlaps counted once: nested traces and compiles on
    two threads at once are one interval."""
    with _lock:
        spans = sorted((s, min(e, until)) for n, s, e in _intervals
                       if n in names and s < until)
    total, reach = 0.0, -math.inf
    for s, e in spans:
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def _on_duration(event: str, duration_secs: float, **_) -> None:
    name = EVENTS.get(event)
    if name is None:
        return
    end = time.perf_counter()   # listeners run as the event ends
    with _lock:
        c = _counts[name]
        c[0] += 1
        c[1] += duration_secs
        _intervals.append((name, end - duration_secs, end))


def _on_event(event: str, **_) -> None:
    name = EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name][0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
# the device trace names each op's operator family from its op-name
# metadata, so an executable loaded from the persistent compilation cache
# must carry this build's metadata, not that of a build whose programs
# differ from it in metadata alone; source files enter that metadata by
# name only, so a checkout in another directory still finds its programs
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
if not jax.config.jax_hlo_source_file_canonicalization_regex:
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
