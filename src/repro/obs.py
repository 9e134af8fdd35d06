"""Named host spans of the program, on JAX's own sinks.

``span(name)`` marks one host interval twice: a
``jax.profiler.TraceAnnotation`` puts it on the profiler's clock beside the
device operations, and on exit ``jax.monitoring.record_event_time_span``
hands ``"/grafs/" + name`` with its start and end (``time.time`` seconds,
the clock of JAX's own time-span events) to every registered listener.
With no profiler running and no listener registered each costs a few
microseconds.  The module keeps no state: the profiler and the listeners
are the readers.

Device work is named at trace time instead, with ``jax.named_scope``
(``grafs.slot_gather``, ``grafs.slot_scatter``, ``grafs.tile_activity``,
``grafs.res_activity``, ``grafs.merge``) and the Pallas kernels' own names
(``grafs_pull_sweep``, ``grafs_push_sweep``, ``grafs_resolve``); DESIGN.md
§16 lists every span and scope.
"""
from __future__ import annotations

import contextlib
import time

import jax

EVENT_PREFIX = "/grafs/"


@contextlib.contextmanager
def span(name: str):
    """Mark the enclosed host work as the span ``name``."""
    with jax.profiler.TraceAnnotation(name):
        start = time.time()
        try:
            yield
        finally:
            jax.monitoring.record_event_time_span(EVENT_PREFIX + name, start,
                                                  time.time())
