"""Host spans the served path opens in the profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation``: while a profiler session
runs (``jax.profiler.trace(dir)``, or ``start_trace`` / ``stop_trace``) it
lands on the host line of the thread that opened it, on the same clock as
the device's operations; with no session it costs about a microsecond.
Spans take no keyword arguments: they would cost more with the profiler off,
and a trace reduction that reads names only drops them anyway.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SPANS", "span"]

SPANS = (
    # Engine flush phases (runtime/engine.py), timed by EngineStats.phase.
    "mole.flush.coalesce",  # begin_flush: pending rows into work items
    "mole.flush.device",    # execute_flush: dispatch, waits and fetches below
    "mole.flush.dispatch",  # uploads and calls into the jitted steps, all items
    "mole.flush.wait",      # one item: block until its device output is ready
    "mole.flush.fetch",     # one item: copy its ready output to the host
    "mole.flush.publish",   # publish_flush: scatter results into requests
    # The async flusher thread (runtime/async_engine.py).
    "mole.flusher.idle",    # waiting for rows or a deadline: no flush is due
    "mole.flush.resolve",   # resolving the round's futures and callbacks
    # The network front door (launch/server.py), on the event-loop thread.
    "mole.server.decode",   # wire.decode_request of one request frame
    "mole.server.encode",   # wire.encode_result of one result frame
    "mole.server.write",    # handing one response frame to the socket writer
)


def span(name: str) -> TraceAnnotation:
    """A host span named ``name``, one of :data:`SPANS`, as a context
    manager."""
    return TraceAnnotation(name)
