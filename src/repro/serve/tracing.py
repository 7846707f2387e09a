"""Spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it lands in the profiler's
own trace, on the same time base as the device's ops, and costs one
annotation object when no profiler session records.  There is no buffer,
no exporter and no setting: tracing is on exactly while a session records
(``jax.profiler.trace`` / ``start_trace``), and span arguments are computed
only then.
"""
from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation

recording = TraceAnnotation.is_enabled


def _values(args: dict) -> dict:
    return {k: v() if callable(v) else v for k, v in args.items()}


def span(name: str, **args) -> TraceAnnotation:
    """A span named ``name``; each argument is a value or a callable that
    gives one, called only while a session records."""
    if args and recording():
        return TraceAnnotation(name, **_values(args))
    return TraceAnnotation(name)


def note(sp: TraceAnnotation, **args) -> None:
    """Add arguments to an open span, as ``span`` takes them."""
    if recording():
        sp.set_metadata(**_values(args))


_gc_open: list[TraceAnnotation] = []


def _on_gc(phase: str, info: dict) -> None:
    """A ``host.gc`` span from each collection's start to its stop."""
    if phase == "start":
        if recording():
            sp = TraceAnnotation("host.gc", generation=info["generation"])
            sp.__enter__()
            _gc_open.append(sp)
    elif _gc_open:
        sp = _gc_open.pop()
        sp.set_metadata(collected=info["collected"])
        sp.__exit__(None, None, None)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
