"""Named host spans of the scan path, written into the JAX profiler's trace.

``span(name)`` returns a context manager.  Where ``jax`` is already
imported it is ``jax.profiler.TraceAnnotation(name)``: a TraceMe event on
the profiler's host plane, one line per host thread, on the clock of the
device planes, so a span can be set beside the device's busy and idle
time.  Where no module has imported ``jax``, no profiler can be recording,
and it is one shared ``nullcontext``; this module never imports ``jax``
itself, so the host-only modules (storage, file format, dataset) that
call it stay free of it.

With no profiler recording, a span costs one ``with`` statement.  Names
are static strings, ``repro.<layer>.<stage>``; spans sit at row-group,
column-chunk, buffer or kernel-call granularity, never per row or page.
"""

from __future__ import annotations

import contextlib
import sys

_OFF = contextlib.nullcontext()


def span(name: str):
    """A context manager that marks its body as host span ``name``."""
    # getattr: a thread may get here while another is still importing jax
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return _OFF
    return profiler.TraceAnnotation(name)
