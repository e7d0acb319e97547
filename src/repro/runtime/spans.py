"""Host spans on the profiler's timeline.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: it records
only while a JAX profiler runs, on the profiler's clock, so program spans
and device ops share one timeline.  It keeps no log of its own and writes
no file; the profile the caller asked for holds it, with ``args`` as the
event's stats.  With no profiler running it costs one ``TraceMe``
construction.
"""
from __future__ import annotations

import jax


def span(name: str, **args):
    """A context manager that marks ``name`` (with ``args``) in the trace."""
    return jax.profiler.TraceAnnotation(name, **args)
