"""Tracing/profiling hooks (SURVEY §5: absent in the reference; optional
here).

Thin wrappers over the JAX profiler so traces can be captured on any task
and inspected with Perfetto/TensorBoard.  Enable globally by exporting
``TPUMESOS_TRACE_DIR`` — the trainer and node runtime leave these off by
default (profiling is opt-in; it perturbs step timing).

:func:`annotate` is what ``ContinuousBatcher`` opens every phase of a
serve-loop tick with (``batcher.pull``, ``batcher.admit``, ... each with
its ``tick`` number; docs/SERVING.md "Observability"): a trace taken here
or by the benchmark shows what the host was doing in every gap between
device programs.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Iterator, Optional

TRACE_DIR_ENV = "TPUMESOS_TRACE_DIR"


@contextmanager
def trace(logdir: Optional[str] = None) -> Iterator[Optional[str]]:
    """Capture a profiler trace for the enclosed block.

    Yields the trace directory, or None (block still runs, untraced) when no
    directory is configured — so call sites can wrap unconditionally.
    """
    logdir = logdir or os.environ.get(TRACE_DIR_ENV)
    if not logdir:
        yield None
        return
    import jax
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **stats):
    """Named region on the calling thread's host line of a profiler
    trace, on the device planes' clock; ``stats`` (``tick=17``) ride
    the event.  Outside a profiler session entering one is a flag test."""
    import jax
    return jax.profiler.TraceAnnotation(name, **stats)
