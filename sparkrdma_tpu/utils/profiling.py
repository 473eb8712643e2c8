"""Profiling hooks — the tracing half of SURVEY.md §5's observability row.

The reference's observability is a per-remote-executor fetch-latency
histogram printed to the executor log (RdmaShuffleReaderStats, behind
``spark.shuffle.rdma.collectShuffleReadStats``) plus Spark's own metrics.
The TPU build keeps the histogram idea in :mod:`sparkrdma_tpu.utils.stats`
and adds what a compiled SPMD runtime can offer that a JVM plugin cannot:
XLA device traces. ``annotate`` and ``phase`` name host regions
(``shuffle:plan``, ``shuffle:exchange/dispatch``...) on the profiler's
own clock, so each idle gap of the device in a trace is attributable to
what the host was doing; the compiled programs name their device phases
with ``jax.named_scope("sr_*")``. Whoever profiles starts the profiler
(``jax.profiler.start_trace``); these hooks cost nothing when it is off.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Dict, Iterator


def annotate(name: str):
    """Named sub-region annotation visible in the device trace timeline."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def annotate_span(phase: str, span_id: int = 0):
    """Phase annotation carrying the exchange-journal span id.

    Emits ``plan#s42`` instead of ``plan`` so a region in the XProf
    timeline and a line in the JSON-lines journal (which records the
    same ``span_id``) identify the same exchange. Falls back to the
    plain phase name when no span id is in flight (journal disabled).
    """
    return annotate(f"{phase}#s{span_id}" if span_id else phase)


def device_phase(name: str) -> Callable[[Callable], Callable]:
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    every op it emits into a compiled program carries ``name`` in its
    metadata (the ``tf_op`` stat of a profiler trace). Op metadata only:
    the compiled program runs the same. A fresh scope per call, since a
    scope object keeps state while entered."""

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


@contextlib.contextmanager
def phase(name: str, timeline=None, **extras) -> Iterator[Dict]:
    """One named phase of the host code, named once for both records:
    a TraceAnnotation ``name`` in the profiler's trace and, when
    ``timeline`` (an :class:`~sparkrdma_tpu.obs.timeline.EventTimeline`)
    is enabled, its begin/end pair under the same name for the journal.

    ``extras`` ride the begin event; the dict yielded collects extras
    for the end event (known only once the phase has run)."""
    at_end: Dict = {}
    with annotate(name):
        if timeline is None or not timeline.enabled:
            yield at_end
            return
        timeline.begin(name, **extras)
        try:
            yield at_end
        finally:
            timeline.end(name, **at_end)


__all__ = ["annotate", "annotate_span", "device_phase", "phase"]
