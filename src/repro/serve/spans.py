"""The serving layer's trace spans: every name in one place.

Each span is a ``jax.profiler.TraceAnnotation``, written on the
profiler's own host clock, the clock the device trace is put on.  With
no profiler running a span is an object made and dropped: the names are
constants, and metadata (a request id, page counts) is encoded only
while a profiler runs.  The per-tick spans (``serve.step``,
``serve.sync`` and its children) carry no metadata at all.

Nesting, as :class:`~repro.serve.engine.BatchedEngine` opens them::

    serve.admit
        serve.admit.reap            leading sync + releasing done pages
        serve.admit.prefill  x n    prompt upload + prefill dispatch (rid)
        serve.admit.scatter  x n    the prefill cache into the slot (rid,
                                    fresh_pages, shared_pages)
        serve.admit.state    x n    the prefill's final recurrent state
                                    into the slot (rid; models with one)
        serve.admit.first_tokens    the one fetch + tick-state updates
    serve.step                      the tick dispatch
    serve.sync
        serve.sync.fetch            the one device_get
        serve.sync.apply            the harvest into the requests
"""
from __future__ import annotations

import jax

ADMIT = "serve.admit"
ADMIT_REAP = "serve.admit.reap"
ADMIT_PREFILL = "serve.admit.prefill"
ADMIT_SCATTER = "serve.admit.scatter"
ADMIT_STATE = "serve.admit.state"
ADMIT_FIRST_TOKENS = "serve.admit.first_tokens"
STEP = "serve.step"
SYNC = "serve.sync"
SYNC_FETCH = "serve.sync.fetch"
SYNC_APPLY = "serve.sync.apply"


def span(name: str, **meta):
    """A context manager timing ``name`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(name, **meta)
