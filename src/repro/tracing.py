"""Profiler spans of the served query path.

A span is a :class:`jax.profiler.TraceAnnotation`, so it lands in the
profiler's host planes on the same clock as the device's ops.  The
profiler session is the only switch: off a session a span costs well
under a microsecond and its arguments are never built.  Arguments go to
the event's stats, never into its name, so the names below stay fixed
for whoever reads a trace.

Spans and the thread that opens them:

  ``copr.serve.queue``  client: ``submit`` until a wave worker takes
                        the ticket's wave (args ``query``, ``wave``)
  ``copr.serve.wave``   client: wave taken until the candidates are
                        back on the client (``query``, ``wave``)
  ``copr.postfilter``   client: the whole exact post-filter of one
                        answer
  ``copr.postfilter.decompress``  client: one batch-LRU miss, zstd
                        decompress and lower-case of one batch into
                        one byte string (``ascii``: 1 where the batch
                        is ASCII and lowered as bytes, else 0)
  ``copr.postfilter.retokenize``  client: term mode, re-tokenizing one
                        answer's substring hits in one pass
  ``copr.wave``         wave worker: pick-up until every ticket of the
                        wave is completed (``wave``)
  ``copr.wave.sync``    wave worker: one device-to-host wait
                        (``what``: ``counts`` or ``ids``)

The post-filter spans of a served answer carry its ``query`` and
``wave`` through :func:`request`, which ``StoreServer`` opens around the
post-filter on the client's thread.

The device programs are named too: ``copr_probe`` (per segment, and the
sharded wave), ``copr_reduce`` and ``copr_extract`` are the jitted
functions' names, so their host events read ``PjitFunction(copr_*)``
and their device modules ``jit_copr_*``.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

SERVE_QUEUE = "copr.serve.queue"
SERVE_WAVE = "copr.serve.wave"
POSTFILTER = "copr.postfilter"
POSTFILTER_DECOMPRESS = "copr.postfilter.decompress"
POSTFILTER_RETOKENIZE = "copr.postfilter.retokenize"
WAVE = "copr.wave"
WAVE_SYNC = "copr.wave.sync"

_request = threading.local()


def enabled() -> bool:
    """True inside a profiler session."""
    return TraceAnnotation.is_enabled()


def span(name: str, **args) -> TraceAnnotation:
    """A span named ``name``.  Inside a profiler session its stats are
    ``args`` and the ids of the thread's current :func:`request`."""
    if not TraceAnnotation.is_enabled():
        return TraceAnnotation(name)
    return TraceAnnotation(name, **getattr(_request, "args", {}), **args)


@contextmanager
def request(**args):
    """Tag the spans this thread opens in the body with ``args`` (a
    served answer's ``query`` and ``wave``)."""
    outer = getattr(_request, "args", None)
    _request.args = args
    try:
        yield
    finally:
        if outer is None:
            del _request.args
        else:
            _request.args = outer
