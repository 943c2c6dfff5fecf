"""jepsen_tpu.obs — the flight recorder: span tracing + metrics.

Jepsen's own lineage ships observability as a first-class checker
(perf.clj's latency/rate graphs, timeline.clj's HTML timeline ride
next to the linearizability verdict); this package is that idea for
the reproduction's *own* machinery.  Two halves, one instrumentation
pass:

  * **spans** (:mod:`.trace`) — where did the wall-clock go: a
    zero-dep, thread-safe ``obs.span("fold", rows=128)`` context
    manager + ``@obs.traced()`` decorator recording into bounded
    per-run ring buffers, exported as Chrome-trace/Perfetto JSON
    (``store/<run>/trace.json``, ``python -m jepsen_tpu.obs trace``,
    the web run page's timeline panel).  Off by default; the CLI's
    ``--trace`` / ``JEPSEN_TPU_TRACE=1`` turns it on, and off means
    *near-zero* cost (one truthiness check per site).
  * **metrics** (:mod:`.metrics`) — what is the service doing right
    now: always-on counters/gauges/histograms (ops ingested, segments
    folded, forks spawned/capped, verdict- and kernel-cache hits,
    bucket padding, watchdog escalations, shed lines) served in
    Prometheus text from ``/metrics`` on the results web UI and the
    stream service, plus the ``/api/stats`` JSON snapshot the
    ``/campaigns`` grid polls.

:func:`log_ctx` is the third, small piece: a LoggerAdapter stamping
``run_id=``/``conn=`` fields onto log lines so a multiplexed-service
warning is attributable to the run that caused it.
"""

from __future__ import annotations

import logging

from . import metrics  # noqa: F401  (the registry half)
from . import telemetry  # noqa: F401  (the device-search aux block)
from .metrics import REGISTRY  # noqa: F401
from .trace import (DEFAULT_CAP, SpanRecorder, check_scope,  # noqa: F401
                    chrome_trace, current_run, drop_recorder, enable,
                    enabled, recorder, set_run, span, traced,
                    write_trace)


class _CtxAdapter(logging.LoggerAdapter):
    """Prefix every message with stable ``k=v`` context fields."""

    def process(self, msg, kwargs):
        ctx = " ".join(f"{k}={v}" for k, v in self.extra.items()
                       if v is not None)
        return (f"[{ctx}] {msg}" if ctx else msg), kwargs


def log_ctx(logger: logging.Logger, **fields) -> logging.LoggerAdapter:
    """``obs.log_ctx(log, run_id=r, conn=addr)`` — an adapter whose
    lines carry the run/connection context, so a warning out of a
    service multiplexing hundreds of runs names the one that failed."""
    return _CtxAdapter(logger, fields)
