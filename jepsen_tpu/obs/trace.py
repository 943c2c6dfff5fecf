"""Span tracing — the flight recorder's timeline half.

A *span* is one timed region of work (a bucket's device dispatch, a
streamed segment fold, a campaign cell's workload phase) recorded as a
plain dict into a bounded per-run ring buffer.  The API is two
primitives:

  * :func:`span` — a context manager: ``with obs.span("fold",
    run="r1", rows=128): ...`` records begin/end/attrs; when tracing
    is off it returns a shared no-op object, so an instrumented hot
    path costs one truthiness check and nothing else.
  * :func:`traced` — the decorator form for whole functions.

Spans attribute to a *run*: either the explicit ``run=`` argument (the
stream service multiplexes many runs in one process) or the
process-wide current run (:func:`set_run`, set by ``core.run`` for the
single-run case so deep instrumentation — bucket scheduler, decomposed
engine — lands in the right buffer without threading ids through every
call).  Each run gets its own :class:`SpanRecorder` ring buffer, so a
long fleet process never grows without bound: old spans fall off the
back, a finished run's buffer is dropped after export.

Export is Chrome-trace JSON (the ``traceEvents`` array of ``"X"``
complete events, microsecond timestamps) — loadable directly in
Perfetto / ``chrome://tracing`` — via :func:`chrome_trace` /
:func:`write_trace`.  ``core.run`` writes ``store/<run>/trace.json``
when tracing is on; ``python -m jepsen_tpu.obs trace <run>`` re-emits
it and ``tools/trace_report.py`` folds it into a phase-time table.

Each recorded span carries an ``id``, the ``parent`` span open on its
thread when it began, and the ``check`` it belongs to: an integer the
outermost checker call opens with :func:`check_scope` and holds in a
contextvar, so a thread started with a copied context (the bucket prep
thread) attributes its spans to the same check.  The Chrome export
carries all three in ``args``.

Zero dependencies; threads are first-class (the recorder appends are
atomic, thread names become Perfetto track names).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque

#: process epoch every span timestamp is relative to (microseconds
#: since this module imported) — Chrome trace wants a shared monotonic
#: microsecond clock, not wall time
_EPOCH = time.perf_counter()

#: default ring-buffer capacity (spans per run).  A span dict is a few
#: hundred bytes, so the default bounds a run's recorder at ~tens of MB
#: even under per-op tracing.
DEFAULT_CAP = 65536

_TRUTHY = ("1", "true", "on", "yes")

#: module override (tests, programmatic enable); None = follow the env
_forced: bool | None = None
#: the env knob, read ONCE: ``enabled()`` sits on per-op hot paths
#: (span per client op, per fold, per slice), and an ``os.environ``
#: lookup plus ``.strip().lower()`` allocates two strings per call —
#: with tracing OFF that was the single biggest per-site cost.  The
#: cached flag makes the off path allocation-free: one function call,
#: two attribute reads, the shared ``_NOOP`` return.  Processes that
#: flip the env var mid-run must call ``enable(True)`` /
#: ``enable(None)`` to apply / re-read it — the CLI's ``--trace``
#: handler does exactly that for its own process.
_env_on: bool | None = None
#: serializes the one-time env read against concurrent first callers
#: (worker/prep/reaper threads all hit ``enabled()`` on their hot
#: paths); the hot path itself stays lock-free — double-checked
#: locking, sound here because the GIL makes the ``_env_on`` load
#: atomic and the value is computed idempotently from the env
_knob_lock = threading.Lock()

#: span and check ids, process-wide (``next`` on a count is atomic
#: under the GIL)
_span_ids = itertools.count(1)
_check_ids = itertools.count(1)
#: per thread, the ids of the spans open on it, innermost last
_open = threading.local()
#: the check the current context works for (see :func:`check_scope`)
_CHECK: contextvars.ContextVar = contextvars.ContextVar(
    "jepsen_tpu_check", default=None)
#: `SpanRecorder.record`'s default parent and check: what is open on
#: the recording thread
_ON_THREAD = object()


def _open_stack() -> list:
    st = getattr(_open, "stack", None)
    if st is None:
        st = _open.stack = []
    return st


def enabled() -> bool:
    """Is tracing on?  ``JEPSEN_TPU_TRACE=1`` (the CLI's ``--trace``)
    or a programmatic :func:`enable`.  The env knob is cached after
    the first read (see ``_env_on``)."""
    global _env_on
    if _forced is not None:
        return _forced
    if _env_on is None:
        with _knob_lock:
            if _env_on is None:
                _env_on = os.environ.get(
                    "JEPSEN_TPU_TRACE", "").strip().lower() in _TRUTHY
    return _env_on


def enable(on: bool | None = True) -> None:
    """Force tracing on/off for this process (``None`` reverts to the
    env knob, re-read on next use) — the tests' and REPL's switch."""
    global _forced, _env_on
    with _knob_lock:
        if on is None:
            # clear the cache BEFORE dropping the force: a concurrent
            # enabled() must not see the stale cached knob with the
            # force already gone
            _env_on = None
        _forced = on


# ---------------------------------------------------------------------------
# recorders
# ---------------------------------------------------------------------------


class SpanRecorder:
    """A bounded ring buffer of finished spans for one run.

    Appends are ``deque.append`` on a ``maxlen`` deque — atomic under
    the GIL, so worker threads, the bucket prep thread, and the stream
    fold thread all record without a lock on the hot path."""

    def __init__(self, run: str | None = None, cap: int = DEFAULT_CAP):
        self.run = run
        self.cap = cap
        self._spans: deque = deque(maxlen=cap)
        self.dropped = 0  # spans pushed off the back, lifetime

    def __len__(self) -> int:
        return len(self._spans)

    def record(self, name: str, cat: str, t0: float, t1: float,
               args: dict | None = None, *, sid: int | None = None,
               parent=_ON_THREAD, check=_ON_THREAD) -> None:
        """Record one finished span; ``t0``/``t1`` are
        ``time.perf_counter()`` readings.  Without ``sid`` the span
        takes a fresh id; without ``parent``, the span open on this
        thread; without ``check``, the current check."""
        if sid is None:
            sid = next(_span_ids)
        if parent is _ON_THREAD:
            st = _open_stack()
            parent = st[-1] if st else None
        if check is _ON_THREAD:
            check = _CHECK.get()
        if len(self._spans) == self.cap:
            self.dropped += 1
        self._spans.append({
            "name": name, "cat": cat,
            "ts": round((t0 - _EPOCH) * 1e6, 1),
            "dur": round((t1 - t0) * 1e6, 1),
            "tid": threading.current_thread().name,
            "args": args or {},
            "id": sid, "parent": parent, "check": check,
        })

    def spans(self) -> list[dict]:
        return list(self._spans)

    def clear(self) -> None:
        self._spans.clear()

    def chrome_trace(self) -> dict:
        """The Chrome-trace / Perfetto JSON object: ``"X"`` complete
        events plus thread-name metadata so tracks are labelled."""
        pid = os.getpid()
        tids: dict[str, int] = {}
        events = []
        for s in self.spans():
            tid = tids.setdefault(s["tid"], len(tids) + 1)
            events.append({"name": s["name"], "cat": s["cat"],
                           "ph": "X", "ts": s["ts"], "dur": s["dur"],
                           "pid": pid, "tid": tid,
                           "args": {**s["args"], "id": s.get("id"),
                                    "parent": s.get("parent"),
                                    "check": s.get("check")}})
        meta = [{"name": "thread_name", "ph": "M", "pid": pid,
                 "tid": t, "args": {"name": n}}
                for n, t in tids.items()]
        if self.run is not None:
            meta.append({"name": "process_name", "ph": "M", "pid": pid,
                         "tid": 0, "args": {"name": str(self.run)}})
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "otherData": {"run": self.run,
                              "dropped_spans": self.dropped}}


_recorders: dict = {}
_recorders_lock = threading.Lock()
_current_run: str | None = None


def recorder(run: str | None = None) -> SpanRecorder:
    """The (created-on-demand) recorder for ``run`` — ``None`` is the
    process-default buffer for spans outside any run."""
    rec = _recorders.get(run)
    if rec is None:
        with _recorders_lock:
            rec = _recorders.setdefault(run, SpanRecorder(run))
    return rec


def set_run(run: str | None) -> None:
    """Set the process-wide current run: spans with no explicit
    ``run=`` attribute to it.  ``core.run`` sets this for the duration
    of a test; services that multiplex runs pass ``run=`` explicitly
    instead."""
    global _current_run
    _current_run = run


def current_run() -> str | None:
    return _current_run


def drop_recorder(run: str | None) -> None:
    """Forget a finished run's buffer (after export) so a long fleet
    process doesn't accumulate one ring buffer per run forever."""
    with _recorders_lock:
        _recorders.pop(run, None)


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------


class _Noop:
    """The shared do-nothing span: tracing off costs one call + one
    truthiness check, allocates nothing."""

    __slots__ = ()
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "cat", "run", "args", "_t0", "id", "parent",
                 "check")

    def __init__(self, name: str, cat: str, run: str | None,
                 args: dict | None):
        self.name = name
        self.cat = cat
        self.run = run
        self.args = args

    def set(self, **attrs) -> None:
        """Add attrs known only once the work has run (a verdict, a
        count); they land in the recorded span's args."""
        self.args = {**(self.args or {}), **attrs}

    def __enter__(self):
        self.id = next(_span_ids)
        self.check = _CHECK.get()
        st = _open_stack()
        self.parent = st[-1] if st else None
        st.append(self.id)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        st = _open_stack()
        if st and st[-1] == self.id:
            st.pop()
        elif self.id in st:
            st.remove(self.id)
        args = self.args
        if exc_type is not None:
            args = dict(args or {})
            args["error"] = exc_type.__name__
        run = self.run if self.run is not None else _current_run
        try:
            recorder(run).record(self.name, self.cat, self._t0, t1, args,
                                 sid=self.id, parent=self.parent,
                                 check=self.check)
        except Exception:  # pragma: no cover — the recorder must never
            pass           # take down the instrumented code
        return False


def span(name: str, *, cat: str = "span", run: str | None = None,
         **attrs):
    """``with obs.span("fold", run=..., rows=128): ...`` — no-op when
    tracing is off."""
    if not enabled():
        return _NOOP
    return _Span(name, cat, run, attrs or None)


class _CheckScope:
    __slots__ = ("_token",)

    def __enter__(self):
        self._token = _CHECK.set(next(_check_ids))
        return self

    def __exit__(self, *exc):
        _CHECK.reset(self._token)
        return False


def check_scope():
    """``with obs.check_scope(): ...`` around a checker call: the spans
    recorded inside carry one fresh ``check`` id.  Only the outermost
    call opens one (a nested checker's spans belong to the caller's
    check); no-op when tracing is off."""
    if not enabled() or _CHECK.get() is not None:
        return _NOOP
    return _CheckScope()


def traced(name: str | None = None, *, cat: str = "span"):
    """Decorator form: ``@obs.traced()`` / ``@obs.traced("prep",
    cat="host")`` wraps the call in a span named after the function."""
    import functools

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not enabled():
                return fn(*a, **kw)
            with _Span(label, cat, None, None):
                return fn(*a, **kw)

        return wrapper

    return deco


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------


def chrome_trace(run: str | None = None) -> dict:
    """The Chrome-trace JSON for one run's recorder (``None`` = the
    default buffer)."""
    return recorder(run).chrome_trace()


def write_trace(path: str, run: str | None = None) -> str:
    """Write ``run``'s Chrome trace to ``path`` (atomically — a live
    web UI may be reading the previous snapshot); returns the path."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(chrome_trace(run), f)
    os.replace(tmp, path)
    return path
