"""Zero-dependency tracing + metrics for the compile/exec/serve stack.

The paper's headline claims are *phase* claims — SNG cycles vs. computation
cycles vs. readout (Table 8) — so the reproduction needs a way to attribute
wall-clock the same way: where did a served request's 40 ms go across
admission, batching, stream generation, and pass execution?  This module is
that window.  It is deliberately dependency-free (no jax import) so every
layer from ``compiler/pipeline.py`` down to ``serve/sc_engine.py`` can use
it without cycles.

Three pieces:

* ``Trace`` — an in-memory span collector.  ``trace.span(name, **attrs)``
  is a context manager producing nested spans with monotonic timestamps;
  nesting is tracked per thread (a thread-local stack on the trace), so one
  ``Trace`` can be shared across worker threads and each thread gets its
  own correct parent chain.  ``trace.add_span(...)`` records a span
  retroactively from timestamps stamped earlier (the serve engine uses this
  to emit a request's queued/staged/inflight phases at reap time), and
  ``trace.event(...)`` records instant events (retry, quarantine, shed).
  Exporters: ``to_chrome_json()`` (load in chrome://tracing or Perfetto)
  and ``summary()`` (flat per-span-name totals).  Each live span is also
  entered as a ``jax.profiler.TraceAnnotation`` of the same name, so that
  a running ``jax.profiler`` session records it on the device trace's
  clock (and nothing is recorded when none runs).

* ``MetricsRegistry`` — named counters / gauges / histograms behind one
  lock.  Every ``Trace`` owns one (``trace.metrics``); a process-wide
  ``REGISTRY`` exists for code with no trace in hand.

* A current-trace context: ``tracing(trace)`` sets a contextvar for the
  dynamic extent of a block, ``install(trace)`` sets a process-wide
  fallback (what ``REPRO_TRACE=1`` does at import), and ``span(...)`` /
  ``event(...)`` module-level helpers no-op cheaply when neither is set —
  the disabled path is one contextvar read, so instrumented hot paths cost
  nothing measurable when tracing is off.  ``tracing(trace, run=n)`` also
  names the ``run()`` call that caused the block's spans: each span then
  carries ``run=n``, as an attribute and as the annotation's argument
  (``name#run=n#`` in the profiler trace), so one frame's spans share it.

Example::

    from repro.core import obs
    tr = obs.Trace("demo")
    with obs.tracing(tr):
        with obs.span("outer", step=1):
            with obs.span("inner"):
                pass
    print(tr.summary()["spans"]["outer"]["count"])  # 1
    open("/tmp/trace.json", "w").write(tr.to_chrome_json())
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator

__all__ = [
    "Span", "Trace", "MetricsRegistry", "REGISTRY",
    "current_trace", "tracing", "install", "span", "event", "span_on",
]


class Span:
    """One timed region: ``name``, perf_counter start/end, attrs, parent.

    ``tid`` is the chrome-trace track the span renders on — the recording
    thread's ident for live spans, or a virtual track id for retroactive
    spans (the serve engine gives each request its own track so its
    queued → staged → inflight children nest visibly).
    """

    __slots__ = ("name", "t0", "t1", "tid", "parent", "attrs")

    def __init__(self, name: str, t0: float, t1: "float | None",
                 tid: int, parent: "Span | None", attrs: dict):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.parent = parent
        self.attrs = attrs

    @property
    def duration_ms(self) -> float:
        return 0.0 if self.t1 is None else (self.t1 - self.t0) * 1e3

    def set(self, key: str, value: Any) -> None:
        """Attach/overwrite an attribute while the span is open."""
        self.attrs[key] = value

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"Span({self.name!r}, {self.duration_ms:.3f} ms)"


class _NullSpan:
    """Inert stand-in returned by ``span(...)`` when tracing is disabled."""

    __slots__ = ()
    name = "<disabled>"
    attrs: dict = {}
    duration_ms = 0.0

    def set(self, key: str, value: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


class Counter:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = v


class Histogram:
    """Bounded-sample histogram: exact count/sum, percentiles from the
    most recent ``cap`` observations (enough for latency distributions)."""

    __slots__ = ("count", "total", "vmin", "vmax", "_samples", "_cap")

    def __init__(self, cap: int = 4096):
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self._samples: list[float] = []
        self._cap = cap

    def observe(self, v: float) -> None:
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        if len(self._samples) >= self._cap:
            self._samples.pop(0)
        self._samples.append(v)

    def percentile(self, q: float) -> float:
        if not self._samples:
            return 0.0
        s = sorted(self._samples)
        return s[min(len(s) - 1, int(q * len(s)))]

    def as_dict(self) -> dict:
        if not self.count:
            return {"count": 0}
        return {"count": self.count,
                "sum": round(self.total, 6),
                "mean": round(self.total / self.count, 6),
                "min": round(self.vmin, 6), "max": round(self.vmax, 6),
                "p50": round(self.percentile(0.50), 6),
                "p99": round(self.percentile(0.99), 6)}


class MetricsRegistry:
    """Process- or trace-scoped named counters/gauges/histograms.

    Accessors create on first use; all mutation goes through one lock, so
    the registry is safe to share across the server's caller threads.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._hists: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter()
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge()
            return g

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            return h

    def inc(self, name: str, n: int = 1) -> None:
        self.counter(name).inc(n)

    def observe(self, name: str, v: float) -> None:
        self.histogram(name).observe(v)

    def snapshot(self) -> dict:
        """Point-in-time dict: ``{"counters": {...}, "gauges": {...},
        "histograms": {name: {count/sum/mean/min/max/p50/p99}}}``."""
        with self._lock:
            return {
                "counters": {k: c.value for k, c in self._counters.items()},
                "gauges": {k: g.value for k, g in self._gauges.items()},
                "histograms": {k: h.as_dict() for k, h in self._hists.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


#: Process-wide registry for call sites with no Trace in hand.
REGISTRY = MetricsRegistry()


#: ``jax.profiler.TraceAnnotation``, looked up on the first live span so
#: that importing this module imports no jax.
_Annotation: Any = None


def _annotation_class():
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation
        _Annotation = TraceAnnotation
    return _Annotation


class Trace:
    """An in-memory collection of spans + instant events + metrics.

    Safe to share across threads: completed spans append under a lock, and
    the open-span stack used for parent inference is thread-local, so spans
    opened on different threads never corrupt each other's nesting.
    """

    def __init__(self, name: str = "trace"):
        self.name = name
        self.metrics = MetricsRegistry()
        self.t_origin = time.perf_counter()
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        self._events: list[dict] = []
        self._tls = threading.local()
        self._vtids: dict[str, int] = {}

    # -- recording ---------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Span]:
        """Open a live nested span; closed (and recorded) on exit.

        The span is also a profiler annotation of the same name, with the
        current ``run`` id as its argument when one is set."""
        run = _run.get()
        if run is not None:
            attrs["run"] = run
        cls = _annotation_class()
        ann = cls(name) if run is None else cls(name, run=run)
        st = self._stack()
        sp = Span(name, time.perf_counter(), None, threading.get_ident(),
                  st[-1] if st else None, attrs)
        st.append(sp)
        try:
            with ann:
                yield sp
        finally:
            st.pop()
            sp.t1 = time.perf_counter()
            with self._lock:
                self._spans.append(sp)

    def add_span(self, name: str, t0: float, t1: float, *,
                 parent: "Span | None" = None, tid: "int | None" = None,
                 **attrs: Any) -> Span:
        """Record a span retroactively from perf_counter timestamps.

        Used where the interesting interval was stamped earlier than it can
        be attributed (the serve engine stamps admission/stage/launch times
        on the pending request and emits the spans at reap).  Pass the
        returned span as ``parent=`` to nest children under it.
        """
        sp = Span(name, t0, t1, threading.get_ident() if tid is None else tid,
                  parent, attrs)
        with self._lock:
            self._spans.append(sp)
        return sp

    def event(self, name: str, *, t: "float | None" = None,
              tid: "int | None" = None, **attrs: Any) -> None:
        """Record an instant event (chrome-trace ``ph: "i"``)."""
        ev = {"name": name,
              "t": time.perf_counter() if t is None else t,
              "tid": threading.get_ident() if tid is None else tid,
              "attrs": attrs}
        with self._lock:
            self._events.append(ev)

    def virtual_tid(self, label: str) -> int:
        """Stable synthetic track id for ``label`` (named in the export).

        Virtual tracks keep overlapping retroactive spans (e.g. concurrent
        requests) from stacking on one thread's row in chrome://tracing.
        """
        with self._lock:
            tid = self._vtids.get(label)
            if tid is None:
                tid = self._vtids[label] = 1_000_000 + len(self._vtids)
            return tid

    # -- inspection --------------------------------------------------------

    def spans(self) -> "list[Span]":
        with self._lock:
            return list(self._spans)

    def events(self) -> "list[dict]":
        with self._lock:
            return list(self._events)

    # -- exporters ---------------------------------------------------------

    def to_chrome_json(self, indent: "int | None" = None) -> str:
        """Serialize to the chrome://tracing / Perfetto JSON array format.

        Spans become complete (``"ph": "X"``) events with microsecond
        ``ts``/``dur`` relative to trace creation; instant events become
        ``"ph": "i"``; virtual tracks get ``thread_name`` metadata so the
        viewer labels them.
        """
        pid = os.getpid()
        out: list[dict] = [{"name": "process_name", "ph": "M", "pid": pid,
                            "tid": 0, "args": {"name": self.name}}]
        with self._lock:
            spans, events = list(self._spans), list(self._events)
            vtids = dict(self._vtids)
        for label, tid in sorted(vtids.items(), key=lambda kv: kv[1]):
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": label}})
        for sp in spans:
            t1 = sp.t1 if sp.t1 is not None else sp.t0
            out.append({"name": sp.name, "ph": "X", "pid": pid, "tid": sp.tid,
                        "ts": round((sp.t0 - self.t_origin) * 1e6, 3),
                        "dur": round((t1 - sp.t0) * 1e6, 3),
                        "args": _jsonable(sp.attrs)})
        for ev in events:
            out.append({"name": ev["name"], "ph": "i", "s": "t", "pid": pid,
                        "tid": ev["tid"],
                        "ts": round((ev["t"] - self.t_origin) * 1e6, 3),
                        "args": _jsonable(ev["attrs"])})
        return json.dumps({"traceEvents": out, "displayTimeUnit": "ms"},
                          indent=indent)

    def summary(self) -> dict:
        """Flat aggregation: per-span-name count/total/mean/max ms, event
        counts, and the trace's metrics snapshot."""
        spans, events = self.spans(), self.events()
        agg: dict[str, dict] = {}
        for sp in spans:
            a = agg.setdefault(sp.name, {"count": 0, "total_ms": 0.0,
                                         "max_ms": 0.0})
            a["count"] += 1
            a["total_ms"] += sp.duration_ms
            a["max_ms"] = max(a["max_ms"], sp.duration_ms)
        for a in agg.values():
            a["mean_ms"] = round(a["total_ms"] / a["count"], 4)
            a["total_ms"] = round(a["total_ms"], 4)
            a["max_ms"] = round(a["max_ms"], 4)
        ev_counts: dict[str, int] = {}
        for ev in events:
            ev_counts[ev["name"]] = ev_counts.get(ev["name"], 0) + 1
        end = max([sp.t1 or sp.t0 for sp in spans]
                  + [ev["t"] for ev in events] + [self.t_origin])
        return {"name": self.name,
                "wall_ms": round((end - self.t_origin) * 1e3, 4),
                "n_spans": len(spans), "n_events": len(events),
                "spans": agg, "events": ev_counts,
                "metrics": self.metrics.snapshot()}


def _jsonable(attrs: dict) -> dict:
    return {k: (v if isinstance(v, (int, float, str, bool, type(None)))
                else str(v))
            for k, v in attrs.items()}


# -- current-trace context -------------------------------------------------

_current: "contextvars.ContextVar[Trace | None]" = contextvars.ContextVar(
    "repro_obs_trace", default=None)
_installed: "Trace | None" = None
_run: "contextvars.ContextVar[int | None]" = contextvars.ContextVar(
    "repro_obs_run", default=None)


def current_trace() -> "Trace | None":
    """The active trace: context-local if set, else the installed global."""
    tr = _current.get()
    return tr if tr is not None else _installed


def install(trace: "Trace | None") -> "Trace | None":
    """Set (or clear, with None) the process-wide fallback trace.

    Unlike the contextvar set by :func:`tracing`, the installed trace is
    visible from *every* thread — which is what lets ``REPRO_TRACE=1``
    capture spans from server caller threads without plumbing.
    """
    global _installed
    _installed = trace
    return trace


@contextmanager
def tracing(trace: Trace, run: "int | None" = None) -> Iterator[Trace]:
    """Make ``trace`` the current trace for the dynamic extent of a block;
    ``run`` (the id of the ``run()`` call behind the block) tags every span
    opened in it."""
    token = _current.set(trace)
    run_token = _run.set(run)
    try:
        yield trace
    finally:
        _run.reset(run_token)
        _current.reset(token)


def span(name: str, **attrs: Any):
    """Span on the current trace, or an inert no-op when tracing is off."""
    tr = current_trace()
    if tr is None:
        return NULL_SPAN
    return tr.span(name, **attrs)


def event(name: str, **attrs: Any) -> None:
    """Instant event on the current trace; no-op when tracing is off."""
    tr = current_trace()
    if tr is not None:
        tr.event(name, **attrs)


def span_on(trace: "Trace | None", name: str, **attrs: Any):
    """Span on an explicit trace handle (None → no-op) — for call sites
    like the serve engine that hold their own trace reference."""
    if trace is None:
        return NULL_SPAN
    return trace.span(name, **attrs)


if os.environ.get("REPRO_TRACE", "") not in ("", "0"):
    install(Trace("REPRO_TRACE"))
