"""Span tracing for the host side of the serving stack, on the
profiler's clock.

``jax.profiler`` shows where DEVICE time goes; this module names what
the HOST was doing meanwhile: a lightweight ``Tracer`` producing NESTED
spans (``eval_tpu`` > ``eval_tpu.decode`` / ``.dispatch`` / ``.fetch``;
``submit`` > ``admit`` / ``pack`` / ``backpressure`` / ``dispatch``,
``wait``, ``decode``; the router's ``route`` / ``retry`` /
``failover``, the supervisor's ``rebuild``; and ``gc``, each full
garbage collection), carried through ``DPF.eval_tpu``,
``ServingEngine``, ``SchemeRouter``, ``EngineSupervisor`` and
``LookupStream`` via the module-level ``span()`` helper.

Design constraints (docs/OBSERVABILITY.md):

* **Tracing-off fast path** — ``span()`` with no tracer installed
  returns one shared no-op context manager: a single global read on the
  serving hot path, no allocation.
* **One clock with the device** — while a tracer is installed
  (``enable()``), every span also opens a
  ``jax.profiler.TraceAnnotation`` named ``dpf.<span name>`` with the
  span's attrs as its arguments, so inside a profiler capture the spans
  land on the host plane of the same trace as the device ops, nested as
  they were opened.  Open that trace in Perfetto; there is no separate
  export.  JAX is imported at ``enable()``, never by this module.
* **Bounded memory** — finished spans land in a ring
  (``deque(maxlen=capacity)``); a long-lived serving process keeps the
  most recent window, like the latency ring.  ``events()`` and
  ``digest()`` read it.

Spans are thread-aware (one nesting stack per thread, thread id on
every span), so supervisor rebuilds and background resolution show up
on their own tracks.
"""

from __future__ import annotations

import gc
import itertools
import threading
import time
from collections import deque

from .flight import _env_capacity

#: default bounded span-ring capacity per tracer
SPAN_RING = 8192

#: ``jax.profiler.TraceAnnotation`` while a tracer is installed, else None
_ANNOTATION = None


class NullSpan:
    """The shared no-op span: ``span()``'s answer when tracing is off.

    Stateless and reentrant — one instance serves every call site
    concurrently, so the off path costs a global read and nothing else.
    """

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = NullSpan()


class Span:
    """One live span; use as a context manager (``Tracer.span``).

    ``set(**attrs)`` attaches attributes any time before exit (e.g. the
    routed construction, the bucket size).  On exit the span computes
    its SELF time (duration minus direct children) and lands in the
    tracer's ring.  While a tracer is installed the span also holds a
    profiler annotation ``dpf.<name>`` open over the same interval,
    carrying the same attrs.
    """

    __slots__ = ("name", "attrs", "span_id", "parent_id", "tid",
                 "t0", "dur_s", "_children_s", "_tracer", "_ann")

    def __init__(self, tracer, name, span_id, parent_id, tid, attrs):
        self._tracer = tracer
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.tid = tid
        self.attrs = attrs
        self.t0 = None
        self.dur_s = 0.0
        self._children_s = 0.0
        self._ann = None

    def set(self, **attrs):
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    def __enter__(self):
        self._tracer._push(self)
        annotation = _ANNOTATION
        if annotation is not None:
            self._ann = annotation("dpf." + self.name, **self.attrs)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_s = time.perf_counter() - self.t0
        if exc_type is not None and "error" not in self.attrs:
            self.set(error=exc_type.__name__)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        self._tracer._pop(self)
        return False


class Tracer:
    """Bounded-ring span recorder; install process-wide via ``enable()``.

    All methods are thread-safe; each thread keeps its own nesting
    stack so concurrent submits/rebuilds produce correctly-parented
    spans on separate tracks.  The lock is reentrant: a full collection
    can start between any two bytecodes, also inside a locked block,
    and its ``gc`` span lands in the ring from the same thread.
    """

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            capacity = _env_capacity("DPF_SPAN_RING", SPAN_RING)
        self._ring = deque(maxlen=int(capacity))
        self._lock = threading.RLock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._epoch = time.perf_counter()
        self._gc_span = None      # the open ``gc`` span, if one is open
        self.dropped = 0          # spans evicted from the full ring
        self.recorded = 0

    # ------------------------------------------------------- recording

    def span(self, name: str, **attrs) -> Span:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        parent = stack[-1].span_id if stack else None
        return Span(self, name, next(self._ids), parent,
                    threading.get_ident(), attrs)

    def _push(self, sp: Span):
        self._local.stack.append(sp)

    def _pop(self, sp: Span):
        stack = self._local.stack
        # tolerate exotic unwinds: pop through to this span
        while stack and stack[-1] is not sp:
            stack.pop()
        if stack:
            stack.pop()
        if stack:
            stack[-1]._children_s += sp.dur_s
        row = {"name": sp.name, "span_id": sp.span_id,
               "parent_id": sp.parent_id, "tid": sp.tid,
               "ts_us": round((sp.t0 - self._epoch) * 1e6, 1),
               "dur_us": round(sp.dur_s * 1e6, 1),
               "self_us": round(max(0.0, sp.dur_s - sp._children_s)
                                * 1e6, 1)}
        if sp.attrs:
            row["attrs"] = sp.attrs
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(row)
            self.recorded += 1

    # --------------------------------------------------------- reading

    def events(self) -> list:
        """Finished spans, oldest first (each a JSON-ready dict)."""
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self.recorded = 0

    def digest(self, top: int = 12) -> dict | None:
        """Aggregate SELF time per span name: small enough to embed in
        a benchmark record (``obs.record_sections``)."""
        events = self.events()
        if not events:
            return None
        by_name = {}
        total_us = 0.0
        for e in events:
            s = e["self_us"]
            total_us += s
            cnt, us = by_name.get(e["name"], (0, 0.0))
            by_name[e["name"]] = (cnt + 1, us + s)
        spans = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
        return {"spans_recorded": self.recorded,
                "spans_dropped": self.dropped,
                "host_ms": round(total_us / 1e3, 3),
                "top_spans": [{"span": k, "count": c,
                               "ms": round(us / 1e3, 3)}
                              for k, (c, us) in spans]}


# ------------------------------------------------- process-wide tracer

_TRACER: Tracer | None = None


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook while a tracer is installed: each full
    (generation 2) collection becomes a ``gc`` span on the thread that
    ran it.  Younger generations collect too often to be worth a span."""
    t = _TRACER
    if t is None or info["generation"] != 2:
        return
    if phase == "start":
        t._gc_span = t.span("gc", generation=2)
        t._gc_span.__enter__()
    elif t._gc_span is not None:
        sp, t._gc_span = t._gc_span, None
        sp.set(collected=info["collected"])
        sp.__exit__(None, None, None)


def enable(capacity: int | None = None) -> Tracer:
    """Install (and return) the process tracer; idempotent unless a
    different capacity is requested.  ``capacity=None`` resolves the
    ``DPF_SPAN_RING`` environment knob (else ``SPAN_RING``).  From here
    on every span also writes a ``dpf.<name>`` profiler annotation, and
    full garbage collections are recorded as ``gc`` spans."""
    global _TRACER, _ANNOTATION
    if capacity is None:
        capacity = _env_capacity("DPF_SPAN_RING", SPAN_RING)
    if _TRACER is None or _TRACER._ring.maxlen != int(capacity):
        _TRACER = Tracer(capacity)
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return _TRACER


def disable() -> None:
    """Remove the process tracer: ``span()`` reverts to the no-op fast
    path, the GC hook comes out (already-captured spans are dropped
    with the tracer)."""
    global _TRACER, _ANNOTATION
    _TRACER = None
    _ANNOTATION = None
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def get_tracer() -> Tracer | None:
    return _TRACER


def tracing() -> bool:
    return _TRACER is not None


def span(name: str, **attrs):
    """THE hot-path entry point: a real span when tracing is enabled,
    the shared ``NULL_SPAN`` otherwise (one global read, no alloc)."""
    t = _TRACER
    if t is None:
        return NULL_SPAN
    return t.span(name, **attrs)


def annotate(**attrs) -> None:
    """Attach ``attrs`` to the calling thread's innermost open span:
    code that learns a fact inside a span its caller opened (the kernel
    a dispatch resolved) names it there.  One global read when tracing
    is off."""
    t = _TRACER
    if t is None:
        return
    stack = getattr(t._local, "stack", None)
    if stack:
        stack[-1].set(**attrs)
