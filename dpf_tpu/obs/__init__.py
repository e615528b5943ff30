"""End-to-end serving observability (docs/OBSERVABILITY.md).

Three coupled pieces, one per module:

* ``tracer``  — nested span tracing of the host path (``eval_tpu`` >
  ``eval_tpu.decode`` / ``.dispatch`` / ``.fetch``; ``submit`` >
  ``admit`` / ``pack`` / ``backpressure`` / ``dispatch``, ``wait``,
  ``decode``, each carrying the request's ``rid``; ``route``,
  ``retry``, ``failover``, ``rebuild``; ``gc`` for each full garbage
  collection) into a bounded ring.  Off by default — the serving hot
  path pays one global read (``span()`` returns the shared no-op).
  Installed (``enable()``), every span also writes a ``dpf.<name>``
  ``jax.profiler`` annotation, so a profiler capture holds the host
  spans on the device trace's clock: open that trace in Perfetto.
  The device program names its parts with ``jax.named_scope``
  (``dpf.frontier``, ``dpf.subtree``, ``dpf.prf``, ``dpf.cw_add``,
  ``dpf.contract``) on the same trace.
* ``metrics`` — typed Counter/Gauge/Histogram registry with an
  OpenMetrics text exporter and JSON snapshot; ``EngineCounters``,
  ``CacheCounters``, ``SWALLOWED_ERRORS``, breaker states and the
  router's EWMA cost table self-register as first-class series.
* ``flight``  — a bounded ring of recent structured DECISIONS (route,
  shed, breaker transition, retry, failover, injected fault, rebuild),
  dumpable on demand and embedded in benchmark records.
"""

from .flight import FLIGHT, FlightRecorder, flight_dump  # noqa: F401
from .metrics import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, default_registry,
                      register_cluster, register_engine, register_router)
from .tracer import (NULL_SPAN, Span, Tracer, disable,  # noqa: F401
                     enable, get_tracer, span, tracing)


def set_process_index(index: int | None) -> None:
    """Label THIS process's observability output with its jax
    ``process_index`` (multi-host serving): flight-recorder events gain
    a ``process`` attribute and engine/router/cluster metric series a
    ``process`` label, so merged cross-host dumps stay attributable.
    ``multihost.initialize`` calls this on success; cluster workers set
    their rank explicitly."""
    from .flight import set_process_index as _flight
    from .metrics import set_process_index as _metrics
    _flight(index)
    _metrics(index)


def record_sections(flight_last: int = 64) -> dict:
    """The observability sections every benchmark record embeds:
    ``metrics`` (the registry JSON snapshot), ``flight`` (the tail of
    the decision ring), and — when a tracer is installed —
    ``trace_digest`` (host span self-times).  Small and JSON-ready."""
    out = {"metrics": REGISTRY.snapshot(),
           "flight": flight_dump(last=flight_last)}
    t = get_tracer()
    if t is not None:
        out["trace_digest"] = t.digest()
    return out
