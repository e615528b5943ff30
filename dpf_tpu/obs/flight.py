"""Flight recorder: a bounded ring of recent routing/fault decisions.

The serving stack makes runtime decisions on every batch — cost-model
routing, admission control, retries, breaker trips, supervisor
rebuilds — and until now the only evidence was aggregate counter sums:
a p99 regression or a mis-routed burst could not be attributed to a
DECISION after the fact.  The flight recorder is the attribution
substrate: every decision point appends one small structured event to
a process-wide bounded ring, dumpable on demand (``flight_dump()``),
embedded in benchmark records, and dumped automatically when the chaos
bench's equality gate fails so an escape is diagnosable.

Event kinds (full schema in docs/OBSERVABILITY.md):

* ``route``    — construction, routed_from, bucket, batch, the cost
  estimates the argmin saw, and (under fault injection) the arrival
  index — the join key that attributes a later fault to the decision
  that placed the batch.
* ``shed`` / ``deadline`` — admission control rejections and
  cooperative-deadline trips, with the queue state that triggered them.
* ``breaker``  — every breaker state transition.
* ``retry`` / ``failover`` — resilient-submit recovery steps.
* ``fault``    — every injected-fault fire (kind, construction,
  bucket, arrival), written by ``FaultInjector``.
* ``rebuild``  — supervisor engine rebuilds (ok/failed).
* ``scatter`` / ``host_drop`` / ``cluster_recovery`` — the multi-host
  tier (``parallel/cluster.py``): per-arrival scatter plans, detected
  host losses, and the re-shard-or-degrade decision that answered each
  loss (``decision`` ∈ {"reshard", "degrade"}).

Events carry a monotonic timestamp relative to recorder start and a
global sequence number, so interleavings across threads stay ordered.
Multi-host runs stamp each event with the recording process's
``process`` index (``set_process_index``), so merged rings stay
attributable per host.
Recording is always on: one dict + deque append per DECISION (not per
query), bounded memory, no I/O.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

#: default bounded flight-ring capacity (events, not queries)
FLIGHT_RING = 2048


def _env_capacity(name: str, default: int) -> int:
    """Positive-int ring capacity from the environment, else the
    default (a malformed value must never break recorder import)."""
    try:
        v = int(os.environ.get(name, ""))
        return v if v > 0 else default
    except (TypeError, ValueError):
        return default


class FlightRecorder:
    """Thread-safe bounded event ring; one process-wide instance
    (``FLIGHT``) is the default everywhere.

    ``capacity`` defaults to ``DPF_FLIGHT_RING`` from the environment
    (else ``FLIGHT_RING``) — a busy multi-tenant process can widen the
    ring without code changes.  ``dropped`` counts events evicted from
    a full ring (exported as ``dpf_flight_events_dropped_total``), so
    ring overrun is visible instead of silently losing
    fault-attribution evidence."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            capacity = _env_capacity("DPF_FLIGHT_RING", FLIGHT_RING)
        self._ring = deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self.recorded = 0           # total ever recorded (ring evicts)
        self.dropped = 0            # events evicted from the full ring
        self._process = None        # jax process_index label (multi-host)

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    def set_process(self, index: int | None) -> None:
        """Stamp every subsequent event with a ``process`` label — the
        ``jax.process_index()`` of this process (``multihost.initialize``
        calls this on success; cluster workers set their rank), so a
        multi-host flight merge stays attributable per host."""
        self._process = None if index is None else int(index)

    def record(self, kind: str, **attrs) -> None:
        """Append one event; never raises (decision paths call this)."""
        try:
            ev = {"seq": 0, "t": round(time.monotonic() - self._t0, 6),
                  "kind": kind}
            if self._process is not None and "process" not in attrs:
                ev["process"] = self._process
            ev.update(attrs)
            with self._lock:
                self.recorded += 1
                ev["seq"] = self.recorded
                if len(self._ring) == self._ring.maxlen:
                    self.dropped += 1
                self._ring.append(ev)
        except Exception:
            pass

    def dump(self, last: int | None = None) -> list:
        """JSON-ready copy of the ring, oldest first (``last`` bounds
        the tail for embedding in records)."""
        with self._lock:
            out = list(self._ring)
        if last is not None:
            out = out[-int(last):]
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
        # `recorded` keeps counting: it is a monotonic metric

    def export_jsonl(self, path: str) -> int:
        events = self.dump()
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")
        return len(events)


#: the process flight recorder (serving code records into this)
FLIGHT = FlightRecorder()


def flight_dump(last: int | None = None) -> list:
    """Dump the process flight ring (the on-demand diagnosis entry
    point named by docs/OBSERVABILITY.md)."""
    return FLIGHT.dump(last=last)


def set_process_index(index: int | None) -> None:
    """Label the process ring's events with a process index
    (multi-host serving: one flight ring per process, merged by rank)."""
    FLIGHT.set_process(index)
