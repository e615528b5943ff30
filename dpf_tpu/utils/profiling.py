"""Host-side measurement helpers: the serving engine's counters
(``EngineCounters``: work, waits, latency ring and histogram), the
cache counters, the swallowed-error registry and ``Timer``.

Device traces come from ``jax.profiler``; the program's host spans land
on the same trace through ``obs.tracer`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings


def quantile(samples, q: float, *, presorted: bool = False) -> float:
    """Nearest-rank quantile of a sequence of floats (q in [0, 1]).

    Deliberately numpy-free: the latency ring is consulted on the
    admission-control hot path (every ``submit``), where an np.quantile
    round-trip would cost more than the dispatch it guards.
    ``presorted=True`` skips the sort (the ring keeps a cached sorted
    view for exactly that path)."""
    if not samples:
        raise ValueError("quantile of an empty sample set")
    if not 0.0 <= q <= 1.0:
        raise ValueError("q must be in [0, 1] (got %r)" % (q,))
    s = samples if presorted else sorted(samples)
    return s[min(len(s) - 1, max(0, int(q * len(s) + 0.5) - 1))]


#: bounded size of the per-engine latency ring: big enough that p99 over
#: it is stable, small enough that a long-lived engine's memory and the
#: per-submit quantile stay O(1)-ish
LATENCY_RING = 2048

#: fixed upper bounds (seconds) of the per-engine latency HISTOGRAM —
#: the mergeable cumulative complement of the ring's exact bounded-
#: window quantiles (the ring forgets, the histogram accumulates; the
#: OpenMetrics exporter in obs/metrics.py renders both).  1 ms .. 10 s
#: log-ish ladder, +Inf bucket implicit.
LATENCY_HIST_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                          0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _hist_zero() -> list:
    return [0] * (len(LATENCY_HIST_BUCKETS_S) + 1)


@dataclasses.dataclass
class EngineCounters:
    """Per-engine serving counters (serve/engine.py).

    Host pack time (vectorized decode + bucket pad), dispatch time (the
    jitted call — async enqueue on TPU, the compute itself on the
    synchronous CPU backend) and wait time (host blocking on device
    results) are split so the host/device overlap the engine buys is
    visible in the benchmark record.

    SLO accounting (docs/SERVING.md "Load testing & SLOs"): per-batch
    submit→result latencies land in a bounded ring (``note_latency``;
    p50/p95/p99 via ``quantile``) AND a fixed-bucket cumulative
    histogram (``latency_histogram``, rendered by the OpenMetrics
    exporter), ``deadline_misses`` counts cooperative-deadline trips,
    and ``shed_*`` count batches/queries the admission control rejected
    instead of queueing.  ``reset()`` and ``merge()`` let a router
    (serve/router.py) or ``LookupStream`` aggregate per-engine counters
    into one record without hand-copying fields.

    Mutation is THREAD-SAFE where threads actually race: the
    ``note_*`` recorders, ``inc()`` (the spelling for cross-thread
    ``field += n`` — supervisor rebuild threads and
    ``RoutedFuture.result()`` callers share a router's ``recovery``
    counters), ``merge``/``reset`` and the readers all hold the
    per-instance lock.  Single-owner hot-path writes inside
    ``ServingEngine`` (an engine is not itself a concurrent object)
    stay plain attribute updates.
    """
    batches_submitted: int = 0
    queries_submitted: int = 0
    dispatches: int = 0
    padded_queries: int = 0       # pad rows dispatched (bucket waste)
    in_flight_hwm: int = 0        # high-water mark of the dispatch window
    pack_time_s: float = 0.0
    dispatch_time_s: float = 0.0
    wait_time_s: float = 0.0
    deadline_misses: int = 0      # cooperative-deadline trips
    shed_batches: int = 0         # batches rejected by admission control
    shed_queries: int = 0         # queries inside those batches
    # fault-tolerance accounting (serve/faults.py, docs/SERVING.md
    # "Fault tolerance & chaos testing"): additive like the counters
    # above, so they flow through merge()/as_dict unchanged
    retries: int = 0              # re-attempts after a failed submit
    failovers: int = 0            # batches moved to another construction
    breaker_opens: int = 0        # circuit-breaker closed->open trips
    engine_restarts: int = 0      # supervisor engine rebuilds
    swallowed_errors: int = 0     # caught-and-suppressed exceptions
    #: bounded ring of recent per-batch latencies (seconds); leading
    #: underscore keeps the raw samples out of as_dict — records carry
    #: the quantiles, not 2048 floats
    _latencies: list = dataclasses.field(default_factory=list, repr=False)
    _lat_pos: int = 0
    #: sorted view of the ring, rebuilt lazily: admission control reads
    #: p99 on every submit, so the sort must not repeat while no new
    #: sample landed
    _lat_sorted: list | None = dataclasses.field(default=None,
                                                 repr=False)
    #: cumulative fixed-bucket histogram of every latency ever noted
    #: (the ring's mergeable complement; last slot is the +Inf bucket)
    _lat_hist: list = dataclasses.field(default_factory=_hist_zero,
                                        repr=False)
    _lat_hist_sum: float = dataclasses.field(default=0.0, repr=False)
    _lat_hist_count: int = dataclasses.field(default=0, repr=False)
    #: per-instance lock (RLock: as_dict -> quantile nests); excluded
    #: from ==/repr and NEVER replaced by reset() — a racing thread may
    #: hold it
    _lock: object = dataclasses.field(
        default_factory=threading.RLock, repr=False, compare=False)

    def inc(self, name: str, delta=1):
        """Thread-safe ``self.<name> += delta`` — the one spelling for
        counter bumps that can race across threads (supervisor rebuild
        threads, ``RoutedFuture.result()`` callers)."""
        with self._lock:
            setattr(self, name, getattr(self, name) + delta)

    def note_dispatch(self, padded: int, in_flight: int):
        with self._lock:
            self.dispatches += 1
            self.padded_queries += padded
            self.in_flight_hwm = max(self.in_flight_hwm, in_flight)

    def note_latency(self, seconds: float):
        """Record one batch's submit→result latency in the ring
        (overwriting the oldest sample once ``LATENCY_RING`` is full)
        and the cumulative fixed-bucket histogram."""
        s = float(seconds)
        with self._lock:
            if len(self._latencies) < LATENCY_RING:
                self._latencies.append(s)
            else:
                self._latencies[self._lat_pos] = s
                self._lat_pos = (self._lat_pos + 1) % LATENCY_RING
            self._lat_sorted = None
            i = 0
            while (i < len(LATENCY_HIST_BUCKETS_S)
                   and s > LATENCY_HIST_BUCKETS_S[i]):
                i += 1
            self._lat_hist[i] += 1
            self._lat_hist_sum += s
            self._lat_hist_count += 1

    def quantile(self, q: float) -> float | None:
        """Latency quantile over the ring (seconds), None when empty."""
        with self._lock:
            if not self._latencies:
                return None
            if self._lat_sorted is None:
                self._lat_sorted = sorted(self._latencies)
            return quantile(self._lat_sorted, q, presorted=True)

    def latency_histogram(self) -> dict:
        """The cumulative fixed-bucket latency histogram:
        ``{"buckets": bounds, "counts": per-bucket (+Inf last),
        "sum", "count"}`` — what the OpenMetrics exporter renders as
        ``dpf_engine_latency_seconds``."""
        with self._lock:
            return {"buckets": list(LATENCY_HIST_BUCKETS_S),
                    "counts": list(self._lat_hist),
                    "sum": round(self._lat_hist_sum, 6),
                    "count": self._lat_hist_count}

    @property
    def p50(self):
        return self.quantile(0.50)

    @property
    def p95(self):
        return self.quantile(0.95)

    @property
    def p99(self):
        return self.quantile(0.99)

    @property
    def pad_waste(self) -> float:
        """Fraction of dispatched query slots that were padding."""
        total = self.queries_submitted + self.padded_queries
        return self.padded_queries / total if total else 0.0

    def reset(self) -> "EngineCounters":
        """Zero every counter and drop the latency ring/histogram, in
        place (the lock itself survives — a racing thread may hold it)."""
        with self._lock:
            for f in dataclasses.fields(self):
                if f.name == "_lock":
                    continue
                setattr(
                    self, f.name,
                    f.default if f.default_factory is dataclasses.MISSING
                    else f.default_factory())
        return self

    def merge(self, other: "EngineCounters") -> "EngineCounters":
        """Fold ``other`` into self: sums for the additive counters, max
        for the high-water mark, both latency rings pooled and the
        histograms added bucket-wise.  A pool over the ring bound is
        DOWNSAMPLED by a uniform stride (not truncated) so every merged
        engine keeps proportional representation in the aggregate
        quantiles — a tail slice would silently reduce the aggregate to
        the last engine merged.  Returns self, so
        ``reduce(EngineCounters.merge, stats_list, EngineCounters())``
        builds one aggregate record.  Locks both instances in id order
        (no deadlock against a concurrent opposite-direction merge).
        Merging an instance into itself is a no-op (it would silently
        double every counter and duplicate the pooled latency ring)."""
        if other is self:
            return self
        first, second = ((self, other) if id(self) <= id(other)
                         else (other, self))
        with first._lock, second._lock:
            for f in dataclasses.fields(self):
                if f.name.startswith("_") or f.name == "in_flight_hwm":
                    continue
                setattr(self, f.name,
                        getattr(self, f.name) + getattr(other, f.name))
            self.in_flight_hwm = max(self.in_flight_hwm,
                                     other.in_flight_hwm)
            pooled = self._latencies + other._latencies
            if len(pooled) > LATENCY_RING:
                step = len(pooled) / LATENCY_RING
                pooled = [pooled[int(i * step)]
                          for i in range(LATENCY_RING)]
            self._latencies = pooled
            self._lat_pos = 0
            self._lat_sorted = None
            self._lat_hist = [a + b for a, b in
                              zip(self._lat_hist, other._lat_hist)]
            self._lat_hist_sum += other._lat_hist_sum
            self._lat_hist_count += other._lat_hist_count
        return self

    def as_dict(self) -> dict:
        with self._lock:
            d = {}
            for f in dataclasses.fields(self):
                if f.name.startswith("_"):
                    continue  # raw latency samples: summarized below
                v = getattr(self, f.name)
                d[f.name] = round(v, 6) if isinstance(v, float) else v
            d["pad_waste"] = round(self.pad_waste, 4)
            if self._latencies:
                d["latency_ms"] = {
                    "count": len(self._latencies),
                    "p50": round(self.p50 * 1e3, 3),
                    "p95": round(self.p95 * 1e3, 3),
                    "p99": round(self.p99 * 1e3, 3),
                }
            return d


@dataclasses.dataclass
class CacheCounters:
    """Process-wide cache-effectiveness counters (tune/ subsystem).

    ``tuning_*`` move on every persistent-tuning-cache lookup
    (``tune/cache.py``); ``compile_*`` mirror JAX's
    ``/jax/compilation_cache/*`` monitoring events once
    ``tune.compcache.enable()`` has registered its listener.  A warm
    second process shows ``tuning_hits > 0`` (autotune search skipped)
    and ``compile_hits > 0`` (XLA recompile skipped) — the assertion
    the warm-start test makes.
    """
    tuning_hits: int = 0
    tuning_misses: int = 0
    tuning_stores: int = 0
    compile_hits: int = 0
    compile_misses: int = 0
    compile_time_saved_s: float = 0.0

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["compile_time_saved_s"] = round(d["compile_time_saved_s"], 4)
        return d

    def reset(self) -> "CacheCounters":
        """Zero every counter in place (mirrors ``EngineCounters.reset``
        so tests and benches can scope cache measurements to one run)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name, f.default)
        return self


CACHE_COUNTERS = CacheCounters()


#: process-wide registry of caught-and-suppressed exceptions:
#: site -> {exception class name -> count}.  The serving stack has
#: several deliberate "must never break serving" suppression points
#: (cache lookups, compile-cache enable, diagnostics); before this
#: registry they discarded the cause entirely, so a misconfigured cache
#: was indistinguishable from a cold one.  ``note_swallowed`` is the
#: one spelling of "suppress but stay diagnosable".
SWALLOWED_ERRORS: dict = {}
_SWALLOWED_WARNED: set = set()
#: suppression sites fire from supervisor/resolver threads as well as
#: the caller's — the registry mutation must not race
_SWALLOWED_LOCK = threading.Lock()


def note_swallowed(site: str, exc: BaseException, stats=None) -> None:
    """Record a deliberately suppressed exception.

    Increments ``SWALLOWED_ERRORS[site][type(exc).__name__]`` (under a
    module lock — suppression sites fire from background threads), bumps
    ``stats.swallowed_errors`` when an ``EngineCounters`` is supplied,
    and emits ONE ``RuntimeWarning`` per (site, exception class) per
    process — loud enough to see in logs, quiet enough not to spam a
    serving loop that hits the same broken cache on every lookup.
    Never raises (it guards suppression sites)."""
    try:
        cls = type(exc).__name__
        with _SWALLOWED_LOCK:
            by_cls = SWALLOWED_ERRORS.setdefault(site, {})
            by_cls[cls] = by_cls.get(cls, 0) + 1
            warn = (site, cls) not in _SWALLOWED_WARNED
            if warn:
                _SWALLOWED_WARNED.add((site, cls))
        if stats is not None:
            if hasattr(stats, "inc"):
                stats.inc("swallowed_errors")
            else:
                stats.swallowed_errors += 1
        if warn:
            warnings.warn(
                "suppressed %s at %s: %s (further occurrences counted "
                "in dpf_tpu.utils.profiling.SWALLOWED_ERRORS, not "
                "re-warned)" % (cls, site, exc), RuntimeWarning,
                stacklevel=3)
    except Exception:
        pass


def swallowed_snapshot() -> dict:
    """A JSON-ready copy of the swallowed-error registry (benchmark
    records embed it so suppressed causes are visible in artifacts)."""
    with _SWALLOWED_LOCK:
        return {site: dict(by_cls) for site, by_cls in
                sorted(SWALLOWED_ERRORS.items())}


class Timer:
    """Wall-clock block timer that blocks on device completion.

    The old exit barrier — ``block_until_ready(jnp.zeros(()))`` — only
    proves ONE fresh dispatch finished; on an asynchronous backend (TPU)
    independent prior computations may still be in flight, so the timer
    under-reported.  The exit blocks on the outputs handed to the
    constructor or ``note()``, then drains via ``jax.effects_barrier()``."""

    def __init__(self, *outputs):
        self.elapsed = 0.0
        self._outputs = list(outputs)

    def note(self, *outputs) -> "Timer":
        """Register result arrays the exit barrier must block on."""
        self._outputs.extend(outputs)
        return self

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        # drain async dispatch before stopping the clock
        if self._outputs:
            jax.block_until_ready(self._outputs)
        jax.effects_barrier()
        self.elapsed = time.perf_counter() - self._t0
        return False
