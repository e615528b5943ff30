"""Pipelined serving engine: keep the device saturated under a stream of
query batches.

The blocking loop (``DPF.eval_tpu`` per batch) serializes host and
device: deserialize keys, pack, dispatch, then ``np.asarray`` — the
device idles while the host parses the next batch (the host/device
overlap problem of the TPU linear-algebra literature, PAPERS.md
arXiv:2112.09017).  The engine splits that pipeline:

* **Vectorized ingest** — a whole batch decodes through the batched wire
  codec (``keygen.decode_keys_batched`` / ``radix4``'s counterpart) in
  O(1) Python ops instead of a per-key loop.
* **Double-buffered dispatch** — ``submit()`` returns a future
  immediately after enqueueing the jitted program (JAX async dispatch,
  no premature ``np.asarray``); the host packs batch k+1 while batch k
  runs on device.  A configurable ``max_in_flight`` window bounds the
  queue: when full, ``submit`` blocks on the oldest outstanding dispatch
  (backpressure) before enqueueing more.
* **Shape-bucketed batching** — ragged batch sizes pad up to a small
  fixed set of power-of-two buckets (``serve/buckets.py``) so at most
  ``len(buckets)`` XLA programs compile; ``warmup()`` precompiles all of
  them at init.

The engine is server-agnostic: any object with ``_decode_batch(keys) ->
packed batch`` and ``_dispatch_packed(pk) -> device array`` works — both
``api.DPF`` (single chip, all three constructions: binary GGM, radix-4,
and sqrt-N via ``sqrtn.PackedSqrtKeys``) and
``parallel.sharded.ShardedDPFServer`` (mesh path) provide the pair.
Results are bit-identical to the blocking loop (pad rows are discarded;
per-key math is batch-shape independent).
"""

from __future__ import annotations

import itertools
import time
from collections import deque

import numpy as np

from ..core.expand import DeadlineExceeded
from ..obs.flight import FLIGHT
from ..obs.tracer import span
from ..utils.profiling import EngineCounters, note_swallowed
from .buckets import Buckets


class LoadShed(RuntimeError):
    """Admission control rejected a batch instead of queueing it.

    Raised by ``ServingEngine.submit`` when ``shed=True`` and either the
    pending-future queue is at ``max_queue_depth`` or the engine's p99
    latency estimate exceeds ``slo_s`` while a backlog exists.  The
    batch was NOT dispatched — nothing to unwind; the caller (a router,
    a front-end) answers the client with a retry/reject instead of
    letting the queue grow past the SLO."""


class EngineClosed(RuntimeError):
    """The engine was decommissioned (``ServingEngine.close``): every
    subsequent ``submit`` is rejected cleanly.  Distinct from
    ``LoadShed`` (an admission *decision* that self-heals) — a closed
    engine never comes back; the caller must route elsewhere.  This is
    the autoscaler's scale-down contract (``plan/autoscale.py``): a
    retained handle that submits after the drain gets this instead of
    racing the teardown."""


class _Part:
    """One dispatched (bucket-padded) chunk of a submitted batch."""
    __slots__ = ("dev", "n_real", "bucket", "rid", "out")

    def __init__(self, dev, n_real, bucket, rid):
        self.dev = dev          # device array, possibly still in flight
        self.n_real = n_real    # rows that are real queries (not pad)
        self.bucket = bucket    # padded dispatch size (fault targeting)
        self.rid = rid          # the submitting future's request id
        self.out = None         # resolved host array


class EngineFuture:
    """Result handle for one submitted batch.

    ``result()`` blocks until this batch — and, FIFO, every batch
    submitted before it — has left the device, then returns the
    ``[batch, entry_size]`` int32 share array.  ``rid`` numbers the
    engine's submits from 1; the request's spans carry it.
    """
    __slots__ = ("_engine", "_parts", "_value", "_t0", "rid")

    def __init__(self, engine, rid: int):
        self._engine = engine
        self._parts = []
        self._value = None
        self._t0 = None     # submit-entry perf_counter (latency ring)
        self.rid = rid

    def done(self) -> bool:
        return self._value is not None

    def result(self):
        if self._value is None:
            self._engine._resolve_through(self)
        return self._value


class ServingEngine:
    """Throughput-oriented DPF serving over one prepared table.

    Args:
      server: an ``api.DPF`` after ``eval_init`` or a
        ``parallel.sharded.ShardedDPFServer``.
      max_in_flight: dispatch-window size (outstanding device programs
        before ``submit`` applies backpressure).  2 is classic double
        buffering.
      buckets: a ``Buckets``, an iterable of power-of-two sizes, or None
        for the default /2 ladder under the server's batch cap.  On the
        mesh path, sizes should be multiples of the mesh "batch" axis or
        the dispatch pads further (still one program per bucket).
      warmup: precompile every bucket at construction.
      max_queue_depth: admission bound on PENDING futures (batches
        submitted but not yet resolved).  When reached, ``submit``
        resolves the oldest future first (deeper backpressure than the
        dispatch window) — or, with ``shed=True``, rejects the batch.
      slo_s: target per-batch latency.  With ``shed=True``, a batch
        arriving while the p99 of the latency ring exceeds ``slo_s``
        AND a backlog exists is rejected (``LoadShed``) rather than
        queued — an idle engine always admits, so shedding self-heals
        once the backlog drains.
      shed: reject (raise ``LoadShed``, counted in
        ``stats.shed_batches/shed_queries``) instead of blocking when
        admission control trips.
      label: construction label for fault targeting and router
        bookkeeping (``serve/faults.py``); None outside a router.
      injector: a ``faults.FaultInjector`` consulted at the first-class
        injection points (before each dispatch, on each resolved
        result, before each warmup precompile).  None = no injection —
        the points cost one attribute check on the hot path.

    ``deadline`` (a ``time.monotonic()`` value — immune to NTP steps;
    pass ``timeout_s`` to have the engine compute it) is checked
    cooperatively between dispatches and resolutions — never mid-compile —
    raising ``expand.DeadlineExceeded``
    and counting the trip in ``stats.deadline_misses``.
    """

    def __init__(self, server, *, max_in_flight: int = 2, buckets=None,
                 warmup: bool = False, deadline: float | None = None,
                 timeout_s: float | None = None,
                 max_queue_depth: int | None = None,
                 slo_s: float | None = None, shed: bool = False,
                 label: str | None = None, injector=None,
                 tenant: str | None = None):
        if max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1 (got %d)"
                             % max_in_flight)
        if deadline is not None and timeout_s is not None:
            raise ValueError(
                "pass deadline (absolute time.monotonic()) or timeout_s "
                "(relative), not both")
        if timeout_s is not None:
            deadline = time.monotonic() + timeout_s
        if max_queue_depth is not None and max_queue_depth < 1:
            raise ValueError("max_queue_depth must be >= 1 (got %d)"
                             % max_queue_depth)
        n = getattr(server, "table_num_entries", None)
        if n is None:
            n = getattr(server, "n", None)
        if n is None:
            raise RuntimeError(
                "server has no initialized table — call eval_init first")
        self._server = server
        self._n = int(n)
        self._out_width = getattr(server, "table_effective_entry_size",
                                  None) or getattr(server, "entry_size")
        self.max_in_flight = int(max_in_flight)
        if not isinstance(buckets, Buckets):
            cap = (getattr(server, "BATCH_SIZE", None)
                   or getattr(server, "batch_size", 512))
            buckets = Buckets(buckets if buckets is not None
                              else Buckets.default_sizes(cap))
        self.buckets = buckets
        self.deadline = deadline
        self.max_queue_depth = max_queue_depth
        self.slo_s = slo_s
        self.shed = bool(shed)
        self.label = label
        self.tenant = tenant      # owning tenant (metrics/flight labels)
        self._injector = injector
        self.stats = EngineCounters()
        self._closed = False      # set by close(); submit rejects after
        self._queue = deque()     # _Part refs, dispatch order, unresolved
        self._pending = deque()   # futures with unresolved parts, FIFO
        self._rids = itertools.count(1)
        # Persistent XLA compilation cache, on by default for the serve
        # path (disable: DPF_TPU_COMPILE_CACHE=0): warmup is real
        # serving latency, and a warm cache turns each bucket's compile
        # into a deserialize on every process after the first.
        try:
            from ..tune import compcache
            compcache.enable()
        except Exception as e:  # cache must never break serving —
            # but the cause stays diagnosable (counter + one-shot warn)
            note_swallowed("serve.engine.compcache_enable", e, self.stats)
        try:
            from ..obs.metrics import register_engine
            register_engine(self)
        except Exception as e:  # observability must never break serving
            note_swallowed("serve.engine.register_metrics", e, self.stats)
        if warmup:
            self.warmup()

    # ------------------------------------------------------------- submit

    def submit(self, keys) -> EngineFuture:
        """Decode + dispatch one batch; returns a future immediately.

        The host-side work here is the vectorized decode and the bucket
        pad; the device program is enqueued asynchronously.  When the
        in-flight window is full, blocks on the oldest outstanding
        dispatch first (backpressure, a ``backpressure`` span around
        the ``wait`` spans it makes).  Admission control
        (``max_queue_depth``/``slo_s``) runs first: over the bound the
        batch either waits on the oldest pending future or — with
        ``shed=True`` — is rejected with ``LoadShed`` before any decode
        or dispatch work happens.
        """
        if self._closed:
            raise EngineClosed(
                "engine %r is closed — submit after close()"
                % (self.label or "engine",))
        self._check_deadline()
        t_enter = time.perf_counter()
        # pre-decoded packed batches (LookupStream) carry .batch
        b_req = getattr(keys, "batch", None) or len(keys)
        rid = next(self._rids)
        with span("submit", engine=self.label or "engine", batch=b_req,
                  rid=rid):
            with span("admit"):
                self._admit(b_req)
            t0 = time.perf_counter()
            with span("pack", phase="decode"):
                pk = self._server._decode_batch(keys)
            b = pk.batch
            fut = EngineFuture(self, rid)
            # the latency ring measures from submit ENTRY: a blocking
            # admission wait is exactly the client-observed queueing the
            # p99 SLO trigger exists to see (pack_time_s stays post-admit)
            fut._t0 = t_enter
            try:
                for lo, hi in self.buckets.chunks(b):
                    self._check_deadline()
                    size = self.buckets.bucket_for(hi - lo)
                    with span("pack", phase="pad", bucket=size):
                        padded = pk.slice(lo, hi).pad_to(size)
                    self.stats.pack_time_s += time.perf_counter() - t0
                    if len(self._queue) >= self.max_in_flight:
                        with span("backpressure"):
                            while len(self._queue) >= self.max_in_flight:
                                self._check_deadline()
                                self._resolve_one()
                    with span("dispatch", bucket=size):
                        if self._injector is not None:
                            # first-class injection point: may sleep
                            # (straggler), raise InjectedDispatchError, or
                            # raise EngineDead — the partial-unwind below
                            # handles either
                            self._injector.on_dispatch(self, size)
                        t1 = time.perf_counter()
                        dev = self._server._dispatch_packed(padded)
                        self.stats.dispatch_time_s += (time.perf_counter()
                                                       - t1)
                    part = _Part(dev, hi - lo, size, rid)
                    fut._parts.append(part)
                    self._queue.append(part)
                    self.stats.note_dispatch(padded=size - (hi - lo),
                                             in_flight=len(self._queue))
                    t0 = time.perf_counter()
            except BaseException:
                # Unwind a partially submitted batch: its dispatched parts
                # must not stay orphaned in the window (the future is never
                # returned), so block on each (never interrupt an in-flight
                # program) and drop it from the queue.
                for p in fut._parts:
                    try:
                        self._queue.remove(p)
                    except ValueError:
                        pass
                    if p.dev is not None:
                        np.asarray(p.dev)
                        p.dev = None
                raise
            self.stats.batches_submitted += 1
            self.stats.queries_submitted += b
            self._pending.append(fut)
            return fut

    # ---------------------------------------------------------- resolution

    def _resolve_one(self):
        """Block on the oldest in-flight dispatch and store its rows."""
        part = self._queue.popleft()
        with span("wait", bucket=part.bucket, rid=part.rid):
            t0 = time.perf_counter()
            part.out = np.asarray(part.dev)[:part.n_real]
            if self._injector is not None:
                # injection point: corrupted-share faults replace the rows
                # here, downstream of the device — the bit-gating oracle
                # path must catch every one (integrity-check role)
                part.out = self._injector.on_result(self, part.bucket,
                                                    part.out)
            self.stats.wait_time_s += time.perf_counter() - t0
            part.dev = None

    def _finalize(self, fut: EngineFuture):
        with span("decode", parts=len(fut._parts), rid=fut.rid):
            parts = fut._parts
            if len(parts) == 1:
                out = parts[0].out
            else:
                out = np.concatenate([p.out for p in parts])
            fut._value = np.ascontiguousarray(out[:, :self._out_width])
            fut._parts = []
            if fut._t0 is not None:
                self.stats.note_latency(time.perf_counter() - fut._t0)

    def _resolve_through(self, fut: EngineFuture):
        """Resolve futures FIFO until (and including) ``fut``."""
        while self._pending:
            head = self._pending.popleft()
            while any(p.out is None for p in head._parts):
                self._resolve_one()
            self._finalize(head)
            if head is fut:
                return
        if fut._value is None:  # not one of ours
            raise RuntimeError("future does not belong to this engine")

    def drain(self) -> None:
        """Resolve every outstanding dispatch (blocks until the device is
        idle); all previously returned futures become ``done()``."""
        while self._pending:
            self._check_deadline()
            head = self._pending.popleft()
            while any(p.out is None for p in head._parts):
                self._resolve_one()
            self._finalize(head)

    def close(self) -> None:
        """Decommission: drain every outstanding dispatch, then reject
        all future ``submit``s with ``EngineClosed``.  In-flight work
        completes (every previously returned future resolves normally);
        counters are left intact for the caller's final accounting.
        Idempotent — the autoscaler's scale-down path
        (``plan/autoscale.ReplicaPool.scale_down``) drains explicitly
        first and then calls this for the rejection contract."""
        self.drain()
        if not self._closed:
            self._closed = True
            ev = dict(engine=self.label or "engine",
                      served=self.stats.queries_submitted)
            if self.tenant is not None:
                ev["tenant"] = self.tenant
            FLIGHT.record("engine_close", **ev)

    @property
    def closed(self) -> bool:
        return self._closed

    # ------------------------------------------------------------- warmup

    def warmup(self, tune: bool = False, trace=None) -> None:
        """Precompile every bucket's program with synthetic keys.

        A zero-codeword key with a valid header (depth/n — or, for
        scheme='sqrtn', the default K x R split) decodes into the exact
        array shapes real traffic produces, so each dispatch here
        populates the jit cache for one bucket size; outputs are
        discarded and none of the serving counters move.  (Sqrt-N keys
        minted with a custom ``n_keys`` split compile their own program
        on first dispatch — only the default split is prewarmed.)
        Because each dispatch goes through ``resolved_eval_knobs``, the
        precompiled program is whatever kernel the resolver picks —
        for sqrtn that includes ``kernel_impl`` ("xla" scan or the
        fused "pallas" grid kernel) AND any searched kernel variant
        (a ``kvariant`` tuning-cache entry from ``tune/
        kernel_search.py`` resolves with ``kernel_resolved_from=
        "searched"`` and its structural keywords thread through to the
        launcher), so real traffic hits a warm cache for the same
        program the search picked.

        ``tune=True`` first re-tunes the serving knobs in place: the
        persistent tuning cache (``tune/cache.py``) is consulted for
        this (device, table shape, cap) and, on a miss, the grid search
        (``tune.serve_tune.tune_serving``) runs against a synthetic
        arrival trace (or ``trace``, a list of batch sizes) — the
        engine's ``buckets`` and ``max_in_flight`` are then replaced by
        the measured winner before the precompile loop runs.  Searching
        needs a server that can mint keys (``api.DPF``); on the mesh
        path a cache miss leaves the knobs untouched.
        """
        if tune:
            from ..tune.serve_tune import lookup_serve_knobs, tune_serving
            cap = self.buckets.max
            knobs = lookup_serve_knobs(self._server, cap)
            if knobs is None and hasattr(self._server, "gen"):
                knobs = tune_serving(self._server, cap=cap,
                                     trace=trace)["knobs"]
            if knobs:
                self.buckets = Buckets(knobs["buckets"])
                self.max_in_flight = int(knobs["max_in_flight"])
        for size in self.buckets.sizes:
            if self._injector is not None:
                # injection point: compile failures fire here (and a
                # dead engine's warmup stays dead) — a supervisor
                # rebuild's re-warm exercises exactly this path
                self._injector.on_warmup(self, size)
            np.asarray(self._server._dispatch_packed(
                self._synthetic_packed(size)))

    def _synthetic_packed(self, size: int):
        """A zero-codeword packed batch with the exact array shapes real
        traffic produces at this bucket size (warmup/probe input)."""
        from ..core.keygen import PackedKeys
        if getattr(self._server, "scheme", "logn") == "sqrtn":
            from ..core import sqrtn
            from ..core.sqrtn import PackedSqrtKeys
            k, r = sqrtn.default_split(self._n)
            return PackedSqrtKeys(
                seeds=np.zeros((size, k, 4), dtype=np.uint32),
                cw1=np.zeros((size, r, 4), dtype=np.uint32),
                cw2=np.zeros((size, r, 4), dtype=np.uint32),
                n=self._n)
        return PackedKeys(
            cw1=np.zeros((size, 64, 4), dtype=np.uint32),
            cw2=np.zeros((size, 64, 4), dtype=np.uint32),
            last=np.zeros((size, 4), dtype=np.uint32),
            depth=self._n.bit_length() - 1, n=self._n)

    def probe(self, reps: int = 1) -> dict:
        """Measure one warmed dispatch per bucket size (seconds).

        The router's cost-model seed (serve/router.py): each bucket's
        program runs once untimed (compile/warm — a no-op when
        ``warmup()`` already ran and the jit cache is hot), then
        best-of-``reps`` timed blocking dispatches.  Synthetic
        zero-codeword keys measure the same program real traffic runs
        (the eval is data-independent).  Serving counters do not move.
        Returns ``{bucket_size: seconds}``.
        """
        out = {}
        for size in self.buckets.sizes:
            if self._injector is not None:
                # a dead engine must fail its probe: the breaker's
                # half-open re-probe relies on this to stay open until
                # the supervisor's rebuilt engine is actually serving
                self._injector.on_warmup(self, size)
            pk = self._synthetic_packed(size)
            np.asarray(self._server._dispatch_packed(pk))
            best = float("inf")
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                np.asarray(self._server._dispatch_packed(pk))
                best = min(best, time.perf_counter() - t0)
            out[size] = best
        return out

    # ------------------------------------------------------------ plumbing

    def resolved_config(self) -> dict:
        """The engine's effective program-shape config — bucket ladder,
        in-flight window, and (when the server exposes its resolution,
        ``DPF.resolved_eval_knobs``) the eval knobs of the cap-size
        program.  Benchmark records embed this so every BENCH_* file is
        self-describing about what actually ran."""
        d = {"buckets": list(self.buckets.sizes),
             "max_in_flight": self.max_in_flight}
        rk = getattr(self._server, "resolved_eval_knobs", None)
        if callable(rk):
            try:
                d.update(rk(self.buckets.max))
            except Exception as e:  # diagnostics must never break
                # serving — but the cause stays diagnosable
                note_swallowed("serve.engine.resolved_config", e,
                               self.stats)
        return d

    def _check_deadline(self):
        # monotonic, not wall-clock: an NTP step must neither fire the
        # deadline spuriously nor starve it forever
        if self.deadline is not None and time.monotonic() > self.deadline:
            self.stats.deadline_misses += 1
            ev = dict(engine=self.label or "engine",
                      pending=len(self._pending),
                      in_flight=len(self._queue))
            if self.tenant is not None:
                ev["tenant"] = self.tenant
            FLIGHT.record("deadline", **ev)
            raise DeadlineExceeded(
                "serving-engine deadline passed between dispatches")

    def _admit(self, n_queries: int):
        """Admission control, before any decode/dispatch work.

        Two triggers: the pending-future queue at ``max_queue_depth``,
        or (``slo_s`` set) the ring's p99 latency estimate over the SLO
        while a backlog exists.  ``shed=True`` rejects (``LoadShed``);
        otherwise the engine blocks on the oldest pending future until
        the queue is back under the bound (the p99 trigger never
        blocks — waiting would only worsen the latency it guards).
        """
        over_depth = (self.max_queue_depth is not None
                      and len(self._pending) >= self.max_queue_depth)
        over_slo = False
        if self.slo_s is not None and (self._pending or self._queue):
            p99 = self.stats.p99
            over_slo = p99 is not None and p99 > self.slo_s
        if self.shed and (over_depth or over_slo):
            self.stats.shed_batches += 1
            self.stats.shed_queries += n_queries
            ev = dict(engine=self.label or "engine", batch=n_queries,
                      reason=("queue_depth" if over_depth
                              else "p99_over_slo"),
                      pending=len(self._pending),
                      p99=self.stats.p99, slo_s=self.slo_s)
            if self.tenant is not None:
                ev["tenant"] = self.tenant
            FLIGHT.record("shed", **ev)
            raise LoadShed(
                "admission control rejected the batch (%s; pending=%d, "
                "p99=%s, slo_s=%s)"
                % ("queue depth" if over_depth else "p99 over SLO",
                   len(self._pending), self.stats.p99, self.slo_s))
        while (self.max_queue_depth is not None
               and len(self._pending) >= self.max_queue_depth):
            self._check_deadline()
            self._resolve_through(self._pending[0])

    @property
    def in_flight(self) -> int:
        return len(self._queue)

    def __repr__(self):
        return ("ServingEngine(n=%d, buckets=%s, max_in_flight=%d, "
                "served=%d)" % (self._n, list(self.buckets.sizes),
                                self.max_in_flight,
                                self.stats.queries_submitted))
