"""Open-loop load benchmark: cost-model scheme router vs sticky baseline.

``benchmark.py --load``.  Replays one seeded bursty mixed-shape arrival
trace (``serve/loadgen.py``) through two serving stacks over the same
table and reports full SLO accounting for each:

* **sticky** — one ``ServingEngine`` over the construction a
  ``DPF(scheme="auto")`` deployment would pin: the cached
  ``--autotune-scheme`` winner when the tuning cache is warm, else the
  conservative heuristic (binary GGM).  This is today's production
  path.
* **router** — ``serve.router.SchemeRouter``: per-arrival construction
  choice by the live cost model (probe-seeded, EWMA-updated).

The replay is **open-loop**: arrivals fire at their scheduled
timestamps whether or not the server kept up, so a stack slower than
the offered load accumulates a backlog and its latencies grow — per-
arrival latency is measured completion − *scheduled arrival*, the
client's-eye SLO number.  The trace's burst rate is chosen to exceed
the sticky construction's service capacity while staying under the
router's, which is exactly the regime the ROADMAP item names ("bursty,
heavy-tailed arrivals"): the sticky stack falls behind during bursts
(qps capped at its capacity, p99 inflated by queueing) while the
router absorbs them.

**Every routed answer is equality-gated against the scalar oracle**:
each pool key's reference share is computed once via ``DPF.eval_cpu``
(the host NumPy/native path) and every served batch — sticky and
routed — must match its reference rows bit-exactly; rejections are
counted in the record (an acceptance criterion is 0).

A third **shed leg** re-runs the router with admission control armed
(``slo_s`` + ``max_queue_depth``, ``shed=True``) under a deliberately
overloading trace, demonstrating bounded p99 at the cost of counted
sheds.  The committed CPU record is ``BENCH_LOAD_r10.json``; the same
command produces the TPU record.

  JAX_PLATFORMS=cpu \
      python benchmark.py --load [--dryrun] [--out FILE]
"""

from __future__ import annotations

import json
import time
from collections import deque

import numpy as np

from ..obs import FLIGHT, record_sections
from ..obs.tracer import span
from ..utils.profiling import quantile
from .engine import LoadShed, ServingEngine
from . import loadgen


def _key_pool(srv, n: int, distinct: int, tag: bytes):
    """``distinct`` server-0 keys for ``srv`` + their scalar-oracle
    reference shares (one ``eval_cpu`` call — the host NumPy/native
    path, the same oracle every tuner gate uses)."""
    keys = [srv.gen((i * 0x9E3779B1) % n, n, seed=tag + b"-%d" % i)[0]
            for i in range(distinct)]
    refs = np.asarray(srv.eval_cpu(keys))      # [distinct, E]
    return keys, refs


def _batch_for(pool, j: int, b: int):
    """Deterministic rotating view of the key pool: arrival j's batch
    of b keys and their pool indices (for the reference lookup)."""
    keys, _ = pool
    idxs = [(j + i) % len(keys) for i in range(b)]
    return [keys[i] for i in idxs], idxs


def replay(trace, submit, *, window: int = 8):
    """Open-loop replay of ``trace`` through ``submit(arrival, j)``.

    ``submit`` returns a future (``.result()``) or raises ``LoadShed``.
    Arrivals are released at their scheduled ``t`` (sleeping when
    ahead; when behind, back-to-back — the backlog is the server's
    problem, as in production).  While ahead of schedule the replay
    resolves outstanding futures (the polling client), and never holds
    more than ``window`` unresolved — per-arrival latency is
    completion − scheduled arrival, in seconds.

    One honesty note: the client is single-threaded, so a blocking
    ``result()`` in the idle gap can delay a later arrival's submit
    past its schedule.  The delay still lands in the MEASURED latency
    (which is against the scheduled time, not the actual submit), and
    both race legs replay through this identical loop, so the
    comparison is fair — but shed counts under overload are a floor
    (a threaded client would have offered, and shed, sooner).

    Returns ``(latencies, per_arrival, makespan_s, shed_batches,
    shed_queries)`` where ``per_arrival`` is ``(arrival, j, future)``
    for the equality gate (shed arrivals excluded).
    """
    t0 = time.perf_counter()
    outstanding = deque()               # (arrival, j, fut)
    done = []                           # (arrival, j, fut)
    lats = []
    sheds = shed_q = 0

    def resolve_oldest():
        a, j, fut = outstanding.popleft()
        fut.result()
        lats.append((time.perf_counter() - t0) - a.t)
        done.append((a, j, fut))

    for j, a in enumerate(trace):
        while True:
            now = time.perf_counter() - t0
            if now >= a.t:
                break
            if outstanding:             # use the idle gap to poll
                resolve_oldest()
            else:
                time.sleep(min(a.t - now, 0.02))
        while len(outstanding) >= window:
            resolve_oldest()
        try:
            fut = submit(a, j)
        except LoadShed:
            sheds += 1
            shed_q += a.batch
            continue
        outstanding.append((a, j, fut))
    while outstanding:
        resolve_oldest()
    return lats, done, time.perf_counter() - t0, sheds, shed_q


def _slo_stats(lats, slo_s: float) -> dict:
    if not lats:    # empty trace / everything shed: report, don't crash
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None,
                "max_ms": None, "deadline_miss_batches": 0,
                "deadline_miss_rate": 0.0}
    ms = sorted(x * 1e3 for x in lats)
    miss = sum(1 for x in lats if x > slo_s)
    return {
        "p50_ms": round(quantile(ms, 0.50, presorted=True), 3),
        "p95_ms": round(quantile(ms, 0.95, presorted=True), 3),
        "p99_ms": round(quantile(ms, 0.99, presorted=True), 3),
        "max_ms": round(ms[-1], 3),
        "deadline_miss_batches": miss,
        "deadline_miss_rate": round(miss / len(lats), 4),
    }


def _gate(done, pools, label_of) -> int:
    """Bit-exact equality of every served batch against the scalar-
    oracle reference rows; returns the rejection count."""
    rejections = 0
    with span("gate", batches=len(done)):
        for a, j, fut in done:
            label = label_of(fut)
            _, refs = pools[label]
            _, idxs = _batch_for(pools[label], j, a.batch)
            if not np.array_equal(fut.result(), refs[idxs]):
                rejections += 1
    return rejections


def load_bench(n=4096, entry_size=16, cap=128, prf=0, *,
               trace=None, seed=11, duration_s=7.0, on_rate=320.0,
               slo_ms=250.0, reps=2, distinct=16, window=8,
               shed_leg=True, quiet=False) -> dict:
    """Race the cost-model router against the sticky baseline on one
    seeded open-loop bursty trace; returns the ``--load`` record."""
    from .router import LABELS, SchemeRouter, resolve_sticky

    FLIGHT.clear()      # scope the embedded flight tail to this bench
    table = np.random.default_rng(seed ^ 0x10ad).integers(
        0, 2 ** 31, (n, entry_size), dtype=np.int32, endpoint=False)
    if trace is None:
        trace = loadgen.bursty_trace(
            on_rate=on_rate, off_rate=2.0, on_s=1.0, off_s=2.0,
            duration_s=duration_s, cap=cap, seed=seed, n=n)
    total_q = loadgen.total_queries(trace)
    slo_s = slo_ms / 1e3

    # ---- stacks: router (3 constructions) + sticky single engine ----
    router = SchemeRouter(table, prf=prf, cap=cap, probe=True)
    # the ONE sticky-resolution rule, shared with the router's fallback
    sticky_label, sticky_from = resolve_sticky(n, entry_size, prf, cap)
    sticky_srv = router.server(sticky_label)     # same table upload
    sticky_engine = ServingEngine(sticky_srv, max_in_flight=2,
                                  buckets=router.buckets, warmup=True)
    pools = {lb: _key_pool(router.server(lb), n, distinct,
                           b"load-%s" % lb.encode())
             for lb in LABELS}

    def sticky_submit(a, j):
        keys, _ = _batch_for(pools[sticky_label], j, a.batch)
        return sticky_engine.submit(keys)

    def router_submit(a, j):
        dec = router.route(a.batch)
        keys, _ = _batch_for(pools[dec.construction], j, a.batch)
        return router.submit(dec, keys)

    def run_leg(submit, reset, stats_fn) -> tuple:
        """Best-qps rep; ``stats_fn()`` is snapshotted per rep so the
        record's counters describe the SAME run as its qps/latencies."""
        best = None
        for _ in range(max(1, reps)):
            reset()
            lats, done, makespan, sheds, shed_q = replay(
                trace, submit, window=window)
            qps = int((total_q - shed_q) / makespan)
            if best is None or qps > best[0]:
                best = (qps, lats, done, makespan, stats_fn())
        return best

    # ---- sticky leg --------------------------------------------------
    q_s, lats_s, done_s, mk_s, stats_s = run_leg(
        sticky_submit, sticky_engine.stats.reset,
        lambda: sticky_engine.stats.as_dict())
    sticky_leg = {
        "construction": sticky_label, "resolved_from": sticky_from,
        "qps": q_s, "makespan_s": round(mk_s, 4),
        "served_queries": total_q,
        **_slo_stats(lats_s, slo_s),
        "engine_stats": stats_s,
    }

    # ---- router leg --------------------------------------------------
    q_r, lats_r, done_r, mk_r, stats_r = run_leg(
        router_submit, router.reset_counters, router.stats)
    router_leg = {
        "qps": q_r, "makespan_s": round(mk_r, 4),
        "served_queries": total_q,
        **_slo_stats(lats_r, slo_s),
        "router_stats": stats_r,
    }

    # ---- shed leg first: its served batches are gated too ------------
    shed_rec = None
    if shed_leg:
        servers = {lb: router.server(lb) for lb in router.constructions}
        shed_rec = _shed_leg(servers, cap, trace, pools, slo_s, window)

    # ---- equality gate (post-timing; futures cache their results) ----
    rejections = _gate(done_s, pools, lambda f: sticky_label)
    rejections += _gate(done_r, pools,
                        lambda f: f.decision.construction)
    if shed_rec is not None:
        rejections += shed_rec["gate_rejections"]

    record = {
        "metric": "traffic-shaped serving: cost-model scheme router vs "
                  "sticky cached-winner engine (entries=%d, "
                  "entry_size=%d, prf=%d, bursty open-loop trace: %d "
                  "arrivals / %d queries, cap=%d, slo=%dms, 1 device)"
                  % (n, entry_size, prf, len(trace), total_q, cap,
                     int(slo_ms)),
        "value": q_r,
        "unit": "queries/sec",
        "vs_baseline": round(q_r / q_s, 4) if q_s else None,
        "baseline": "sticky-scheme ServingEngine (the DPF(scheme="
                    "'auto') resolution: cached --autotune-scheme "
                    "winner, else the binary-GGM heuristic) on the "
                    "identical seeded trace and key pools",
        "p99_vs_baseline": round(router_leg["p99_ms"]
                                 / sticky_leg["p99_ms"], 4)
        if sticky_leg["p99_ms"] and router_leg["p99_ms"] is not None
        else None,
        "slo_ms": slo_ms,
        "trace": {"kind": "bursty", "seed": seed,
                  "duration_s": duration_s, "on_rate": on_rate,
                  "arrivals": len(trace), "queries": total_q,
                  "cap": cap, "reps": reps, "window": window},
        "sticky": sticky_leg,
        "router": router_leg,
        # the live EWMA cost model after the race — the digital twin's
        # service-time input (plan/twin.py); embedding it makes every
        # downstream twin run auditable against this record
        "cost_table": router.cost_table(),
        "gate_rejections": rejections,
        "checked": rejections == 0,  # every served batch matched the
        #                              scalar oracle (DPF.eval_cpu)
    }

    if shed_rec is not None:
        record["shed_leg"] = shed_rec
    record["obs"] = record_sections()
    if not quiet:
        print(json.dumps(record), flush=True)
    return record


def _shed_leg(servers, cap, trace, pools, slo_s, window) -> dict:
    """Router with admission control armed on a compressed (4x rate)
    copy of the trace — offered load well past even the router's
    capacity: p99 of ADMITTED arrivals stays bounded, the overload
    shows up as counted sheds instead of unbounded queueing.  Reuses
    the main router's prepared servers (no second table upload /
    warmup compile — the engines' admission knobs are the only
    difference)."""
    from .router import SchemeRouter
    router = SchemeRouter(None, servers=servers, cap=cap, probe=True,
                          slo_s=slo_s, max_queue_depth=max(2, window // 2),
                          shed=True)
    squeezed = loadgen.squeeze(trace, 4.0)

    def submit(a, j):
        dec = router.route(a.batch)
        keys, _ = _batch_for(pools[dec.construction], j, a.batch)
        return router.submit(dec, keys)

    lats, done, makespan, sheds, shed_q = replay(squeezed, submit,
                                                 window=window)
    counters = router.counters()
    return {
        "qps_admitted": int((loadgen.total_queries(squeezed) - shed_q)
                            / makespan),
        "makespan_s": round(makespan, 4),
        "shed_batches": sheds, "shed_queries": shed_q,
        **_slo_stats(lats, slo_s),
        "engine_shed_batches": counters.shed_batches,
        "slo_s": slo_s,
        # the ADMITTED batches are gated like the main legs (the
        # docstring's every-served-batch promise includes this leg)
        "gate_rejections": _gate(done, pools,
                                 lambda f: f.decision.construction),
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--entry-size", type=int, default=16)
    ap.add_argument("--cap", type=int, default=128)
    ap.add_argument("--prf", type=int, default=0,
                    help="PRF id (default 0=DUMMY; 2=ChaCha20, "
                         "3=AES128)")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--duration", type=float, default=7.0,
                    help="trace duration in seconds")
    ap.add_argument("--on-rate", type=float, default=320.0,
                    help="burst arrival rate (arrivals/sec in ON "
                         "windows)")
    ap.add_argument("--slo-ms", type=float, default=250.0)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--no-shed-leg", action="store_true")
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny trace/table smoke (CI): exercises every "
                         "leg in seconds, makes no perf claims")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    if args.dryrun:
        record = load_bench(n=512, entry_size=8, cap=16, prf=args.prf,
                            seed=args.seed, duration_s=1.5,
                            on_rate=30.0, slo_ms=args.slo_ms, reps=1,
                            distinct=8, shed_leg=not args.no_shed_leg)
    else:
        record = load_bench(n=args.n, entry_size=args.entry_size,
                            cap=args.cap, prf=args.prf, seed=args.seed,
                            duration_s=args.duration,
                            on_rate=args.on_rate, slo_ms=args.slo_ms,
                            reps=args.reps,
                            shed_leg=not args.no_shed_leg)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record


if __name__ == "__main__":
    main()
