"""Observability benchmark: joint trace digest, OpenMetrics export,
flight-recorder fault attribution, and the tracing overhead bound.

``benchmark.py --trace``.  Four legs over one tuned serving shape
(entries=4096, entry_size=16, cap=128 — the PR-6 load-bench point),
committed as ``BENCH_TRACE_r12.json``:

* **profile** — a short closed-loop burst through the cost-model
  router with BOTH capture layers on: the host span tracer
  (``obs.tracer``) and a ``jax.profiler`` device trace of the same
  run.  The record embeds ``joint_digest`` — host span self-times
  merged with device op self-times — the one digest that says where a
  served batch's time went on each side of the dispatch boundary.
* **openmetrics** — the full OpenMetrics text exposition after that
  traffic: per-engine counters + latency histogram, per-construction
  breaker state, the router's EWMA cost table, routing provenance.
  The gate asserts the engine/router/breaker families are present.
* **chaos flight** — a replay slice under a seeded fault plan
  (``serve.faults``) through ``submit_resilient``; the flight
  recorder's ring is then JOINED on the arrival index: every injected
  fault event must attribute back to the route decision that placed
  its batch (construction + arrival match).  The gate asserts ≥ 1
  attributed fault — the attribution story, demonstrated end to end.
* **overhead** — the whole observability stack's cost: the identical
  closed-loop replay of the PR-6 bursty trace (seed 11), tracing OFF
  vs ON (spans recording into the ring), measured as adjacent paired
  segment replays and scored by the median paired delta (ambient host
  load swings far more than the effect under test).  The gate bounds
  the delta at 2% — observability cheap enough to leave on in
  production.

The replay here is CLOSED-loop (back-to-back, in arrival order) where
the load bench is open-loop: an open-loop replay's qps is bound by the
arrival schedule, which would hide any tracing overhead entirely —
back-to-back submission is the honest denominator.

  JAX_PLATFORMS=cpu \
      python benchmark.py --trace [--dryrun] [--out FILE]
"""

from __future__ import annotations

import json
import time
from collections import deque

import numpy as np

from ..serve import loadgen
from ..serve.bench_load import _batch_for, _key_pool
from ..utils.profiling import trace as profiler_trace
from . import tracer as obs_tracer
from .flight import FLIGHT, flight_dump
from .metrics import REGISTRY
from .tracer import joint_digest

#: OpenMetrics families the gate requires (engine / router / breaker /
#: flight coverage — the first-class series the ISSUE names)
REQUIRED_FAMILIES = (
    "dpf_engine_batches_submitted_total",
    "dpf_engine_latency_seconds_bucket",
    "dpf_router_cost_seconds",
    "dpf_router_routed_from_total",
    "dpf_breaker_state",
    "dpf_flight_events_total",
)


def _closed_loop(submit, sizes, *, window: int = 8) -> float:
    """Back-to-back replay of ``sizes`` through ``submit(j, b)``
    (returns a future); returns the makespan in seconds."""
    t0 = time.perf_counter()
    outstanding = deque()
    for j, b in enumerate(sizes):
        while len(outstanding) >= window:
            outstanding.popleft().result()
        outstanding.append(submit(j, b))
    while outstanding:
        outstanding.popleft().result()
    return time.perf_counter() - t0


def _router_submit(router, pools):
    def submit(j, b):
        dec = router.route(b)
        keys, _ = _batch_for(pools[dec.construction], j, b)
        return router.submit(dec, keys)
    return submit


def _attribute_faults(events) -> list:
    """Join fault events to the route decision that placed their batch:
    same arrival index AND same construction.  Returns
    ``[{fault, route}]`` pairs — the attribution the flight recorder
    exists to answer."""
    routes = {}
    for e in events:
        if e["kind"] == "route" and "arrival" in e:
            routes[(e["arrival"], e["construction"])] = e
    out = []
    for e in events:
        if e["kind"] != "fault":
            continue
        rt = routes.get((e["arrival"], e["construction"]))
        if rt is not None:
            out.append({"fault": e, "route": rt})
    return out


def trace_bench(n=4096, entry_size=16, cap=128, prf=0, *, seed=11,
                duration_s=7.0, on_rate=320.0, distinct=16, reps=3,
                window=8, profile_arrivals=48, constructions=None,
                trace_dir="/tmp/dpf_tpu_traces", overhead_gate=True,
                quiet=False) -> dict:
    """Run all four observability legs; returns the ``--trace`` record."""
    from ..serve.faults import FaultPlan, FaultSpec, RetryPolicy
    from ..serve.router import LABELS, SchemeRouter

    labels = tuple(constructions or LABELS)
    FLIGHT.clear()          # scope the ring to this bench
    table = np.random.default_rng(seed ^ 0x0b5).integers(
        0, 2 ** 31, (n, entry_size), dtype=np.int32, endpoint=False)
    # the PR-6 load-bench arrival process, replayed closed-loop
    arrivals = loadgen.bursty_trace(
        on_rate=on_rate, off_rate=2.0, on_s=1.0, off_s=2.0,
        duration_s=duration_s, cap=cap, seed=seed, n=n)
    sizes = loadgen.batch_sizes(arrivals)
    total_q = sum(sizes)

    router = SchemeRouter(table, prf=prf, cap=cap, probe=True,
                          constructions=labels)
    pools = {lb: _key_pool(router.server(lb), n, distinct,
                           b"trace-%s" % lb.encode()) for lb in labels}
    submit = _router_submit(router, pools)

    # ---- leg 1: joint host+device profile over a short burst ---------
    t = obs_tracer.enable()
    t.clear()
    cfg = "obs_trace_n%d_e%d_cap%d" % (n, entry_size, cap)
    with profiler_trace(cfg, base_dir=trace_dir) as tdir:
        _closed_loop(submit, sizes[:profile_arrivals], window=window)
    joint = joint_digest(tracer=t, trace_dir=tdir)
    host_spans = {s["span"] for s in
                  (joint["host"] or {}).get("top_spans", ())}
    spans_jsonl = "%s/host_spans.jsonl" % tdir
    chrome_json = "%s/host_spans.chrome.json" % tdir
    t.export_jsonl(spans_jsonl)
    t.export_chrome(chrome_json)     # open next to the device trace in
    #                                  Perfetto (docs/OBSERVABILITY.md)
    obs_tracer.disable()

    # ---- leg 2: the OpenMetrics exposition after that traffic --------
    text = REGISTRY.openmetrics()
    families_present = {f: (("\n%s" % f) in ("\n" + text))
                        for f in REQUIRED_FAMILIES}

    # ---- leg 3: chaos slice -> flight-recorder fault attribution -----
    plan = FaultPlan([
        # max_fires < the retry policy's max_attempts: one arrival can
        # absorb every remaining fire and still succeed on its last
        # attempt, so the chaos slice never fails a batch outright
        FaultSpec(kind="dispatch_error", start=2, stop=24, p=0.5,
                  max_fires=3),
        FaultSpec(kind="latency", start=4, stop=24, p=0.25,
                  latency_s=0.005, max_fires=4),
    ], seed=seed)
    inj = plan.injector()
    chaos_router = SchemeRouter(
        None, servers={lb: router.server(lb) for lb in labels},
        cap=cap, probe=True, injector=inj,
        retry=RetryPolicy(max_attempts=4, backoff_s=0.001, seed=seed))
    flight_mark = FLIGHT.recorded

    def chaos_submit(j, b):
        inj.begin_arrival(j)
        return chaos_router.submit_resilient(
            b, lambda lb: _batch_for(pools[lb], j, b)[0])
    chaos_sizes = sizes[:max(24, profile_arrivals)]
    _closed_loop(chaos_submit, chaos_sizes, window=window)
    chaos_events = [e for e in flight_dump()
                    if e["seq"] > flight_mark]
    attributed = _attribute_faults(chaos_events)

    # ---- leg 4: tracing-on vs tracing-off qps (closed loop) ----------
    # one untimed full pass first (the earlier legs only touched a
    # prefix of the trace, so the first timed measurement would
    # otherwise eat the remaining bucket warmup).  Ambient load on a
    # shared host swings whole seconds between passes — far more than
    # the sub-percent effect under test — so only measurements taken
    # BACK-TO-BACK are comparable: the replay is split into contiguous
    # segments, each segment timed as an adjacent (off, on) pair with
    # the leg order alternating, and the score is the MEDIAN of the
    # paired relative deltas (drops the pairs a load spike still split).
    _closed_loop(submit, sizes, window=window)

    def timed(tracing_on: bool, seg) -> float:
        if tracing_on:
            obs_tracer.enable()
        else:
            obs_tracer.disable()
        try:
            return _closed_loop(submit, seg, window=window)
        finally:
            obs_tracer.disable()
    nseg = min(12, max(1, len(sizes) // 8))
    bounds = [i * len(sizes) // nseg for i in range(nseg + 1)]
    segments = [sizes[a:b] for a, b in zip(bounds, bounds[1:]) if b > a]
    deltas, mk_off, mk_on = [], 0.0, 0.0
    pair = 0
    for _ in range(max(1, reps)):
        for seg in segments:
            t = {}
            for on in ((False, True) if pair % 2 == 0
                       else (True, False)):
                t[on] = timed(on, seg)
            pair += 1
            mk_off += t[False]
            mk_on += t[True]
            deltas.append((t[True] - t[False]) / t[False] * 100.0)
    deltas.sort()
    mid = len(deltas) // 2
    median_pct = (deltas[mid] if len(deltas) % 2
                  else (deltas[mid - 1] + deltas[mid]) / 2.0)
    # makespans are per-leg SUMS over every pair (reps full replays)
    mk_off /= max(1, reps)
    mk_on /= max(1, reps)
    qps_off = int(total_q / mk_off)
    qps_on = int(total_q / mk_on)
    overhead_pct = round(median_pct, 3)

    record = {
        "metric": "end-to-end serving observability: per-batch span "
                  "tracing + jax.profiler joint digest, OpenMetrics "
                  "export, flight-recorder fault attribution, and the "
                  "full-stack tracing overhead (entries=%d, "
                  "entry_size=%d, prf=%d, cap=%d, closed-loop replay "
                  "of the seeded bursty trace: %d arrivals / %d "
                  "queries, 1 device)"
                  % (n, entry_size, prf, cap, len(sizes), total_q),
        "value": overhead_pct,
        "unit": "percent makespan overhead, tracing on vs off (median "
                "of paired adjacent segment replays)",
        "vs_baseline": round(qps_on / qps_off, 4) if qps_off else None,
        "baseline": "the identical closed-loop replay with the span "
                    "tracer disabled (flight recorder + counters stay "
                    "on in both legs — they are always-on)",
        "trace": {"kind": "bursty", "seed": seed,
                  "duration_s": duration_s, "on_rate": on_rate,
                  "arrivals": len(sizes), "queries": total_q,
                  "cap": cap, "reps": reps, "window": window},
        "constructions": list(labels),
        "profile": {
            "config": cfg,
            "arrivals": profile_arrivals,
            "joint_digest": joint,
            "host_spans_jsonl": spans_jsonl,
            "host_spans_chrome": chrome_json,
        },
        "openmetrics": {
            "families_required": dict(families_present),
            "lines": len(text.splitlines()),
            "text": text,
        },
        "chaos_flight": {
            "plan": plan.as_dict(),
            "injected": dict(inj.injected),
            "events": len(chaos_events),
            "attributed_faults": len(attributed),
            "attribution_sample": attributed[:4],
            "flight_tail": chaos_events[-48:],
        },
        "overhead": {
            "qps_tracing_off": qps_off,
            "qps_tracing_on": qps_on,
            "makespan_off_s": round(mk_off, 4),
            "makespan_on_s": round(mk_on, 4),
            "segments": len(segments),
            "pairs": pair,
            "paired_deltas_pct": [round(d, 3) for d in deltas],
            "overhead_pct": overhead_pct,
            "bound_pct": 2.0,
            # the dryrun's segments are tens of ms — far below what the
            # paired estimator can resolve — so it measures but does
            # not gate ("no perf claims")
            "gated": bool(overhead_gate),
        },
        "checked": bool(
            joint["host"] is not None
            and {"submit", "dispatch"} <= host_spans
            and joint["device"] is not None
            and joint["device"]["device_ms"] > 0
            and all(families_present.values())
            and len(attributed) >= 1
            and (not overhead_gate or overhead_pct <= 2.0)),
    }
    if not quiet:
        print(json.dumps(record), flush=True)
    return record


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
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--trace-dir", default="/tmp/dpf_tpu_traces")
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny trace/table smoke (CI): exercises every "
                         "leg in seconds, makes no perf claims")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    if args.dryrun:
        record = trace_bench(n=512, entry_size=8, cap=16, prf=args.prf,
                             seed=args.seed, duration_s=1.5,
                             on_rate=30.0, distinct=8, reps=1,
                             profile_arrivals=12,
                             constructions=("logn", "radix4"),
                             trace_dir=args.trace_dir,
                             overhead_gate=False)
    else:
        record = trace_bench(n=args.n, entry_size=args.entry_size,
                             cap=args.cap, prf=args.prf, seed=args.seed,
                             duration_s=args.duration,
                             on_rate=args.on_rate, reps=args.reps,
                             trace_dir=args.trace_dir)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record


if __name__ == "__main__":
    main()
