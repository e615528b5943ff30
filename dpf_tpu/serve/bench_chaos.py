"""Chaos benchmark: availability under seeded fault injection.

``benchmark.py --chaos``.  Replays the SAME seeded bursty trace the
load benchmark uses (``serve/loadgen.py``) through a fault-tolerant
router stack (``SchemeRouter`` + ``RetryPolicy`` + per-construction
circuit breakers + ``EngineSupervisor``) under escalating fault plans
(``serve/faults.py``):

* **baseline** — no faults: the availability reference for this
  machine/trace (what the recovery legs must stay close to).
* **faults**   — ≥10% injected dispatch failures across every
  construction, latency spikes, and silently corrupted result shares.
* **chaos**    — the faults leg PLUS a full engine death: the
  cost-model favorite construction is killed mid-trace; its traffic
  must fail over to the healthy engines over the same table while the
  supervisor rebuilds it in the background and the circuit breaker
  walks open → half-open → closed.

**Availability** is the correct-within-SLO fraction: an arrival counts
only if its batch was served, bit-gated against the scalar oracle
(``DPF.eval_cpu`` reference shares, checked inline before the client
accepts the answer), and completed within the SLO measured from its
*scheduled* arrival time.  The inline gate doubles as the corruption
detector: every injected share corruption must be caught and the batch
re-served (``corruptions_detected`` == injected, ``gate_escapes`` ==
0), proving the equality gate is an integrity check, not just a test
assertion.

Every injection decision is deterministic under the plan seed (see
``faults.FaultInjector``), so the committed record —
``BENCH_CHAOS_r11.json`` — replays the identical fault sequence on the
identical trace.

  JAX_PLATFORMS=cpu \
      python benchmark.py --chaos [--dryrun] [--out FILE]
"""

from __future__ import annotations

import json
import time

import numpy as np

from ..core.expand import DeadlineExceeded
from ..obs import FLIGHT, flight_dump, record_sections
from ..utils.profiling import swallowed_snapshot
from .bench_load import _batch_for, _key_pool, _slo_stats, replay
from .engine import LoadShed
from .faults import FaultPlan, FaultSpec, RetryPolicy
from . import loadgen


class _FailedBatch:
    """Future-shaped sentinel for an arrival whose serve attempts were
    exhausted: the replay loop resolves it like any future, the
    availability accounting counts it unavailable."""
    ok = False

    def done(self) -> bool:
        return True

    def result(self):
        return None


class _VerifiedFuture:
    """A routed future whose ``result()`` is the full client protocol:
    resolve, bit-gate against the scalar-oracle references, and on a
    failed gate (an injected corruption) or a resolve-time fault,
    RE-SERVE the batch through ``SchemeRouter.submit_resilient`` — up
    to ``client.max_reserves`` times.  The re-serve cost lands in the
    measured latency (against the scheduled arrival), so corruption
    recovery is paid for inside the availability number, not hidden."""

    __slots__ = ("client", "a", "j", "fut", "ok", "_value")

    def __init__(self, client, a, j, fut):
        self.client = client
        self.a = a
        self.j = j
        self.fut = fut
        self.ok = None
        self._value = None

    def done(self) -> bool:
        return self.ok is not None or self.fut.done()

    def result(self):
        if self.ok is not None:
            return self._value
        c = self.client
        out = None
        for attempt in range(c.max_reserves + 1):
            try:
                out = np.asarray(self.fut.result())
            except (LoadShed, DeadlineExceeded):
                raise
            except Exception:
                out = None
            if out is not None:
                lb = self.fut.decision.construction
                _, idxs = _batch_for(c.pools[lb], self.j, self.a.batch)
                if np.array_equal(out, c.pools[lb][1][idxs]):
                    self.ok = True
                    self._value = out
                    return out
                c.detected_corruptions += 1
            if attempt >= c.max_reserves:
                break
            c.reserves += 1
            try:
                self.fut = c.router.submit_resilient(
                    self.a.batch, c.keys_for(self.j, self.a.batch))
            except Exception:
                break
        self.ok = False
        self._value = out
        c.failed_batches += 1
        return out


class _ChaosClient:
    """The submit side of one chaos leg: routes every arrival through
    ``submit_resilient`` (retry + failover) and wraps the future in the
    verify-and-reserve protocol above."""

    def __init__(self, router, pools, injector, *, max_reserves=3):
        self.router = router
        self.pools = pools
        self.injector = injector
        self.max_reserves = max_reserves
        self.detected_corruptions = 0
        self.failed_batches = 0
        self.reserves = 0

    def keys_for(self, j, b):
        return lambda lb: _batch_for(self.pools[lb], j, b)[0]

    def submit(self, a, j):
        if self.injector is not None:
            self.injector.begin_arrival(j)
        try:
            fut = self.router.submit_resilient(a.batch,
                                               self.keys_for(j, a.batch))
        except (LoadShed, DeadlineExceeded):
            raise
        except Exception:
            self.failed_batches += 1
            return _FailedBatch()
        return _VerifiedFuture(self, a, j, fut)


def _fault_specs(*, dispatch_p: float, latency_p: float,
                 latency_s: float, corrupt_p: float) -> list:
    return [
        FaultSpec(kind="dispatch_error", p=dispatch_p),
        FaultSpec(kind="latency", p=latency_p, latency_s=latency_s),
        FaultSpec(kind="corrupt_shares", p=corrupt_p),
    ]


def _favorite(router, cap: int) -> str:
    """The cost-model favorite at the cap bucket after probe seeding —
    the construction whose death hurts the most (its traffic is the
    argmin's first choice)."""
    costs = {lb: router.cost(lb, cap) for lb in router.constructions}
    known = {lb: c for lb, c in costs.items() if c is not None}
    return (min(known, key=known.get) if known
            else router.constructions[0])


def _run_leg(servers, cap, trace, pools, slo_s, window, plan, *,
             retry, breaker_failures, breaker_reset_s,
             reclose_wait_s=10.0) -> dict:
    """One replay of ``trace`` under ``plan`` through a fresh
    fault-tolerant router over the SHARED prepared servers; returns the
    leg record with availability + recovery accounting."""
    from .router import SchemeRouter
    inj = plan.injector() if plan is not None else None
    router = SchemeRouter(None, servers=servers, cap=cap, probe=True,
                          injector=inj, retry=retry,
                          breaker_failures=breaker_failures,
                          breaker_reset_s=breaker_reset_s,
                          supervise=True)
    client = _ChaosClient(router, pools, inj)
    lats, done, makespan, _, _ = replay(trace, client.submit,
                                        window=window)
    router.drain()
    if router.supervisor is not None:
        router.supervisor.join(timeout=reclose_wait_s)
    # give every still-open breaker its half-open re-probe: the routing
    # path itself is the recovery check, so route until settled (the
    # chaos leg's killed construction must re-close here at the latest
    # — usually it already did mid-trace)
    deadline = time.monotonic() + reclose_wait_s
    while (any(br.state != "closed" for br in router.breakers.values())
           and time.monotonic() < deadline):
        router.route(1)
        time.sleep(min(0.05, breaker_reset_s / 4))

    # ---- availability: correct-within-SLO over ALL trace arrivals ----
    # done[i] and lats[i] are appended together by the replay loop
    ok_in_slo = sum(1 for (_, _, fut), lat in zip(done, lats)
                    if getattr(fut, "ok", False) and lat <= slo_s)
    escapes = 0
    for a, j, fut in done:      # re-gate final values: escapes must be 0
        if not getattr(fut, "ok", False):
            continue
        lb = fut.fut.decision.construction
        _, idxs = _batch_for(pools[lb], j, a.batch)
        if not np.array_equal(fut.result(), pools[lb][1][idxs]):
            escapes += 1
    counters = router.counters()
    total = len(trace)
    rec = {
        "availability": round(ok_in_slo / total, 4) if total else None,
        "served_ok": ok_in_slo,
        "arrivals": total,
        "failed_batches": client.failed_batches,
        "reserves_after_gate": client.reserves,
        "makespan_s": round(makespan, 4),
        "qps": int(loadgen.total_queries(trace) / makespan)
        if makespan else None,
        **_slo_stats(lats, slo_s),
        "recovery": {
            "retries": counters.retries,
            "failovers": counters.failovers,
            "breaker_opens": counters.breaker_opens,
            "engine_restarts": counters.engine_restarts,
            "swallowed_errors": counters.swallowed_errors,
        },
        "breakers": {lb: br.as_dict()
                     for lb, br in router.breakers.items()},
        "route_counts": dict(router.route_counts),
    }
    if inj is not None:
        rec["faults"] = {
            "plan": plan.as_dict(),
            "injected": dict(inj.injected),
            "corruptions_injected": len(inj.corruptions),
            "corruptions_detected": client.detected_corruptions,
        }
    rec["gate_escapes"] = escapes
    return rec, router


def chaos_bench(n=4096, entry_size=16, cap=128, prf=0, *,
                seed=11, duration_s=6.0, on_rate=60.0, slo_ms=1000.0,
                dispatch_p=0.12, latency_p=0.05, latency_s=0.02,
                corrupt_p=0.03, window=8, distinct=16,
                breaker_failures=2, breaker_reset_s=0.4,
                quiet=False) -> dict:
    """Escalating fault plans over one seeded bursty trace; returns the
    ``--chaos`` record (``BENCH_CHAOS_r11.json``)."""
    from .router import LABELS, build_servers

    FLIGHT.clear()      # scope the embedded flight tail to this bench
    table = np.random.default_rng(seed ^ 0xc4a05).integers(
        0, 2 ** 31, (n, entry_size), dtype=np.int32, endpoint=False)
    trace = loadgen.bursty_trace(
        on_rate=on_rate, off_rate=2.0, on_s=1.0, off_s=2.0,
        duration_s=duration_s, cap=cap, seed=seed, n=n)
    slo_s = slo_ms / 1e3
    retry = RetryPolicy(max_attempts=4, backoff_s=0.002, seed=seed)

    # one table upload + key pool + oracle reference per construction,
    # shared by every leg (the legs differ ONLY in their fault plan)
    servers = build_servers(table, LABELS, prf_method=prf)
    pools = {lb: _key_pool(servers[lb], n, distinct,
                           b"chaos-%s" % lb.encode())
             for lb in LABELS}
    leg_kw = dict(retry=retry, breaker_failures=breaker_failures,
                  breaker_reset_s=breaker_reset_s)

    # ---- leg 1: baseline (no faults) ---------------------------------
    baseline, _ = _run_leg(servers, cap, trace, pools, slo_s, window,
                           FaultPlan((), seed=seed), **leg_kw)

    # ---- leg 2: dispatch errors + stragglers + corrupted shares ------
    fault_plan = FaultPlan(_fault_specs(
        dispatch_p=dispatch_p, latency_p=latency_p,
        latency_s=latency_s, corrupt_p=corrupt_p), seed=seed)
    faults_leg, fr = _run_leg(servers, cap, trace, pools, slo_s,
                              window, fault_plan, **leg_kw)

    # ---- leg 3: + full engine death of the cost-model favorite -------
    victim = _favorite(fr, fr.buckets.max)
    kill_at = max(1, len(trace) // 3)
    chaos_plan = FaultPlan(_fault_specs(
        dispatch_p=dispatch_p, latency_p=latency_p,
        latency_s=latency_s, corrupt_p=corrupt_p)
        + [FaultSpec(kind="engine_death", construction=victim,
                     start=kill_at)], seed=seed)
    chaos_leg, cr = _run_leg(servers, cap, trace, pools, slo_s, window,
                             chaos_plan, **leg_kw)
    chaos_leg["victim"] = victim
    chaos_leg["killed_at_arrival"] = kill_at
    victim_states = [s for _, s in cr.breakers[victim].transitions]
    chaos_leg["victim_breaker_transitions"] = victim_states

    total_escapes = (baseline["gate_escapes"] + faults_leg["gate_escapes"]
                     + chaos_leg["gate_escapes"])
    record = {
        "metric": "fault-tolerant serving: availability (correct-"
                  "within-SLO fraction) under escalating seeded fault "
                  "plans — %.0f%% dispatch failures + stragglers + "
                  "corrupted shares + one engine death (entries=%d, "
                  "entry_size=%d, prf=%d, bursty trace: %d arrivals / "
                  "%d queries, cap=%d, slo=%dms, 1 device)"
                  % (dispatch_p * 100, n, entry_size, prf, len(trace),
                     loadgen.total_queries(trace), cap, int(slo_ms)),
        "value": chaos_leg["availability"],
        "unit": "availability",
        "vs_baseline": (round(chaos_leg["availability"]
                              / baseline["availability"], 4)
                        if baseline["availability"] else None),
        "baseline": "the identical router stack replaying the identical"
                    " seeded trace with no fault plan",
        "slo_ms": slo_ms,
        "trace": {"kind": "bursty", "seed": seed,
                  "duration_s": duration_s, "on_rate": on_rate,
                  "arrivals": len(trace),
                  "queries": loadgen.total_queries(trace),
                  "cap": cap, "window": window},
        "retry_policy": {"max_attempts": retry.max_attempts,
                         "backoff_s": retry.backoff_s,
                         "backoff_mult": retry.backoff_mult,
                         "jitter": retry.jitter, "seed": retry.seed},
        "breaker": {"failures": breaker_failures,
                    "reset_s": breaker_reset_s},
        "baseline_leg": baseline,
        "faults_leg": faults_leg,
        "chaos_leg": chaos_leg,
        "swallowed_errors": swallowed_snapshot(),
        "gate_escapes": total_escapes,
        "checked": bool(
            total_escapes == 0
            and chaos_leg["availability"] is not None
            and chaos_leg["availability"] >= 0.99
            and chaos_leg["recovery"]["engine_restarts"] >= 1
            and victim_states[-1] == "closed"),
    }
    record["obs"] = record_sections()
    if not record["checked"]:
        # a failed gate is exactly what the flight recorder exists to
        # diagnose: embed the FULL ring (route decisions, breaker walk,
        # every injected fault with its arrival join key)
        record["obs"]["flight_on_gate_failure"] = flight_dump()
        import sys
        print("chaos gate FAILED — full flight dump embedded in record"
              " (obs.flight_on_gate_failure, %d events)"
              % len(record["obs"]["flight_on_gate_failure"]),
              file=sys.stderr, flush=True)
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
    ap.add_argument("--duration", type=float, default=6.0,
                    help="trace duration in seconds")
    ap.add_argument("--on-rate", type=float, default=60.0,
                    help="burst arrival rate (arrivals/sec in ON "
                         "windows)")
    ap.add_argument("--slo-ms", type=float, default=1000.0)
    ap.add_argument("--dispatch-p", type=float, default=0.12,
                    help="per-dispatch injected failure probability")
    ap.add_argument("--corrupt-p", type=float, default=0.03,
                    help="per-batch injected share-corruption "
                         "probability")
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny trace/table smoke (CI): exercises every "
                         "leg in seconds, makes no perf claims")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    if args.dryrun:
        record = chaos_bench(n=512, entry_size=8, cap=16, prf=args.prf,
                             seed=args.seed, duration_s=1.5,
                             on_rate=20.0, slo_ms=args.slo_ms,
                             dispatch_p=args.dispatch_p,
                             corrupt_p=args.corrupt_p, distinct=8,
                             breaker_reset_s=0.2)
    else:
        record = chaos_bench(n=args.n, entry_size=args.entry_size,
                             cap=args.cap, prf=args.prf, seed=args.seed,
                             duration_s=args.duration,
                             on_rate=args.on_rate, slo_ms=args.slo_ms,
                             dispatch_p=args.dispatch_p,
                             corrupt_p=args.corrupt_p)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record


if __name__ == "__main__":
    main()
