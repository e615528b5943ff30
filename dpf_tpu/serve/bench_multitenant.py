"""Multi-tenant noisy-neighbor chaos bench: isolation under one process.

``benchmark.py --multitenant``.  Serves N tenants — distinct (N, E)
tables plus one tenant SHARING another's table and bucket ladder —
through one ``TenantRouter`` (``serve/tenant.py``) over one
``TableRegistry``, and measures per-tenant SLO attainment across three
legs over the same seeded open-loop traces:

1. **solo** — each tenant's trace replayed alone (the baseline every
   isolation tolerance is measured against).
2. **combined** — every tenant's trace merged by timestamp and
   replayed concurrently under the deficit-round-robin scheduler.
3. **noisy-neighbor chaos** — the victim tenant's trace is squeezed 4x
   (burst) AND its router runs a seeded ``FaultPlan`` (dispatch errors
   + an engine death).  The victim degrades — counted sheds, absorbed
   faults — while every OTHER tenant must hold availability 1.0 and
   p99 within ``tolerance`` (1.5x) of its solo baseline.

Every served batch in every leg is bit-gated against the scalar oracle
(``DPF.eval_cpu`` reference shares); ``checked`` requires >= 3 distinct
(N, E) shapes, 0 gate escapes, full non-victim isolation in the chaos
leg, a degraded victim, and per-tenant series visible in the embedded
metrics/flight sections.  The committed record is
``MULTITENANT_r16.json``; the fault plan is serialized into the record
(``faults.plan``) so the sequence is exactly replayable.

  JAX_PLATFORMS=cpu \
      python benchmark.py --multitenant [--dryrun] [--out FILE]
"""

from __future__ import annotations

import json
import queue
import threading
import time

import numpy as np

from ..obs import FLIGHT, record_sections
from . import loadgen
from .bench_load import _batch_for, _key_pool, _slo_stats
from .engine import LoadShed
from .faults import FaultPlan, FaultSpec, RetryPolicy
from .registry import TableRegistry
from .tenant import TenantRouter, TenantSpec

#: non-victim p99 tolerance vs solo baseline in the chaos leg
TOLERANCE = 1.5
#: additive floor for the p99 ratio gate.  The solo baseline has zero
#: cross-tenant overlap by construction, but on the 1-core CPU
#: rehearsal two coincident cap-sized batches serialize at the XLA
#: level (~4 ms each; no scheduler can preempt a dispatched program),
#: so any concurrent leg's p99 — the top sample of ~100 — sits one
#: overlap quantum (up to ~3 stacked batches) above solo even with no
#: victim at all.  The ratio therefore only binds once the absolute
#: delta exceeds this quantum; on a real TPU the device pipeline
#: shrinks it.
SLACK_MS = 12.0


def _mk_trace(cfg: dict, seed: int, duration_s: float) -> list:
    return loadgen.bursty_trace(
        on_rate=cfg["on_rate"], off_rate=cfg["on_rate"] / 8.0,
        on_s=0.6, off_s=0.6, duration_s=duration_s, cap=cfg["cap"],
        seed=seed, n=cfg["n"])


def _merge(traces: dict) -> list:
    """Merge per-tenant traces into one (tenant, arrival,
    tenant-local j) stream ordered by scheduled time."""
    tagged = []
    for name, trace in traces.items():
        tagged.extend((a.t, name, a, j) for j, a in enumerate(trace))
    tagged.sort(key=lambda r: r[0])
    return [(name, a, j) for _, name, a, j in tagged]


def _replay_mt(tr: TenantRouter, tagged, pools, *,
               inject: bool = False):
    """Open-loop replay of a merged multi-tenant stream.

    Submission is strictly on the trace schedule (open loop, one
    thread; latency = completion − scheduled arrival).  Each tenant
    gets its OWN resolver thread: one tenant's slow batches must never
    delay the point where another tenant's completions are *measured*,
    or the victim's chaos leg would inflate every bystander's p99
    purely through the measurement loop.  A tenant's shed (at submit
    OR at dispatch) and fault-exhausted errors are THAT tenant's
    unavailability, never an exception out of the loop.  Arrival
    indices only reach the fault injector when ``inject`` is True (the
    chaos leg) — the solo/combined legs stay fault-free.  Returns
    ``(lats, done, fails, sheds, makespan_s)`` — ``lats`` per tenant
    for ok batches, ``done`` the gate's (tenant, arrival, j, future)
    list, ``fails``/``sheds`` per-tenant counts.
    """
    names = {name for name, _, _ in tagged}
    lats = {n: [] for n in names}
    fails = {n: 0 for n in names}
    sheds = {n: 0 for n in names}     # resolver threads only
    admit_sheds = {n: 0 for n in names}   # submit thread only
    done = {n: [] for n in names}
    queues = {n: queue.Queue() for n in names}
    t0 = time.perf_counter()

    def resolver(name):
        while True:
            item = queues[name].get()
            if item is None:
                return
            a, j, fut = item
            try:
                fut.result()
            except LoadShed:
                sheds[name] += 1
                continue
            except Exception:
                fails[name] += 1
                continue
            lats[name].append((time.perf_counter() - t0) - a.t)
            done[name].append((name, a, j, fut))

    threads = [threading.Thread(target=resolver, args=(n,), daemon=True)
               for n in names]
    for th in threads:
        th.start()
    for name, a, j in tagged:
        while True:
            now = time.perf_counter() - t0
            if now >= a.t:
                break
            time.sleep(min(a.t - now, 0.005))

        def keys_for(lb, _name=name, _j=j, _b=a.batch):
            return _batch_for(pools[_name][lb], _j, _b)[0]
        try:
            fut = tr.submit(name, a.batch, keys_for,
                            arrival=j if inject else None)
        except LoadShed:
            admit_sheds[name] += 1
            continue
        queues[name].put((a, j, fut))
    for n in names:
        queues[n].put(None)
    for th in threads:
        th.join()
    for n in names:
        sheds[n] += admit_sheds[n]
    all_done = [x for n in sorted(names) for x in done[n]]
    return lats, all_done, fails, sheds, time.perf_counter() - t0


def _leg_stats(traces, lats, fails, sheds, escapes_by, slo_s) -> dict:
    out = {}
    for name, trace in traces.items():
        arrivals = len(trace)
        esc = escapes_by.get(name, 0)
        ok = len(lats[name]) - esc
        out[name] = {
            "arrivals": arrivals,
            "ok_batches": ok,
            "shed_batches": sheds[name],
            "failed_batches": fails[name],
            "gate_escapes": esc,
            "availability": (round(ok / arrivals, 4) if arrivals
                             else None),
            **_slo_stats(lats[name], slo_s),
        }
    return out


def _escapes_by_tenant(done, pools) -> dict:
    by = {}
    for name, a, j, fut in done:
        label = fut.decision.construction
        _, refs = pools[name][label]
        _, idxs = _batch_for(pools[name][label], j, a.batch)
        if not np.array_equal(fut.result(), refs[idxs]):
            by[name] = by.get(name, 0) + 1
    return by


def multitenant_bench(*, seed: int = 16, duration_s: float = 5.0,
                      slo_ms: float = 400.0,
                      burst: float = 4.0, prf: int = 0,
                      distinct: int = 8, dryrun: bool = False,
                      quiet: bool = False) -> dict:
    """Serve >= 3 distinct-(N, E) tenants (plus one table-sharing
    tenant) under one process and gate the noisy-neighbor isolation
    claim; returns the ``--multitenant`` record."""
    FLIGHT.clear()      # scope the embedded flight tail to this bench
    if dryrun:
        cfgs = {
            "alpha": dict(n=512, e=8, cap=16, on_rate=16.0, weight=1.0),
            "bravo": dict(n=256, e=4, cap=16, on_rate=16.0, weight=1.0),
            "victim": dict(n=128, e=4, cap=8, on_rate=24.0, weight=1.0),
            "delta": dict(n=512, e=8, cap=16, on_rate=12.0, weight=1.0,
                          table_name="alpha"),
        }
    else:
        cfgs = {
            "alpha": dict(n=4096, e=16, cap=64, on_rate=24.0,
                          weight=1.0),
            "bravo": dict(n=2048, e=8, cap=64, on_rate=24.0,
                          weight=1.0),
            "victim": dict(n=1024, e=4, cap=32, on_rate=40.0,
                           weight=1.0),
            "delta": dict(n=4096, e=16, cap=64, on_rate=16.0,
                          weight=1.0, table_name="alpha"),
        }
    victim = "victim"
    slo_s = slo_ms / 1e3

    # ---- the victim's seeded fault plan (chaos leg only: specs match
    # arrival indices, and arrivals are only threaded in that leg).
    # The dispatch-error window is p=1.0 across ALL constructions so
    # retry + failover cannot absorb it — the victim MUST degrade. ----
    plan = FaultPlan([
        FaultSpec("dispatch_error", p=1.0, start=2, stop=6),
        FaultSpec("engine_death", construction="logn", start=6),
    ], seed=seed)

    # ---- one registry + tenant router over all tables ----------------
    rng = np.random.default_rng(seed ^ 0x7e4a47)
    registry = TableRegistry(prf_method=prf)
    tr = TenantRouter(registry)
    tables = {}
    for name, cfg in cfgs.items():
        shared = cfg.get("table_name")
        if shared is None:
            tables[name] = rng.integers(0, 2 ** 31, (cfg["n"], cfg["e"]),
                                        dtype=np.int32, endpoint=False)
        spec = TenantSpec(
            name,
            table=None if shared else tables[name],
            table_name=shared,
            weight=cfg["weight"], cap=cfg["cap"], slo_s=slo_s,
            max_in_flight=2 if name == victim else 4,
            max_queue_depth=4 if name == victim else None,
            shed=(name == victim),
            plan=plan if name == victim else None,
            retry=RetryPolicy(max_attempts=3, backoff_s=0.002,
                              seed=seed),
            breaker_failures=3, breaker_reset_s=0.5)
        tr.add_tenant(spec)

    # the table-sharing tenant must reuse the collided shape's ladder
    shared_pairs = [(a, b) for a in cfgs for b in cfgs
                    if cfgs[b].get("table_name") == a]
    ladder_shared = all(
        tr.router(a).buckets is tr.router(b).buckets
        for a, b in shared_pairs)

    # ---- scalar-oracle key pools (per tenant, per construction) ------
    pools = {}
    for name, cfg in cfgs.items():
        r = tr.router(name)
        pools[name] = {
            lb: _key_pool(r.server(lb), cfg["n"], distinct,
                          b"mt-%s-%s" % (name.encode(), lb.encode()))
            for lb in r.constructions}

    traces = {name: _mk_trace(cfg, seed + i, duration_s)
              for i, (name, cfg) in enumerate(cfgs.items())}

    gate_escapes = 0

    # ---- leg 1: solo baselines ---------------------------------------
    solo = {}
    for name in cfgs:
        tagged = _merge({name: traces[name]})
        lats, done, fails, sheds, mk = _replay_mt(tr, tagged, pools)
        esc = _escapes_by_tenant(done, pools)
        gate_escapes += sum(esc.values())
        solo[name] = _leg_stats({name: traces[name]}, lats, fails,
                                sheds, esc, slo_s)[name]
        solo[name]["makespan_s"] = round(mk, 4)

    # ---- leg 2: combined (all tenants concurrent) --------------------
    tagged = _merge(traces)
    lats, done, fails, sheds, mk = _replay_mt(tr, tagged, pools)
    esc = _escapes_by_tenant(done, pools)
    gate_escapes += sum(esc.values())
    combined = _leg_stats(traces, lats, fails, sheds, esc, slo_s)
    # honest qps: queries of ok batches / makespan
    ok_queries = sum(a.batch for name, a, j, fut in done)
    combined_qps = int(ok_queries / mk) if mk else 0
    combined_leg = {"per_tenant": combined,
                    "qps_ok": combined_qps,
                    "makespan_s": round(mk, 4)}

    # ---- leg 3: noisy-neighbor chaos ---------------------------------
    chaos_traces = dict(traces)
    chaos_traces[victim] = loadgen.squeeze(traces[victim], burst)
    tagged = _merge(chaos_traces)
    lats, done, fails, sheds, mk = _replay_mt(tr, tagged, pools,
                                              inject=True)
    esc = _escapes_by_tenant(done, pools)
    gate_escapes += sum(esc.values())
    chaos = _leg_stats(chaos_traces, lats, fails, sheds, esc, slo_s)
    injector = tr.router(victim).injector
    chaos_leg = {
        "victim": victim, "burst_factor": burst,
        "per_tenant": chaos,
        "makespan_s": round(mk, 4),
        "injected": injector.stats() if injector is not None else None,
    }

    # ---- isolation gate ----------------------------------------------
    isolation = {}
    for name in cfgs:
        if name == victim:
            continue
        solo_p99 = solo[name]["p99_ms"]
        chaos_p99 = chaos[name]["p99_ms"]
        ratio = (round(chaos_p99 / solo_p99, 4)
                 if solo_p99 and chaos_p99 is not None else None)
        p99_ok = (ratio is None or ratio <= TOLERANCE
                  or chaos_p99 - solo_p99 <= SLACK_MS)
        isolation[name] = {
            "availability": chaos[name]["availability"],
            "p99_solo_ms": solo_p99, "p99_chaos_ms": chaos_p99,
            "p99_vs_solo": ratio, "p99_slack_ms": SLACK_MS,
            "isolated": (chaos[name]["availability"] == 1.0
                         and chaos[name]["gate_escapes"] == 0
                         and p99_ok),
        }
    victim_degraded = (
        chaos[victim]["availability"] is not None
        and chaos[victim]["availability"] < 1.0)

    # ---- per-tenant observability visibility -------------------------
    # metrics snapshot series keys render labels as {a="x",tenant="y"};
    # a tenant is "visible" when some series carries its label
    obs = record_sections()
    metric_tenants = set()
    for fam in obs["metrics"].values():
        for labels in fam.get("series", {}):
            for name in cfgs:
                if 'tenant="%s"' % name in labels:
                    metric_tenants.add(name)
    flight_tenants = {e["tenant"] for e in FLIGHT.dump()
                      if "tenant" in e}
    per_tenant_series = {
        "metrics_tenants": sorted(metric_tenants),
        "flight_tenants": sorted(flight_tenants),
        "visible": all(n in metric_tenants for n in cfgs)
        and len(flight_tenants) > 0,
    }

    shapes = {(c["n"], c["e"]) for c in cfgs.values()
              if not c.get("table_name")}
    checked = (
        len(shapes) >= 3
        and gate_escapes == 0
        and all(i["isolated"] for i in isolation.values())
        and victim_degraded
        and ladder_shared
        and per_tenant_series["visible"]
    )

    tr.close()          # park the per-tenant dispatch workers
    record = {
        "metric": "multi-tenant serving isolation: %d tenants "
                  "(%d distinct (N,E) shapes + 1 table-sharing) under "
                  "one TenantRouter; noisy-neighbor chaos leg = %gx "
                  "victim burst + seeded fault plan (slo=%dms, 1 "
                  "device)"
                  % (len(cfgs), len(shapes), burst, int(slo_ms)),
        "value": combined_qps,
        "unit": "queries/sec",
        "slo_ms": slo_ms,
        "tenants": {name: {"n": cfg["n"], "entry_size": cfg["e"],
                           "cap": cfg["cap"], "on_rate": cfg["on_rate"],
                           "weight": cfg["weight"],
                           "table": cfg.get("table_name", name),
                           "victim": name == victim}
                    for name, cfg in cfgs.items()},
        "trace": {"kind": "bursty", "seed": seed,
                  "duration_s": duration_s},
        "solo": solo,
        "combined": combined_leg,
        "chaos": chaos_leg,
        "isolation": isolation,
        "victim_degraded": victim_degraded,
        "ladder_shared": ladder_shared,
        "per_tenant_series": per_tenant_series,
        "faults": {"plan": plan.as_dict()},
        "scheduler": tr.stats(),
        "gate_escapes": gate_escapes,
        "checked": bool(checked),
        "obs": obs,
    }
    if not checked:
        # a failed gate must be diagnosable: dump the full flight ring
        record["flight_on_gate_failure"] = FLIGHT.dump()
    if not quiet:
        print(json.dumps(record), flush=True)
    return record


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=16)
    ap.add_argument("--duration", type=float, default=5.0,
                    help="per-tenant trace duration in seconds")
    ap.add_argument("--slo-ms", type=float, default=400.0)
    ap.add_argument("--burst", type=float, default=4.0,
                    help="victim burst factor in the chaos leg")
    ap.add_argument("--prf", type=int, default=0,
                    help="PRF id (default 0=DUMMY; 2=ChaCha20, "
                         "3=AES128)")
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny tables/traces smoke (CI): exercises "
                         "every leg in seconds, makes no perf claims")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    record = multitenant_bench(
        seed=args.seed,
        duration_s=1.2 if args.dryrun else args.duration,
        slo_ms=args.slo_ms, burst=args.burst, prf=args.prf,
        distinct=6 if args.dryrun else 8, dryrun=args.dryrun)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record


if __name__ == "__main__":
    main()
