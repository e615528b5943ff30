"""Sweep the offered rate of an open-loop cell to find its knee.

    python benchmarks/knee.py --workload <name> --rates 10,20,40 \
        --seconds <s> --seed <n>

For each rate, in one process, the cell's own driver plays its mix at
that rate (fresh keys, one warm-up, the window) and one JSON line gives
the latency quantiles, how late the client ran, and whether a backlog
built up: the mean latency of the window's last quarter of requests
against its first.  The last line names the knee, the highest rate
below the first whose backlog grew.  The mix's fixed rate is set below
it by hand.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness
    harness.use_program_defaults()
    import numpy as np
    cell = harness.find_cell(args.workload)
    harness.look_for_chip(cell.chips)
    table = harness.make_table(cell.config, args.seed)
    drv = cell.driver()
    steady = []
    for rate in (float(r) for r in args.rates.split(",")):
        ctx = harness.Context(cell.config, dict(cell.traffic, rate=rate),
                              args.seed, args.seconds, table,
                              time.monotonic())
        st = drv.setup(ctx)
        win = drv.window(st, args.seconds, harness.no_annotation)
        drv.release(st)
        late = np.asarray(win.record["client_late_s"]) * 1e3
        lat = np.asarray(win.record["latency_ms"])
        q = len(lat) // 4
        first, last = float(lat[:q].mean()), float(lat[-q:].mean())
        # a backlog that grows over the window shows as later requests
        # waiting longer than the first ones
        ok = last <= 2 * first + 5
        steady.append((rate, ok))
        print(json.dumps({
            "rate": rate, "requests": len(lat), "steady": ok,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "mean_first_quarter_ms": first, "mean_last_quarter_ms": last,
            "client_late_p95_ms": float(np.percentile(late, 95)),
            "engine": win.record["engine"]}), flush=True)
    knee = None
    for rate, ok in sorted(steady):
        if not ok:
            break
        knee = rate
    print(json.dumps({"knee": knee}), flush=True)


if __name__ == "__main__":
    main()
