"""Readings that set the limits of ``correct``, for one cell, in one process.

    python benchmarks/control.py --workload <name> --seeds 11,12,... \
        --control-seeds 3 --seconds <s>

For each seed, one whole run of the cell (the same harness, the same
timed path at the cell's own size and load, over a short window): the
numbers compared for the program give the lower readings.  On the first
``--control-seeds`` seeds the control is read too: the plain reference
with its contraction in float32 (``reference.share(...,
contraction="float32")``) put in the program's place as server 0, its
shares of the sampled keys read by the same comparison and judged by
the same limits.  Its ``correct`` has to come out false; the script
exits non-zero where it does not, or where a sound run is not correct.
Prints one JSON line per seed, then a summary line.  The benchmark's own
runs never run this.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness
    harness.use_program_defaults()
    events = harness.CompileEvents()
    program, control = {}, {}
    faults = []
    t0 = T0
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed, args.seconds, False, t0,
                               control=i < args.control_seeds,
                               events=events)
        t0 = time.monotonic()
        for k, v in out["compared"].items():
            program.setdefault(k, []).append(v["value"])
        ctrl = out.get("control")
        if not out["correct"]:
            faults.append("seed %d: the program is not correct" % seed)
        if ctrl is not None:
            for k, v in ctrl["compared"].items():
                control.setdefault(k, []).append(v["value"])
            if ctrl["correct"]:
                faults.append("seed %d: the control passed" % seed)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "compared": out["compared"], "control": ctrl,
                          "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower": {k: max(v) for k, v in program.items()},
                      "upper": {k: min(v) for k, v in control.items()},
                      "program": program, "control": control,
                      "faults": faults}), flush=True)
    if faults:
        raise SystemExit("; ".join(faults))


if __name__ == "__main__":
    main()
