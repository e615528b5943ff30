"""Run one benchmark cell once and print its result line.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process; it starts no child.  It exits non-zero, printing no
result, without a TPU, with fewer chips than the cell needs, or on a
device that ``benchmarks/peaks.json`` does not know.  The last line of
standard output is the result (``benchmarks/harness.py``); the numbers
compared for ``correct`` are also the last lines of standard error.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", metavar="DIR",
                    help="also write the run's record (what the per-layer "
                         "metrics read) to DIR, and with --trace 1 the "
                         "compact trace")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness
    harness.use_program_defaults()
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T0,
                           keep=args.keep)
    harness.print_result(out)


if __name__ == "__main__":
    main()
