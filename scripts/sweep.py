#!/usr/bin/env python
"""Kernel-shape sweep (role of the reference's ``sweep.sh``): entries x
batch x PRF grid, one log file per config, scrapeable into CSV.

  python scripts/sweep.py [--out DIR] [--quick]

Each run appends its printed-dict line to ``DIR/<config>.log``; rerunning
skips configs whose log already has a result (resumable, like the
reference's one-file-per-config protocol).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="sweep_logs")
    ap.add_argument("--quick", action="store_true",
                    help="small grid for smoke testing")
    ap.add_argument("--csv", default=None)
    ap.add_argument("--mode", default="throughput",
                    choices=("throughput", "latency", "large"),
                    help="latency: warm batch=1 per PRF x N (the coop-"
                         "kernel role); large: 2^22..2^26 single-chip "
                         "large-table runs (README.md:119 scaling axis)")
    args = ap.parse_args()

    import json

    import dpf_tpu
    from dpf_tpu.utils import scrape
    from dpf_tpu.utils.bench import test_dpf_latency, test_dpf_perf
    from dpf_tpu.utils.config import EvalConfig

    def cfg_for(prf, batch):
        # AES uses the per-level dispatch mode: the monolithic
        # bitsliced graph compiles slowly
        if prf == dpf_tpu.PRF_AES128:
            return EvalConfig(prf_method=prf, batch_size=batch,
                              kernel_impl="dispatch", round_unroll=False)
        return EvalConfig(prf_method=prf, batch_size=batch)

    if args.quick:
        entries = [1024, 4096]
        batches = [8, 32]
        prfs = [dpf_tpu.PRF_SALSA20]
        reps = 2
    else:
        entries = [1 << k for k in range(13, 21)]
        batches = [8, 64, 512, 4096]
        prfs = [dpf_tpu.PRF_AES128, dpf_tpu.PRF_SALSA20,
                dpf_tpu.PRF_CHACHA20]
        reps = 5
    if args.mode == "latency":
        batches = [1]
    elif args.mode == "large":
        # 2^22..2^26 x 16 x 4 B = up to 4.3 GB table on one chip; smaller
        # batch keeps the leaf-stream live state bounded
        entries = [1 << 22, 1 << 24, 1 << 26] if not args.quick \
            else [1 << 18]
        batches = [64]
        prfs = [dpf_tpu.PRF_CHACHA20, dpf_tpu.PRF_AES128]
        reps = 3

    os.makedirs(args.out, exist_ok=True)
    for n in entries:
        for batch in batches:
            for prf in prfs:
                name = "%s_entries=%d_batch=%d_prf=%d" % (
                    args.mode, n, batch, prf)
                path = os.path.join(args.out, name + ".log")
                if os.path.exists(path) and scrape.scrape_file(path):
                    continue
                cfg = cfg_for(prf, max(batch, 1))
                if args.mode == "latency":
                    r = test_dpf_latency(N=n, prf=prf, quiet=True,
                                         config=cfg)
                    val = "%g ms" % r["latency_ms"]
                else:
                    r = test_dpf_perf(N=n, batch=batch, prf=prf, reps=reps,
                                      quiet=True, config=cfg)
                    val = "%d dpfs/sec" % r["dpfs_per_sec"]
                with open(path, "a") as f:
                    f.write(json.dumps(r) + "\n")
                print("%s -> %s" % (name, val), flush=True)

    rows = scrape.scrape_dir(os.path.join(args.out, "*.log"))
    csv_path = args.csv or os.path.join(args.out, "sweep.csv")
    scrape.to_csv(rows, csv_path)
    print("wrote %s (%d rows)" % (csv_path, len(rows)))


if __name__ == "__main__":
    main()
