#!/usr/bin/env python
"""Large-N functional run on the virtual 8-device CPU mesh.

An 8-way CPU-mesh functional run at the largest N memory allows, to back the large-N story with an executed
multi-device data point (the reference exercises 2^22..2^26 single-GPU in
``paper/kernel/gpu/scripts/sweep.sh:3-14`` and claims 2^32 support,
``README.md:119``; the TPU build's 2^32 path is the row-sharded mesh in
``parallel/sharded.py``).

This script actually *runs* the mesh-sharded evaluation at table sizes
limited only by host memory and single-core patience, verifying recovery
(server A share - server B share == table row) at every size.  Throughput
numbers from a 1-core CPU host are meaningless and are recorded only as
wall-clock provenance, never as perf claims.

  python experiments/cpu_mesh_large.py [--max-log-n 24] [--batch 4]
      [--out cpu_mesh_results.jsonl]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dpf_tpu.utils.hermetic import force_cpu_mesh  # noqa: E402

force_cpu_mesh(8)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--min-log-n", type=int, default=20)
    ap.add_argument("--max-log-n", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--entry-size", type=int, default=16)
    ap.add_argument("--deadline-s", type=int, default=3600)
    from dpf_tpu.core.prf_ref import PRF_NAMES
    ap.add_argument("--prf", default="CHACHA20",
                    choices=sorted(PRF_NAMES.values()),
                    help="PRF name, e.g. CHACHA20 or CHACHA20_BLK")
    ap.add_argument("--radix", type=int, default=2, choices=(2, 4))
    ap.add_argument("--out", default="cpu_mesh_results.jsonl")
    args = ap.parse_args()
    deadline = time.time() + args.deadline_s

    import numpy as np

    from dpf_tpu import DPF
    from dpf_tpu.parallel import sharded
    from dpf_tpu.utils.config import EvalConfig

    prf_id = {v: k for k, v in PRF_NAMES.items()}[args.prf]
    out = open(args.out, "a", buffering=1)

    def emit(rec):
        rec["t"] = round(time.time(), 1)
        line = json.dumps(rec)
        out.write(line + "\n")
        print(line, flush=True)

    mesh = sharded.make_mesh(n_table=8, n_batch=1)
    dpf = DPF(config=EvalConfig(prf_method=prf_id, radix=args.radix))
    rng = np.random.default_rng(0)

    for log_n in range(args.min_log_n, args.max_log_n + 1):
        if time.time() > deadline:
            emit({"stage": "cpu_mesh_large", "log_n": log_n,
                  "skipped": "deadline"})
            break
        n = 1 << log_n
        # Spot-verify at a handful of rows instead of materializing the
        # whole random table twice: table rows are a deterministic hash of
        # the row index, so table[idx] is recomputable without keeping a
        # second copy.
        t_build = time.time()
        # all-uint32 build: wraparound IS the mod-2^32, so peak memory is
        # the table plus one same-size broadcast temp (an int64
        # intermediate would be a 2x transient — the same trap
        # utils/bench.py:44-46 documents for the large-N sweep)
        table = (np.arange(n, dtype=np.uint32)[:, None]
                 * np.uint32(2654435761)
                 + np.arange(args.entry_size, dtype=np.uint32)[None, :]
                 * np.uint32(40503)).view(np.int32)
        srv = sharded.ShardedDPFServer(
            table, mesh, prf_method=prf_id, batch_size=args.batch,
            radix=args.radix)
        t_build = time.time() - t_build

        idxs = [int(rng.integers(0, n)) for _ in range(args.batch)]
        keys = [dpf.gen(i, n) for i in idxs]
        t0 = time.time()
        a = srv.eval([k[0] for k in keys])
        b = srv.eval([k[1] for k in keys])
        wall = time.time() - t0
        rec = (a - b).astype(np.int32)
        ok = bool((rec == table[idxs]).all())
        emit({"stage": "cpu_mesh_large", "log_n": log_n, "n": n,
              "batch": args.batch, "entry_size": args.entry_size,
              "mesh": dict(mesh.shape), "prf": args.prf,
              "radix": args.radix,
              "recovered_ok": ok, "build_s": round(t_build, 1),
              "eval2_wall_s": round(wall, 1),
              "table_mib": round(table.nbytes / 2 ** 20, 1)})
        if not ok:
            sys.exit(1)
        del table, srv

    emit({"stage": "cpu_mesh_large", "done": True})


if __name__ == "__main__":
    main()
