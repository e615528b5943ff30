#!/usr/bin/env python
"""Headline measurement: batched DPF evaluation throughput on one TPU.

    python bench.py [--entries 65536] [--batch 512] [--reps 10]

Two ``DPF`` servers (AES-128, the default PRF) load one seeded table of
``entries`` x 16 int32; ``gen_batch`` mints ``batch`` keys for distinct
rows.  The warm-up call compiles and must recover every row exactly by
share subtraction; then ``reps`` timed ``eval_tpu`` calls on server 0
(each ends in a host copy, so the device has finished).  Prints ONE JSON
line naming the device; dpfs/sec is ``batch`` over the median call.  The
reference's V100 figure for this shape is 15,392 dpfs/sec (README.md),
hence ``vs_baseline``.

One process, no child.  Not a TPU backend: the script exits non-zero
before measuring anything.
"""

import argparse
import json
import statistics
import sys
import time

import numpy as np

BASELINE_V100_AES128_65536 = 15392.0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--entries", type=int, default=65536)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("bench: no TPU (JAX backend is %r)" % dev.platform)

    import dpf_tpu
    from dpf_tpu.tune import compcache
    compcache.enable()

    n, batch = args.entries, args.batch
    rng = np.random.default_rng(args.seed)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 16),
                         dtype=np.int64).astype(np.int32)
    servers = [dpf_tpu.DPF(prf=dpf_tpu.PRF_AES128) for _ in range(2)]
    for s in servers:
        s.eval_init(table)
    idx = rng.choice(n, batch, replace=False)
    keys = servers[0].gen_batch(idx, n,
                                seeds=[rng.bytes(128) for _ in idx])
    t0 = time.perf_counter()
    a, b = (np.asarray(s.eval_tpu(list(k))) for s, k in zip(servers, keys))
    first_s = time.perf_counter() - t0
    rec = (a.astype(np.int64) - b).astype(np.int32)
    if not np.array_equal(rec, table[idx]):
        sys.exit("bench: shares do not recover the table rows")

    k0 = list(keys[0])
    times = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        np.asarray(servers[0].eval_tpu(k0))
        times.append(time.perf_counter() - t0)
    value = batch / statistics.median(times)
    print(json.dumps({
        "metric": "dpfs/sec (entries=%d, entry_size=16, AES128, batch=%d, "
                  "1 chip)" % (n, batch),
        "value": value,
        "unit": "dpfs/sec",
        "vs_baseline": value / BASELINE_V100_AES128_65536,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "first_calls_s": first_s,
        "call_s": times,
    }), flush=True)


if __name__ == "__main__":
    main()
