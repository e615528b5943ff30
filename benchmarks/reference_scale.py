"""The plain reference at full size on the devices, no program in the loop.

    python benchmarks/reference_scale.py --log2-rows 28 --keys 8,32 \
        --chips 4 [--check-log2-rows 20] [--check-keys 32] [--seed 1]

Makes the table with the harness's ``make_table``, mints key pairs for
uniform rows through ``DPF.gen_batch`` (``harness.mint_keys``), computes
both servers' shares on ``jax.devices()[:chips]``
(``reference_devices.share``) and checks that server 0's share minus
server 1's is the table row for every pair: the reference checks itself
at full size.  At ``--check-log2-rows`` it also compares the devices'
shares of ``--check-keys`` keys with the host NumPy path's, bit for
bit.  One JSON line a step: seconds, seconds per key, the host's RSS
and peak RSS and the fullest device's peak bytes so far (the first
line, ``start``, after JAX and the key minter are up).  Exits non-zero
on any mismatch.  The benchmark's own runs never run this.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--log2-rows", type=int, required=True)
    ap.add_argument("--keys", default="8,32",
                    help="comma-separated key-pair counts")
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--check-log2-rows", type=int, default=20)
    ap.add_argument("--check-keys", type=int, default=32)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    from benchmarks import harness, reference_devices
    from dpf_tpu import DPF
    devices = jax.devices()[:args.chips]
    if len(devices) < args.chips:
        raise SystemExit("%d chips asked for, JAX finds %d"
                         % (args.chips, len(devices)))
    dpf = DPF(prf=2)
    faults = []

    def report(step, seconds, **kw):
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        print(json.dumps(dict(
            step=step, seconds=seconds, chips=len(devices),
            kind=devices[0].device_kind, host_rss_bytes=rss,
            host_peak_rss_bytes=resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024,
            device_peak_bytes=peak, **kw)), flush=True)

    def table_of(log2_rows):
        t = time.monotonic()
        table = harness.make_table({"log2_rows": log2_rows,
                                    "entry_words": 16}, args.seed)
        report("table", time.monotonic() - t, log2_rows=log2_rows,
               table_bytes=table.nbytes)
        return table

    report("start", time.monotonic() - T0)

    # the host path against the devices, bit for bit
    table = table_of(args.check_log2_rows)
    rows, k0, _ = harness.mint_keys(dpf, table.shape[0], args.check_keys,
                                    args.seed, 11)
    t = time.monotonic()
    dev = reference_devices.share(k0, table, "chacha20", devices=devices)
    t_dev = time.monotonic() - t
    t = time.monotonic()
    cell = harness.Cell("scale", len(devices), {"prf": "chacha20",
                                                "check": {}},
                        {}, [], [], ROOT)
    host = harness.reference_shares(k0, table, cell)
    equal = bool(np.array_equal(dev, host))
    report("devices_vs_host", time.monotonic() - t,
           log2_rows=args.check_log2_rows, keys=len(k0),
           device_s_per_key=t_dev / len(k0), equal=equal)
    if not equal:
        faults.append("devices differ from the host at 2^%d"
                      % args.check_log2_rows)
    del table, dev, host

    # both servers at full size: the reference checks itself
    table = table_of(args.log2_rows)
    for i, count in enumerate(int(c) for c in args.keys.split(",")):
        rows, k0, k1 = harness.mint_keys(dpf, table.shape[0], count,
                                         args.seed, 12 + i)
        shares = []
        for keys in (k0, k1):
            t = time.monotonic()
            shares.append(reference_devices.share(keys, table, "chacha20",
                                                  devices=devices))
            dt = time.monotonic() - t
            report("share", dt, log2_rows=args.log2_rows, keys=count,
                   s_per_key=dt / count)
        got = (shares[0].astype(np.int64) - shares[1]).astype(np.int32)
        wrong = int((got != table[rows]).any(axis=1).sum())
        report("recover", 0.0, log2_rows=args.log2_rows, keys=count,
               rows_not_recovered=wrong)
        if wrong:
            faults.append("%d of %d rows not recovered at 2^%d"
                          % (wrong, count, args.log2_rows))
    report("total", time.monotonic() - T0, faults=faults)
    if faults:
        raise SystemExit("; ".join(faults))


if __name__ == "__main__":
    main()
