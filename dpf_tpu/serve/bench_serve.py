"""Streaming serving benchmark: blocking eval_tpu loop vs ServingEngine.

Measures sustained queries/sec over a stream of query batches — the
serving engine's headline — plus the vectorized-ingest micro-benchmark
(scalar per-key codec vs the batched codec at B=512).  Prints ONE JSON
line with the same record shape as ``bench.py`` (metric/value/unit/
vs_baseline); here the baseline is the blocking per-batch loop on the
identical key stream, gated on bit-exact result equality first.

Runs fine on ``JAX_PLATFORMS=cpu`` (the ingest and pipelining wins are
host-side and backend-independent; on the synchronous CPU backend the
engine's win is the vectorized ingest + bucket reuse, on TPU async
dispatch adds the host/device overlap on top).

  JAX_PLATFORMS=cpu \
      python -m dpf_tpu.serve.bench_serve [--out FILE]
"""

from __future__ import annotations

import json
import time

import numpy as np


def ingest_microbench(B=512, n=65536, distinct=32, reps=5):
    """Scalar per-key codec loop vs the batched codec on one key batch.

    Returns {scalar_s, batched_s, speedup, ...}; both paths produce the
    packed (cw1, cw2, last) arrays and are asserted bit-identical before
    timing.
    """
    from ..core import expand, keygen

    ks = []
    for i in range(distinct):
        k0, _ = keygen.generate_keys((i * 0x9E3779B1) % n, n,
                                     b"ingest-%d" % i, prf_method=0)
        ks.append(k0.serialize())
    keys = [ks[i % distinct] for i in range(B)]

    flat = [keygen.deserialize_key(k) for k in keys]
    scalar = expand.pack_keys(flat)
    pk = keygen.decode_keys_batched(keys)
    assert (np.array_equal(scalar[0], pk.cw1)
            and np.array_equal(scalar[1], pk.cw2)
            and np.array_equal(scalar[2], pk.last)), \
        "batched codec diverged from the scalar oracle"

    t0 = time.perf_counter()
    for _ in range(reps):
        expand.pack_keys([keygen.deserialize_key(k) for k in keys])
    scalar_s = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    for _ in range(reps):
        keygen.decode_keys_batched(keys)
    batched_s = (time.perf_counter() - t0) / reps

    return {"batch": B, "entries": n, "reps": reps,
            "scalar_s": round(scalar_s, 6),
            "batched_s": round(batched_s, 6),
            "speedup": round(scalar_s / batched_s, 2)}


def sqrt_ingest_microbench(B=512, n=65536, distinct=32, reps=5):
    """Scalar per-key sqrt-N codec loop vs the batched codec
    (``sqrtn.decode_sqrt_keys_batched``) on one key batch — the sqrt-N
    counterpart of ``ingest_microbench``, same record shape; asserted
    bit-identical before timing."""
    from ..core import sqrtn

    ks = []
    for i in range(distinct):
        k0, _ = sqrtn.generate_sqrt_keys((i * 0x9E3779B1) % n, n,
                                         b"sq-ingest-%d" % i, prf_method=0)
        ks.append(k0.serialize())
    keys = [ks[i % distinct] for i in range(B)]

    scalar = sqrtn.pack_sqrt_keys([sqrtn.deserialize_sqrt_key(k)
                                   for k in keys])
    pk = sqrtn.decode_sqrt_keys_batched(keys)
    assert (np.array_equal(scalar[0], pk.seeds)
            and np.array_equal(scalar[1], pk.cw1)
            and np.array_equal(scalar[2], pk.cw2)
            and pk.n == n), \
        "batched sqrt-N codec diverged from the scalar oracle"

    t0 = time.perf_counter()
    for _ in range(reps):
        sqrtn.pack_sqrt_keys([sqrtn.deserialize_sqrt_key(k)
                              for k in keys])
    scalar_s = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    for _ in range(reps):
        sqrtn.decode_sqrt_keys_batched(keys)
    batched_s = (time.perf_counter() - t0) / reps

    return {"batch": B, "entries": n, "reps": reps,
            "scalar_s": round(scalar_s, 6),
            "batched_s": round(batched_s, 6),
            "speedup": round(scalar_s / batched_s, 2)}


def _key_stream(dpf, n, batch, batches, distinct=16, ragged=False):
    """A deterministic stream of key batches (server-0 keys)."""
    ks = [dpf.gen((i * 0x9E3779B1) % n, n, seed=b"serve-%d" % i)[0]
          for i in range(distinct)]
    sizes = []
    for j in range(batches):
        if ragged:
            sizes.append(max(1, batch >> (j % 3)))  # batch, b/2, b/4, ...
        else:
            sizes.append(batch)
    return [[ks[(j + i) % distinct] for i in range(b)]
            for j, b in enumerate(sizes)]


def _blocking_scalar_pass(dpf, stream):
    """The pre-engine serial serving path, as one round of this PR found
    it: per-key scalar deserialize + per-key pack, dispatch, block.  The
    record's headline baseline — the loop the engine replaces."""
    from ..core import expand, keygen
    outs = []
    for batch in stream:
        flat = [keygen.deserialize_key(k) for k in batch]
        cw1, cw2, last = expand.pack_keys(flat)
        pk = keygen.PackedKeys(cw1, cw2, last,
                               depth=flat[0].depth, n=flat[0].n)
        outs.append(np.asarray(dpf._dispatch_packed(pk)))
    return outs


def stream_bench(n=1024, entry_size=16, batch=256, batches=24, prf=None,
                 max_in_flight=2, ragged=False, quiet=False):
    """Sustained-throughput A/B/C on one streamed workload.

    Three passes over the identical key stream, equality-gated:

    * ``blocking_scalar`` — the pre-engine serial path (per-key codec
      loop + dispatch + block): the PR's baseline, ``vs_baseline``.
    * ``blocking`` — today's ``eval_tpu`` loop (already on the batched
      codec) — isolates what the pipelining/bucketing adds on top of
      the vectorized ingest (``vs_blocking_batched``).
    * the ``ServingEngine`` — ``value`` is its sustained queries/sec.

    On a multi-core host / real accelerator the engine additionally
    overlaps host packing with device execution; on a 1-core CPU the
    win is the ingest + bounded-shape reuse alone.
    """
    import dpf_tpu

    if prf is None:
        prf = dpf_tpu.PRF_DUMMY  # host-path-bound config: the serving
        #        engine's target regime (device math fast, ingest hot)
    dpf = dpf_tpu.DPF(prf=prf)
    table = np.random.default_rng(3).integers(
        0, 2 ** 31, (n, entry_size), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    stream = _key_stream(dpf, n, batch, batches, ragged=ragged)
    total = sum(len(b) for b in stream)

    # warm every shape both paths will compile, outside the timed region
    engine = dpf.serving_engine(max_in_flight=max_in_flight, warmup=True)
    for b in {len(s) for s in stream}:
        np.asarray(dpf.eval_tpu(stream[0][:b]))

    # correctness gate: all three passes bit-identical on the stream
    blocking_ref = [np.asarray(dpf.eval_tpu(b)) for b in stream]
    scalar_ref = _blocking_scalar_pass(dpf, stream)
    futs = [engine.submit(b) for b in stream]
    engine.drain()
    for ref, sc, fut in zip(blocking_ref, scalar_ref, futs):
        if not (np.array_equal(ref, fut.result())
                and np.array_equal(ref, sc)):
            raise AssertionError("serving passes diverged")

    t0 = time.perf_counter()
    _blocking_scalar_pass(dpf, stream)
    scalar_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for b in stream:
        np.asarray(dpf.eval_tpu(b))
    blocking_s = time.perf_counter() - t0

    # pipelined engine, fresh stats
    engine = dpf.serving_engine(max_in_flight=max_in_flight, warmup=True)
    t0 = time.perf_counter()
    futs = [engine.submit(b) for b in stream]
    engine.drain()
    engine_s = time.perf_counter() - t0

    micro = ingest_microbench()
    qps_engine = total / engine_s
    qps_blocking = total / blocking_s
    qps_scalar = total / scalar_s
    record = {
        "metric": "sustained queries/sec (serving engine, entries=%d, "
                  "entry_size=%d, %s, stream %dx%d%s, 1 device)"
                  % (n, entry_size, dpf.prf_method_string, batches, batch,
                     " ragged" if ragged else ""),
        "value": int(qps_engine),
        "unit": "queries/sec",
        "vs_baseline": round(qps_engine / qps_scalar, 4),
        "baseline": "pre-engine blocking loop (per-key scalar codec + "
                    "dispatch + block), identical stream",
        "blocking_scalar_qps": int(qps_scalar),
        "blocking_scalar_elapsed_s": round(scalar_s, 4),
        "blocking_qps": int(qps_blocking),
        "blocking_elapsed_s": round(blocking_s, 4),
        "vs_blocking_batched": round(qps_engine / qps_blocking, 4),
        "engine_elapsed_s": round(engine_s, 4),
        "max_in_flight": max_in_flight,
        "buckets": list(engine.buckets.sizes),
        # the effective program shape (bucket ladder, in-flight window,
        # dot_impl, chunk_leaves, ...), so BENCH_* files are
        # self-describing about what actually ran
        "resolved_config": engine.resolved_config(),
        "engine_stats": engine.stats.as_dict(),
        "ingest_microbench": micro,
        "checked": True,  # bit-exact equality gate ran before timing
    }
    if not quiet:
        print(json.dumps(record), flush=True)
    return record


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--entry-size", type=int, default=16)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--batches", type=int, default=24)
    ap.add_argument("--prf", type=int, default=None,
                    help="PRF id (default DUMMY; 2=ChaCha20, 3=AES128)")
    ap.add_argument("--max-in-flight", type=int, default=2)
    ap.add_argument("--ragged", action="store_true",
                    help="cycle ragged batch sizes (exercises buckets)")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    record = stream_bench(n=args.n, entry_size=args.entry_size,
                          batch=args.batch, batches=args.batches,
                          prf=args.prf, max_in_flight=args.max_in_flight,
                          ragged=args.ragged)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record


if __name__ == "__main__":
    main()
