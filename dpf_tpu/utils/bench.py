"""Benchmark helpers: the printed-dict perf protocol.

Machine-readable result lines mirror the reference's protocol
(``dpf_gpu/dpf_benchmark.cu:307-314`` prints a Python dict per run;
``dpf.py:286-320`` measures wall-clock dpfs/sec over repeated batched
evals) so downstream tooling (sweeps, codesign joins) can scrape them.
"""

from __future__ import annotations

import json
import time

import numpy as np


def test_dpf_perf(N=16384, batch=512, entrysize=16, prf=None, reps=10,
                  keys_distinct=None, quiet=False, check=False,
                  config=None, dispatch_deadline=None):
    """Measure batched eval throughput; returns the result dict.

    Every key in the measured batch is a distinct real key by default
    (keygen is host-side and O(log N), so this costs seconds of setup and
    keeps the headline number beyond reproach); pass a smaller
    `keys_distinct` to tile instead — device work is identical per key.

    check=True verifies share recovery on the measured batch before timing
    (the role of the reference harness's DUMMY-gated check_correct,
    ``dpf_benchmark.cu:281-294`` — here exact for every PRF).
    """
    from ..api import DPF

    dpf = DPF(prf=prf, config=config)
    dpf.dispatch_deadline = dispatch_deadline
    if keys_distinct is None:
        keys_distinct = batch
    # odd multiplier is bijective mod the pow2 table size: indices are
    # distinct (for keys_distinct <= N) and well-spread at any batch size
    idxs = [(i * 0x9E3779B1) % N for i in range(keys_distinct)]
    pairs = [dpf.gen(i, N) for i in idxs]
    ks = [p[0] for p in pairs]
    keys = [ks[i % keys_distinct] for i in range(batch)]

    # generate directly at int32 width (an int64 intermediate would be an
    # 8.6 GB transient at the large-table sweep's N=2^26)
    table = np.random.default_rng(1).integers(
        0, 2 ** 31, (N, entrysize), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)

    if check:
        a = np.asarray(dpf.eval_tpu(ks))
        b = np.asarray(dpf.eval_tpu([p[1] for p in pairs]))
        rec = (a - b).astype(np.int32)
        # explicit raise, not assert: the gate backs the "checked"
        # provenance field and must survive python -O
        if not (rec == table[idxs]).all():
            raise AssertionError("share recovery check failed")

    dpf.eval_tpu(keys)  # compile + warm
    tstart = time.time()
    for _ in range(reps):
        dpf.eval_tpu(keys)
    elapsed = time.time() - tstart

    result = {
        "entries": N,
        "batch_size": batch,
        "entry_size": entrysize,
        "prf": dpf.prf_method_string,
        "reps": reps,
        "elapsed_s": round(elapsed, 4),
        "dpfs_per_sec": int(batch * reps / elapsed),
        "key_size_bytes": 2096,
        "checked": bool(check),  # exact share-recovery gate ran pre-timing
    }
    if not quiet:
        print("%s Key Size: %d bytes, Perf: %d dpfs/sec"
              % (dpf, result["key_size_bytes"], result["dpfs_per_sec"]))
        print(json.dumps(result))
    return result


def test_dpf_latency(N=16384, entrysize=16, prf=None, reps=20, quiet=False,
                     config=None):
    """Single-query latency (the reference's latency benchmark mode,
    ``dpf_benchmark.cu:242-276``): one key, one dispatch, wall-clock ms."""
    from ..api import DPF

    dpf = DPF(prf=prf, config=config)
    k1, _ = dpf.gen(N // 3, N)
    table = np.random.default_rng(1).integers(
        0, 2 ** 31, (N, entrysize), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    dpf.eval_tpu([k1])  # compile + warm
    t0 = time.time()
    for _ in range(reps):
        dpf.eval_tpu([k1])
    elapsed = time.time() - t0
    result = {
        "mode": "latency",
        "entries": N,
        "entry_size": entrysize,
        "prf": dpf.prf_method_string,
        "scheme": getattr(dpf, "scheme", "logn"),
        "reps": reps,
        "latency_ms": round(1e3 * elapsed / reps, 3),
    }
    if not quiet:
        print(json.dumps(result))
    return result


def test_matmul_perf(B=512, K=65536, E=16, reps=10, quiet=False):
    """Benchmark the contraction strategies alone (role of the reference's
    ``dpf_gpu/matmul_benchmark.cu``): [B,K] x [K,E] exact mod-2^32."""
    import jax
    import jax.numpy as jnp

    from ..ops import matmul128

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, (B, K),
                                 dtype=np.int64).astype(np.int32))
    b = jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, (K, E),
                                 dtype=np.int64).astype(np.int32))
    results = {}
    for name, impl in matmul128.IMPLS.items():
        fn = jax.jit(impl)
        fn(a, b).block_until_ready()
        t0 = time.time()
        for _ in range(reps):
            out = fn(a, b)
        out.block_until_ready()
        elapsed = time.time() - t0
        r = {"impl": name, "B": B, "K": K, "E": E, "reps": reps,
             "elapsed_s": elapsed,
             "gops_per_sec": 2e-9 * B * K * E * reps / elapsed}
        results[name] = r
        if not quiet:
            print(json.dumps(r))
    return results
