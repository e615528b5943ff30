"""Bring the two-server PIR path up on a TPU, through the user's entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --multichip  # the mesh phase alone, on four chips

One process, one thread; it starts no child.  The default run, at the
reference's largest shape (N = 2^20 rows x 16 int32, batch 512,
``BASELINE.md``):

* ``pir``: two ``DPF`` servers per PRF (AES-128, ChaCha20) load one
  seeded table; ``gen_batch`` mints 512 keys for distinct rows;
  ``eval_tpu`` on both servers recovers every row exactly, and four keys'
  shares match ``eval_cpu`` bit for bit on each server.
* ``engine``: ``DPF.serving_engine`` over the ChaCha20 servers warms its
  buckets (64, 512) and answers submits of mixed sizes with exact rows.
* ``pallas``: the Pallas kernels (binary ChaCha20 subtree, radix-4
  ChaCha20-BLK subtree, sqrt-N ChaCha20 grid, plane-AES level) match the
  XLA path bit for bit, resolve from ``"config"`` (never ``"degraded"``),
  and their compiled programs hold a ``tpu_custom_call``.  Binary
  ChaCha20's default already is the subtree kernel on a TPU, so its XLA
  reference is a server pinned to ``kernel_impl="xla"``.

``--multichip`` serves a 2^22 x 16 table (ChaCha20 with rolled rounds,
64 keys) from ``ShardedDPFServer``s (binary and sqrt-N over
``make_mesh``, binary over ``make_mesh_2d``) and compares each with
``eval_tpu`` on device 0.

Per-phase compile and warm seconds go to earlier lines; they are
bring-up observations, not a benchmark.  The last line, on success only:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure raises, so the exit code is non-zero and that line is never
printed; so does a backend that is not a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

ENTRY = 16
N_ONE = 1 << 20
N_MESH = 1 << 22
BATCH = 512
MESH_BATCH = 64   # four chips cost four times as much per second
ENGINE_SIZES = (1, 37, 64, 300)
ENGINE_BUCKETS = (64, 512)  # 512 is the PIR phase's program: one compile


# ----------------------------------------------------------------- helpers

class CompileClock:
    """Seconds of backend compilation, from JAX's own monitoring event."""

    seconds = 0.0

    @classmethod
    def start(cls):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(cls._on)

    @classmethod
    def _on(cls, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            cls.seconds += duration


def timed(label, fn, log):
    """fn() twice: the first call compiles, the second is warm."""
    c0 = CompileClock.seconds
    t0 = time.perf_counter()
    out = fn()
    cold = time.perf_counter() - t0
    c1 = CompileClock.seconds
    t0 = time.perf_counter()
    again = fn()
    warm = time.perf_counter() - t0
    assert np.array_equal(out, again), "%s: warm call disagrees" % label
    log({"phase": label, "compile_s": c1 - c0, "cold_s": cold,
         "warm_s": warm})
    return out


def make_table(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(-2 ** 31, 2 ** 31, (n, ENTRY),
                        dtype=np.int64).astype(np.int32)


def make_keys(dpf, n, batch, seed):
    """Keys for ``batch`` distinct rows; (indices, keys0, keys1)."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(n, batch, replace=False)
    k0, k1 = dpf.gen_batch(idx, n, seeds=[rng.bytes(128) for _ in idx])
    return idx, list(k0), list(k1)


def recover(a, b):
    return (np.asarray(a).astype(np.int64)
            - np.asarray(b)).astype(np.int32)


def check_rows(label, a, b, table, idx):
    got = recover(a, b)
    bad = int((got != table[idx]).any(axis=1).sum())
    assert bad == 0, "%s: %d of %d rows not recovered" % (label, bad,
                                                           len(idx))


# ------------------------------------------------------------------ phases

def phase_pir(table, prf, batch, log, seed=1, n_cpu=4):
    """Two servers, one table: exact rows and eval_cpu parity."""
    from dpf_tpu import DPF
    n = table.shape[0]
    name = DPF(prf=prf).prf_method_string
    servers = [DPF(prf=prf), DPF(prf=prf)]
    for s in servers:
        s.eval_init(table)
    idx, k0, k1 = make_keys(servers[0], n, batch, seed)
    shares = [timed("pir." + name, lambda: np.asarray(servers[0].eval_tpu(k0)),
                    log),
              np.asarray(servers[1].eval_tpu(k1))]
    check_rows("pir." + name, shares[0], shares[1], table, idx)
    for s, k, sh in zip(servers, (k0, k1), shares):
        cpu = np.asarray(s.eval_cpu(k[:n_cpu]))
        assert np.array_equal(cpu, sh[:n_cpu]), \
            "pir.%s: eval_tpu differs from eval_cpu" % name
    log({"phase": "pir." + name, "ok": True, "rows": len(idx)})
    return servers, idx, (k0, k1), shares


def phase_engine(servers, idx, keys, table, log, sizes=ENGINE_SIZES,
                 buckets=ENGINE_BUCKETS):
    """The serving engine over both servers: warm buckets, then submits
    of mixed sizes recover exact rows."""
    engines = [s.serving_engine(buckets=buckets) for s in servers]
    t0 = time.perf_counter()
    for e in engines:
        e.warmup()
    warm = time.perf_counter() - t0
    lo = 0
    t0 = time.perf_counter()
    for size in sizes:
        futs = [e.submit(k[lo:lo + size]) for e, k in zip(engines, keys)]
        a, b = (np.asarray(f.result()) for f in futs)
        check_rows("engine.b%d" % size, a, b, table, idx[lo:lo + size])
        lo += size
    for e in engines:
        e.close()
    log({"phase": "engine", "ok": True, "buckets": list(
        engines[0].buckets.sizes), "warmup_s": warm,
        "submits_s": time.perf_counter() - t0, "sizes": list(sizes)})


@contextlib.contextmanager
def compiled_programs():
    """Yields a list that, after the block, holds the StableHLO text of
    every program JAX lowered for compilation inside it (JAX's own IR
    dump, into a temporary directory): the programs ``eval_tpu`` really
    dispatched, with no second trace or compile."""
    import jax
    texts = []
    with tempfile.TemporaryDirectory() as d:
        jax.config.update("jax_dump_ir_to", d)
        jax.config.update("jax_include_debug_info_in_dumps", False)
        try:
            yield texts
        finally:
            jax.config.update("jax_dump_ir_to", "")
            jax.config.update("jax_include_debug_info_in_dumps", True)
            for name in sorted(os.listdir(d)):
                with open(os.path.join(d, name)) as f:
                    texts.append(f.read())


def check_pallas(label, dpf, keys, want, log, want_kernel=True):
    """``dpf`` (a Pallas config) against the XLA path's ``want``."""
    kn = dpf.resolved_eval_knobs(len(keys))
    assert kn["kernel_impl"] == "pallas" and \
        kn["kernel_resolved_from"] == "config", (label, kn)
    import jax
    jax.clear_caches()  # the default path may have compiled it already
    with compiled_programs() as programs:
        got = timed(label, lambda: np.asarray(dpf.eval_tpu(keys)), log)
    assert programs, "%s: no program was compiled" % label
    assert np.array_equal(got, want), "%s differs from the XLA path" % label
    if want_kernel:
        assert any("tpu_custom_call" in p for p in programs), \
            "%s: no Pallas kernel in the compiled program" % label
    log({"phase": label, "ok": True, "resolved_from":
         kn["kernel_resolved_from"]})


PALLAS_FAMILIES = ("binary", "radix4", "sqrtn", "planes")


def phase_pallas(table, batch, log, pir=None, want_kernel=True, seed=2,
                 families=PALLAS_FAMILIES):
    """Each Pallas kernel family against its XLA path on one table.
    ``pir`` maps prf -> (keys0, XLA shares0) from ``phase_pir``."""
    from dpf_tpu import DPF
    from dpf_tpu.utils.config import EvalConfig
    n = table.shape[0]
    pir = dict(pir or {})

    def server(**cfg):
        d = DPF(config=EvalConfig(**cfg))
        d.eval_init(table)
        return d

    def reference(prf, **cfg):
        """(keys, shares) of the XLA path for this construction."""
        if not cfg and prf in pir:
            return pir[prf]
        d = server(prf_method=prf, **cfg)
        _, k0, _ = make_keys(d, n, batch, seed)
        return k0, timed("xla.%d.%s" % (prf, cfg),
                         lambda: np.asarray(d.eval_tpu(k0)), log)

    for family, prf, cfg in (
            ("binary", DPF.PRF_CHACHA20, {}),
            ("radix4", DPF.PRF_CHACHA20_BLK, {"radix": 4}),
            ("sqrtn", DPF.PRF_CHACHA20, {"scheme": "sqrtn"}),
            ("planes", DPF.PRF_AES128, {})):
        if family not in families:
            continue
        label = "pallas.%s.%s" % (DPF(prf=prf).prf_method_string, family)
        keys, want = reference(prf, **cfg)
        check_pallas(label, server(prf_method=prf, kernel_impl="pallas",
                                   **cfg), keys, want, log, want_kernel)


def phase_one_chip(table, batch, log, want_kernel=True,
                   families=PALLAS_FAMILIES, engine_sizes=ENGINE_SIZES):
    """Every one-chip phase, one after another."""
    from dpf_tpu import DPF
    pir = {}
    for prf in (DPF.PRF_AES128, DPF.PRF_CHACHA20):
        servers, idx, keys, shares = phase_pir(table, prf, batch, log)
        if prf == DPF.PRF_CHACHA20:
            phase_engine(servers, idx, keys, table, log, engine_sizes)
        # the Pallas phase reuses these shares as its XLA reference only
        # where the default resolved to the XLA path
        if servers[0].resolved_eval_knobs(batch)["kernel_impl"] == "xla":
            pir[prf] = (keys[0], shares[0])
        for s in servers:
            s.eval_free()
    phase_pallas(table, batch, log, pir, want_kernel, families=families)


def in_use(devs):
    """bytes_in_use per device.  The CPU rehearsal's devices keep no
    allocator stats; there every device counts as one more byte."""
    stats = [d.memory_stats() for d in devs]
    if all(s is None for s in stats) and devs[0].platform == "cpu":
        in_use.calls = getattr(in_use, "calls", 0) + 1
        return [in_use.calls] * len(devs)
    return [s["bytes_in_use"] for s in stats]


def phase_multichip(log, n=N_MESH, batch=MESH_BATCH, prf=None, seed=3):
    """Sharded servers over every device vs ``eval_tpu`` on device 0,
    each on the kernel it resolves (on a TPU, binary ChaCha20 on the
    subtree kernel per shard, as ``eval_tpu`` runs it)."""
    import jax
    from dpf_tpu import DPF
    from dpf_tpu.parallel.sharded import make_mesh, make_mesh_2d
    devs = jax.devices()
    assert len(devs) == 4, "the mesh phase needs 4 devices"
    prf = DPF.PRF_CHACHA20 if prf is None else prf
    table = make_table(n, seed)
    for label, scheme, mesh in (
            ("mesh.binary", "logn", make_mesh(devices=devs)),
            ("mesh.sqrtn", "sqrtn", make_mesh(devices=devs)),
            ("mesh.2d", "logn", make_mesh_2d(2, 2, devices=devs))):
        singles = [DPF(prf=prf, scheme=scheme) for _ in range(2)]
        for d in singles:
            d.eval_init(table)
        idx, k0, k1 = make_keys(singles[0], n, batch, seed)
        want = [np.asarray(d.eval_tpu(k)) for d, k in zip(singles,
                                                          (k0, k1))]
        before = in_use(devs)
        srvs = [d.sharded_server(mesh) for d in singles]
        after = in_use(devs)
        held = {s.device for s in srvs[0].table_sharded.addressable_shards}
        assert len(held) == 4, "%s: table shards on %d devices" % (
            label, len(held))
        assert all(a > b for a, b in zip(after, before)), (
            "%s: bytes_in_use did not rise on every device: %s -> %s"
            % (label, before, after))
        got = [timed(label, lambda: srvs[0].eval(k0), log),
               srvs[1].eval(k1)]
        for g, w in zip(got, want):
            assert np.array_equal(g, w), "%s differs from eval_tpu" % label
        check_rows(label, got[0], got[1], table, idx)
        log({"phase": label, "ok": True, "devices": len(held),
             "kernel": srvs[0].resolved_eval_knobs(batch)["kernel_impl"],
             "bytes_in_use_rise": [a - b for a, b in zip(after, before)]})
        del srvs, singles  # free before the next layout's baseline


# -------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("chip_smoke: no TPU (JAX backend is %r)" % dev.platform)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    def log(rec):
        print(json.dumps(dict(rec, device_kind=device["kind"])), flush=True)

    # tuned entries from elsewhere must not steer the default path
    os.environ.setdefault("DPF_TPU_TUNE_CACHE", "0")
    from dpf_tpu.tune import compcache
    from dpf_tpu.utils.profiling import CACHE_COUNTERS
    CompileClock.start()
    log({"phase": "compile_cache", "dir": compcache.enable()})
    if args.multichip:
        # four chips cost four times as much per second: rolled rounds
        # compile ~3x faster, and the sharding under test is the same
        from dpf_tpu.core import prf
        prf.ROUND_UNROLL = False
        phase_multichip(log)
    else:
        phase_one_chip(make_table(N_ONE, 0), BATCH, log)
    log({"phase": "compile_cache", "hits": CACHE_COUNTERS.compile_hits,
         "misses": CACHE_COUNTERS.compile_misses,
         "compile_s": CompileClock.seconds})
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
