"""Autotuner tests: chunk-bound properties, scoped config application,
tuning-cache behavior, the staged search, and warm-start across
processes (tuning + XLA compilation cache)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import dpf_tpu
from dpf_tpu.core import expand
from dpf_tpu.ops import matmul128
from dpf_tpu.tune import cache as tcache
from dpf_tpu.tune import fingerprint, search, serve_tune
from dpf_tpu.utils.config import EvalConfig, is_auto
from dpf_tpu.utils.profiling import CACHE_COUNTERS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- chunk properties


def _pow2(x):
    return x >= 1 and (x & (x - 1)) == 0


def test_choose_chunk_properties_fuzzed():
    """Result is a power of two, <= n, and the B x C x 16-byte live-seed
    tensor stays within the documented 64 MiB bound (for any batch up to
    16384, where the 256-leaf floor still fits exactly)."""
    rng = np.random.default_rng(42)
    for _ in range(300):
        n = 1 << int(rng.integers(7, 23))
        batch = int(rng.integers(1, 16385))
        c = expand.choose_chunk(n, batch)
        assert _pow2(c), (n, batch, c)
        assert c <= n, (n, batch, c)
        assert c * batch * 16 <= expand.CHUNK_SEED_BYTES_BOUND, \
            (n, batch, c)


def test_chunk_candidates_properties_fuzzed():
    """Every candidate the tuner may measure honors the same invariants
    as the heuristic: power of two, <= n (hence divides the pow2 n),
    within the 64 MiB bound — and the heuristic is always a member."""
    rng = np.random.default_rng(43)
    for _ in range(300):
        n = 1 << int(rng.integers(7, 23))
        batch = int(rng.integers(1, 16385))
        cands = expand.chunk_candidates(n, batch)
        assert cands, (n, batch)
        assert expand.choose_chunk(n, batch) in cands
        for c in cands:
            assert _pow2(c), (n, batch, c)
            assert c <= n and n % c == 0, (n, batch, c)
            assert c * batch * 16 <= expand.CHUNK_SEED_BYTES_BOUND, \
                (n, batch, c)


# --------------------------------------------------------- scoped config


def test_applied_restores_globals():
    from dpf_tpu.core import prf
    before = (prf.ROUND_UNROLL, prf.AES_PAIR_IMPL, matmul128.default_impl())
    cfg = EvalConfig(dot_impl="mxu", aes_impl="gather", round_unroll=True)
    with cfg.applied():
        assert matmul128.default_impl() == "mxu"
        assert prf.AES_PAIR_IMPL == "gather"
        assert prf.ROUND_UNROLL is True
    assert (prf.ROUND_UNROLL, prf.AES_PAIR_IMPL,
            matmul128.default_impl()) == before


def test_applied_restores_on_crash():
    """A crashed candidate measurement must not leave the process
    mis-knobbed (the satellite's whole point)."""
    from dpf_tpu.core import prf
    before = (prf.ROUND_UNROLL, prf.AES_PAIR_IMPL, matmul128.default_impl())
    with pytest.raises(RuntimeError):
        with EvalConfig(dot_impl="mxu", round_unroll=False).applied():
            raise RuntimeError("candidate crashed")
    assert (prf.ROUND_UNROLL, prf.AES_PAIR_IMPL,
            matmul128.default_impl()) == before


def test_apply_globals_auto_fields_reset_to_defaults():
    """Sweep scripts apply configs back-to-back: an auto-state field
    must RESET its global to the auto default, never inherit whatever
    the previous config leaked (and None/'auto' dot_impl must not
    KeyError into set_dot_impl)."""
    from dpf_tpu.core import prf
    snap = (prf.ROUND_UNROLL, prf.AES_PAIR_IMPL, matmul128.default_impl())
    try:
        EvalConfig(dot_impl="mxu", aes_impl="gather",
                   round_unroll=True).apply_globals()
        EvalConfig(dot_impl=None, aes_impl="auto").apply_globals()
        assert prf.ROUND_UNROLL is None
        assert prf.AES_PAIR_IMPL == "auto"
        assert matmul128.default_impl() == "i32"
    finally:
        prf.ROUND_UNROLL, prf.AES_PAIR_IMPL = snap[0], snap[1]
        matmul128.set_dot_impl(snap[2])


def test_is_auto_states():
    assert is_auto(None) and is_auto("auto")
    assert not is_auto("i32") and not is_auto(False) and not is_auto(0)


# ---------------------------------------------------------- tuning cache


def test_tuning_cache_roundtrip_and_counters(tmp_path):
    path = str(tmp_path / "tuning.json")
    c = tcache.TuningCache(path)
    key = fingerprint.cache_key("eval", n=1024, entry_size=16, batch=64,
                                prf_method=0)
    h0, m0 = CACHE_COUNTERS.tuning_hits, CACHE_COUNTERS.tuning_misses
    assert c.lookup(key) is None
    assert CACHE_COUNTERS.tuning_misses == m0 + 1
    c.store(key, {"knobs": {"dot_impl": "mxu", "chunk_leaves": 256}})
    assert c.lookup(key)["knobs"]["dot_impl"] == "mxu"
    assert CACHE_COUNTERS.tuning_hits == h0 + 1
    # a fresh instance (second process analogue) reads the same file
    c2 = tcache.TuningCache(path)
    assert c2.lookup(key)["knobs"]["chunk_leaves"] == 256
    # corrupt file = cold cache, not a crash
    with open(path, "w") as f:
        f.write("{not json")
    assert tcache.TuningCache(path).lookup(key) is None


def test_tuning_cache_nearest_batch_fallback(tmp_path):
    c = tcache.TuningCache(str(tmp_path / "t.json"))
    shape = dict(n=2048, entry_size=16, prf_method=0)
    c.store(fingerprint.cache_key("eval", batch=512, **shape),
            {"knobs": {"dot_impl": "mxu"}})
    assert c.lookup_knobs("eval", batch=512, **shape)["dot_impl"] == "mxu"
    # exact miss at 64 falls back to the 512 entry
    assert c.lookup_knobs("eval", batch=64, nearest_batch=True,
                          **shape)["dot_impl"] == "mxu"
    assert c.lookup_knobs("eval", batch=64, **shape) is None


def test_dpf_consults_tuning_cache(tmp_path, monkeypatch):
    """A cache entry for this (device, shape) steers the dispatch knobs
    when EvalConfig fields are at auto — and results stay correct."""
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    c = tcache.default_cache(refresh=True)
    n, batch = 512, 8
    c.store(fingerprint.cache_key("eval", n=n, entry_size=16, batch=batch,
                                  prf_method=0),
            {"knobs": {"dot_impl": "mxu", "chunk_leaves": 128,
                       "kernel_impl": "xla", "dispatch_group": None,
                       "aes_impl": "gather"}})
    dpf = dpf_tpu.DPF(prf=0)
    table = np.random.default_rng(5).integers(
        0, 2 ** 31, (n, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    knobs = dpf.resolved_eval_knobs(batch)
    assert knobs["dot_impl"] == "mxu" and knobs["chunk_leaves"] == 128
    # explicit config fields still win over the tuned entry
    dpf2 = dpf_tpu.DPF(config=EvalConfig(prf_method=0, dot_impl="i32"))
    dpf2.eval_init(table)
    assert dpf2.resolved_eval_knobs(batch)["dot_impl"] == "i32"
    assert dpf2.resolved_eval_knobs(batch)["chunk_leaves"] == 128
    # and the tuned program is still bit-correct vs the host reference
    ks = [dpf.gen(i, n)[0] for i in range(batch)]
    assert np.array_equal(np.asarray(dpf.eval_tpu(ks)),
                          np.asarray(dpf.eval_cpu(ks)))


def test_global_knob_changes_stay_live_after_dispatch(tmp_path,
                                                      monkeypatch):
    """set_dot_impl / apply_globals between dispatches must keep
    working: the per-batch resolution caches only the tuning lookup,
    never the process-global fallbacks."""
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    tcache.default_cache(refresh=True)
    dpf = dpf_tpu.DPF(prf=0)
    dpf.eval_init(np.zeros((256, 16), np.int32))
    ks = [dpf.gen(1, 256)[0]]
    np.asarray(dpf.eval_tpu(ks))
    assert dpf.resolved_eval_knobs(1)["dot_impl"] == "i32"
    try:
        matmul128.set_dot_impl("mxu")
        assert dpf.resolved_eval_knobs(1)["dot_impl"] == "mxu"
    finally:
        matmul128.set_dot_impl("i32")


# -------------------------------------------------------------- searches


def test_tune_eval_nonpow2_batch_entry_is_reachable(tmp_path):
    """eval_tpu pads every dispatch to the next power of two, so tuning
    at a ragged batch must store under the padded key the dispatch path
    actually resolves with."""
    c = tcache.TuningCache(str(tmp_path / "t.json"))
    rec = search.tune_eval(256, 3, reps=1, distinct=3, cache=c,
                           stages=("chunk_leaves",))
    assert rec["searched"]
    knobs = c.lookup_knobs("eval", n=256, entry_size=16, batch=4,
                           prf_method=0, scheme="logn", radix=2)
    assert knobs == rec["knobs"]


def test_tune_eval_searches_then_hits_cache(tmp_path):
    c = tcache.TuningCache(str(tmp_path / "t.json"))
    rec = search.tune_eval(256, 4, reps=1, distinct=4, cache=c,
                           stages=("chunk_leaves", "dot_impl"))
    assert rec["searched"] and rec["gated"]
    m = rec["measured"]
    assert m["best_s"] <= m["heuristic_s"]  # heuristic is a candidate
    assert m["candidates_tried"] >= 2 and m["rejected"] == 0
    assert rec["knobs"]["chunk_leaves"] in expand.chunk_candidates(256, 4)
    assert rec["knobs"]["dot_impl"] in matmul128.available_impls()
    # warm cache: no search, identical knobs
    rec2 = search.tune_eval(256, 4, reps=1, cache=c)
    assert not rec2["searched"] and rec2["knobs"] == rec["knobs"]


def test_stage_candidates_hardware_aware():
    cur = search.heuristic_knobs(1024, 8, prf_method=3)
    assert search.stage_candidates(
        "aes_impl", cur, n=1024, batch=8, prf_method=3,
        backend="cpu") == ["gather"]
    assert "bitsliced" in search.stage_candidates(
        "aes_impl", cur, n=1024, batch=8, prf_method=3, backend="tpu")
    assert "pallas" not in search.stage_candidates(
        "kernel_impl", cur, n=1024, batch=8, prf_method=2, backend="cpu")
    # dispatch_group only opens up under the dispatch kernel
    assert search.stage_candidates(
        "dispatch_group", cur, n=1024, batch=8, prf_method=0,
        backend="cpu") == []
    groups = search.stage_candidates(
        "dispatch_group", {**cur, "kernel_impl": "dispatch"},
        n=1024, batch=8, prf_method=0, backend="cpu")
    assert None in groups and all(
        g is None or (1024 // cur["chunk_leaves"]) % g == 0
        for g in groups)


def test_sqrtn_knob_space():
    """scheme='sqrtn' enters the tuner with its own three-knob stage
    order; candidates honor the live-slab budget and the heuristic is
    a member."""
    from dpf_tpu.core import sqrtn
    assert search.SQRT_STAGES == ("row_chunk", "dot_impl", "kernel_impl")
    h = search.heuristic_knobs(4096, 64, prf_method=0, scheme="sqrtn")
    assert set(h) == {"row_chunk", "dot_impl", "kernel_impl"}
    k, r = sqrtn.default_split(4096)
    assert h["row_chunk"] == sqrtn.choose_row_chunk(k=k, r=r, batch=64)
    cands = search.stage_candidates("row_chunk", h, n=4096, batch=64,
                                    prf_method=0, backend="cpu")
    assert h["row_chunk"] in cands
    assert cands == sqrtn.sqrt_chunk_candidates(r, k, 64)
    # the fused grid kernel is only a candidate where it can run: TPU
    # backend AND a PRF with a Pallas plane core (ids 1/2/4/5 — not the
    # dummy or AES)
    assert search.stage_candidates("kernel_impl", h, n=4096, batch=64,
                                   prf_method=0, backend="cpu") == ["xla"]
    assert search.stage_candidates("kernel_impl", h, n=4096, batch=64,
                                   prf_method=0, backend="tpu") == ["xla"]
    assert search.stage_candidates(
        "kernel_impl", h, n=4096, batch=64, prf_method=2,
        backend="tpu") == ["xla", "pallas"]


def test_tune_eval_sqrtn_and_resolution(tmp_path, monkeypatch):
    """tune_eval over the sqrtn space: gated, tuned <= heuristic, and a
    fresh DPF resolves row_chunk/dot_impl from the cache at dispatch."""
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    c = tcache.default_cache(refresh=True)
    n, batch = 1024, 4
    rec = search.tune_eval(n, batch, reps=1, distinct=4, cache=c,
                           scheme="sqrtn")
    assert rec["searched"] and rec["gated"]
    m = rec["measured"]
    assert m["best_s"] <= m["heuristic_s"] and m["rejected"] == 0
    from dpf_tpu.core import sqrtn
    k, r = sqrtn.default_split(n)
    assert rec["knobs"]["row_chunk"] in sqrtn.sqrt_chunk_candidates(
        r, k, batch)
    dpf = dpf_tpu.DPF(prf=0, scheme="sqrtn")
    table = np.random.default_rng(2).integers(
        0, 2 ** 31, (n, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    knobs = dpf.resolved_eval_knobs(batch)
    assert knobs.pop("kernel_resolved_from") == "tuned"
    assert knobs == rec["knobs"]
    ks = [dpf.gen(i, n)[0] for i in range(batch)]
    assert np.array_equal(np.asarray(dpf.eval_tpu(ks)),
                          np.asarray(dpf.eval_cpu(ks)))


def test_scheme_sweep_records_winner(tmp_path, monkeypatch):
    """The scheme-level sweep races logn vs radix-4 vs sqrtn, persists
    a per-(N, B) winner reachable via tune.lookup_scheme, and every
    construction's tuned time is <= its heuristic."""
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    c = tcache.default_cache(refresh=True)
    rec = search.scheme_sweep(((512, 4),), reps=1, cache=c, quiet=True)
    assert rec["checked"]
    (point,) = rec["points"]
    labels = {r["construction"] for r in point["constructions"]}
    assert labels == {"logn", "radix4", "sqrtn"}
    for row in point["constructions"]:
        assert row["tuned_s"] <= row["heuristic_s"], row["construction"]
        assert row["rejected"] == 0, row["construction"]
    best = min(point["constructions"], key=lambda r: r["tuned_s"])
    assert point["winner"] == best["construction"]
    knobs = tcache.lookup_scheme(n=512, entry_size=16, batch=4,
                                 prf_method=0)
    assert knobs["construction"] == point["winner"]
    # nearest-batch fallback answers other batch sizes too
    assert tcache.lookup_scheme(n=512, entry_size=16, batch=16,
                                prf_method=0) == knobs
    # warm cache: a second sweep re-reports without re-searching
    stores = CACHE_COUNTERS.tuning_stores
    rec2 = search.scheme_sweep(((512, 4),), reps=1, cache=c, quiet=True)
    assert all(r["from_cache"]
               for r in rec2["points"][0]["constructions"])
    assert CACHE_COUNTERS.tuning_stores == stores + 1  # winner restored


def test_serving_warmup_tune_in_place(tmp_path, monkeypatch):
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    tcache.default_cache(refresh=True)
    n = 256
    dpf = dpf_tpu.DPF(prf=0)
    table = np.random.default_rng(7).integers(
        0, 2 ** 31, (n, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    engine = dpf.serving_engine(max_in_flight=2, buckets=(4, 8))
    engine.warmup(tune=True, trace=[8, 4, 8, 3])
    rc = engine.resolved_config()
    assert rc["buckets"] == list(engine.buckets.sizes)
    assert rc["max_in_flight"] == engine.max_in_flight
    assert rc["dot_impl"] in matmul128.available_impls()
    # the winner persisted under the serve key; a second engine's tuned
    # warmup consults it without re-searching
    knobs = serve_tune.lookup_serve_knobs(dpf, engine.buckets.max)
    assert knobs is not None
    assert knobs["buckets"] == list(engine.buckets.sizes)
    stores = CACHE_COUNTERS.tuning_stores
    engine2 = dpf.serving_engine(buckets=tuple(knobs["buckets"]))
    engine2.warmup(tune=True)
    assert CACHE_COUNTERS.tuning_stores == stores  # no new search
    # tuned engine still serves bit-identically to the blocking loop
    ks = [dpf.gen(i, n)[0] for i in range(8)]
    fut = engine2.submit(ks)
    engine2.drain()
    assert np.array_equal(fut.result(), np.asarray(dpf.eval_tpu(ks)))


def test_tune_serving_accepts_loadgen_traces(tmp_path, monkeypatch):
    """The serving-knob tuner replays loadgen traces (Arrival lists or
    a trace_kind string) — synthetic_trace stays the default when
    neither is given; trace and trace_kind are mutually exclusive."""
    from dpf_tpu.serve import loadgen
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    c = tcache.default_cache(refresh=True)
    n = 256
    dpf = dpf_tpu.DPF(prf=0)
    table = np.random.default_rng(7).integers(
        0, 2 ** 31, (n, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    trace = loadgen.replay_trace([8, 3, 8, 1], rate=100.0)
    rec = serve_tune.tune_serving(dpf, cap=8, trace=trace,
                                  ladders=[(8,), (4, 8)],
                                  in_flight=(1,), reps=1, cache=c)
    assert rec["searched"] and rec["gated"]
    # the record stores the batch-size view of the Arrival trace
    assert rec["measured"]["trace"] == [8, 3, 8, 1]
    with pytest.raises(ValueError, match="not both"):
        serve_tune.tune_serving(dpf, cap=8, trace=[4],
                                trace_kind="bursty", force=True)
    # resolve_trace: kind -> the canonical default, None -> legacy
    sizes = serve_tune.resolve_trace(8, trace_kind="bursty")
    assert sizes and all(1 <= b <= 8 for b in sizes)
    assert serve_tune.resolve_trace(8) == serve_tune.synthetic_trace(8)


def test_compcache_enable_is_idempotent(tmp_path, monkeypatch):
    """A second enable() keeps the directory and the floors it set."""
    import jax

    from dpf_tpu.tune import compcache
    d = str(tmp_path / "xla")
    monkeypatch.delenv("DPF_TPU_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.setattr(compcache, "_ENABLED_DIR", None)
    prior_dir = jax.config.jax_compilation_cache_dir
    prior_floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        jax.config.update("jax_compilation_cache_dir", d)
        assert compcache.enable() == d
        assert compcache.enable() == d == compcache.enabled_dir()
        assert jax.config.jax_compilation_cache_dir == d
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prior_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prior_floor)


# --------------------------------------------------- warm second process

_WARM_DRIVER = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    import dpf_tpu
    from dpf_tpu.tune import compcache
    from dpf_tpu.tune.search import tune_eval
    from dpf_tpu.utils.profiling import CACHE_COUNTERS

    compcache.enable()
    rec = tune_eval(256, 4, reps=1, distinct=4,
                    stages=("chunk_leaves", "dot_impl"))
    # then actually SERVE with the tuned knobs: in a warm process the
    # search is skipped above, so this dispatch is the first compile
    # request — and must be answered by the persistent XLA cache
    dpf = dpf_tpu.DPF(prf=0)
    table = np.random.default_rng(1).integers(
        0, 2 ** 31, (256, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    ks = [dpf.gen(i, 256)[0] for i in range(4)]
    np.asarray(dpf.eval_tpu(ks))
    print(json.dumps({"searched": rec["searched"],
                      "knobs": rec["knobs"],
                      "resolved": dpf.resolved_eval_knobs(4),
                      "counters": CACHE_COUNTERS.as_dict()}))
""")


def test_warm_cache_skips_search_and_recompile(tmp_path):
    """Acceptance: a second process with warm tuning + compilation
    caches skips the coordinate descent AND the XLA recompile, visible
    through the profiling cache counters."""
    env = dict(os.environ)
    env.pop("DPF_TPU_COMPILE_CACHE", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DPF_TPU_TUNE_CACHE": str(tmp_path / "tuning.json"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
        "PYTHONPATH": REPO,
    })

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _WARM_DRIVER], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["searched"] is True
    assert cold["counters"]["tuning_misses"] >= 1
    assert cold["counters"]["compile_misses"] >= 1  # seeded the cache
    warm = run()
    assert warm["searched"] is False               # tuning cache hit ...
    assert warm["counters"]["tuning_hits"] >= 1
    assert warm["counters"]["tuning_stores"] == 0  # ... so no re-search
    assert warm["counters"]["compile_hits"] >= 1   # XLA recompile skipped
    assert warm["knobs"] == cold["knobs"]
    # and the serving DPF resolved its auto fields from the warm cache
    for knob, val in cold["knobs"].items():
        assert warm["resolved"][knob] == val, knob
