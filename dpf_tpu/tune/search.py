"""Staged coordinate-descent autotuner for the fused eval program space.

The knobs that set single-device throughput — ``chunk_leaves``,
``dot_impl``, ``kernel_impl``, ``dispatch_group``, ``aes_impl`` — are
static arguments of the fused eval jit, so each candidate is a distinct
compiled program and the search cost is compiles + a few timed reps.
The repo's static heuristics (``expand.choose_chunk``, ``dot_impl=
"i32"``, ``kernel_impl="xla"``) are good openers; this module treats
them as the *starting point* of a staged coordinate descent (one knob
swept at a time, best kept — the AlphaEvolve-style TPU-FHE tuning move,
PAPERS.md arXiv:2605.14718, and the GPU NTT autotuning line,
arXiv:2502.11110) and persists the winner per (device, shape) in the
JSON tuning cache so the search runs once per machine.

**Every accepted candidate is equality-gated**: its full [B, E] share
output must be bit-identical to the scalar oracle (``DPF.eval_cpu``,
the host reference path) *before* its timing counts.  A candidate that
fails the gate — or crashes — is rejected and recorded, never timed.
Measurements run inside ``EvalConfig.applied()`` so a crashed search
cannot leave the process-wide knobs (``prf.ROUND_UNROLL``,
``prf.AES_PAIR_IMPL``, the matmul128 default) mis-set.
"""

from __future__ import annotations

import json
import time

import numpy as np

from ..core import expand
from ..core.prf_ref import PRF_AES128, PRF_NAMES
from ..ops import matmul128
from ..utils.config import EvalConfig
from ..utils.profiling import CACHE_COUNTERS
from . import compcache
from .cache import TuningCache, default_cache
from .fingerprint import cache_key, device_fingerprint

#: stage order of the coordinate descent (memory shape first — it moves
#: the most data — then the contraction, then the program structure)
STAGES = ("chunk_leaves", "dot_impl", "kernel_impl", "dispatch_group",
          "aes_impl")

#: the sqrt-N stage order: the scan's row chunk (its memory shape),
#: the contraction backend, then the program structure — "xla" (the
#: chunked scan) vs "pallas" (the fused VMEM-resident grid kernel,
#: ops/pallas_sqrt.py; TPU only)
SQRT_STAGES = ("row_chunk", "dot_impl", "kernel_impl")


def heuristic_knobs(n: int, batch: int, *, prf_method: int,
                    radix: int = 2, scheme: str = "logn") -> dict:
    """The static-heuristic knob set: what an untuned process runs, but
    for the kernel on a TPU, where binary Salsa/ChaCha GGM resolves the
    subtree kernel (``api.DPF._heuristic_kernel``).  The tuners time it
    as the xla scan it names."""
    from ..core import prf as _prf
    if scheme == "sqrtn":
        from ..core import sqrtn
        k, r = sqrtn.default_split(n)
        return {
            "row_chunk": sqrtn.choose_row_chunk(r, k, batch),
            "dot_impl": matmul128.default_impl(),
            "kernel_impl": "xla",
        }
    return {
        "chunk_leaves": expand.choose_chunk(n, batch),
        "dot_impl": matmul128.default_impl(),
        "kernel_impl": "xla",
        "dispatch_group": None,
        "aes_impl": (_prf._aes_pair_impl()
                     if prf_method == PRF_AES128 else "gather"),
    }


def heuristic_scheme(n: int) -> dict:
    """Cold-cache construction default for ``DPF(scheme="auto")`` and
    the batch-PIR per-group resolution: the reference-wire-compatible
    binary GGM tree.  Deliberately conservative — the measured winner
    per shape lives in the tuning cache (``scheme_sweep`` populates it,
    ``tune.lookup_scheme`` answers); until a sweep has run on this
    machine the auto mode must not silently switch key formats."""
    return {"scheme": "logn", "radix": 2}


def stage_candidates(stage: str, current: dict, *, n: int, batch: int,
                     prf_method: int, radix: int = 2,
                     backend: str | None = None) -> list:
    """Candidate values for one knob, given the current best of the
    others.  Hardware-aware: Pallas kernels only enter the space on the
    TPU backend, and the bitsliced AES variants only where their big
    graphs compile in reasonable time (TPU; per-level ``dispatch``
    programs elsewhere are a separate stage's job)."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    if stage == "row_chunk":  # sqrtn's memory-shape knob
        from ..core import sqrtn
        k, r = sqrtn.default_split(n)
        return sqrtn.sqrt_chunk_candidates(r, k, batch)
    if stage == "chunk_leaves":
        return expand.chunk_candidates(n, batch)
    if stage == "dot_impl":
        return list(matmul128.available_impls())
    if stage == "kernel_impl":
        if "row_chunk" in current:  # the sqrtn grid-kernel space
            from ..core import sqrtn
            from ..ops.pallas_sqrt import pallas_sqrt_unsupported
            from ..utils.compat import has_pallas_sqrt_kernel
            out = ["xla"]
            k, r = sqrtn.default_split(n)
            if (has_pallas_sqrt_kernel(backend)
                    and pallas_sqrt_unsupported(prf_method, r) is None):
                out.append("pallas")
            return out
        out = ["xla", "dispatch"]
        if backend == "tpu":
            out.append("pallas")
        return out
    if stage == "dispatch_group":
        if current.get("kernel_impl") != "dispatch":
            return []
        f = n // max(1, current.get("chunk_leaves")
                     or expand.choose_chunk(n, batch))
        return [None] + [g for g in (1, 2, 4, 8) if g <= f and f % g == 0]
    if stage == "aes_impl":
        if prf_method != PRF_AES128:
            return []
        if backend == "tpu":
            return ["gather", "bitsliced", "bitsliced:bp"]
        return ["gather"]
    raise KeyError(stage)


def _workload(n, batch, entry_size, prf_method, scheme, radix, distinct):
    """Deterministic (table, keys, oracle) for one shape.  The oracle is
    the scalar host reference (``eval_cpu``) evaluated once per distinct
    key and tiled — identical wire keys produce identical share rows."""
    from ..api import DPF
    dpf = DPF(prf=prf_method,
              config=EvalConfig(prf_method=prf_method, radix=radix,
                                scheme=scheme))
    table = np.random.default_rng(n ^ (batch << 1)).integers(
        0, 2 ** 31, (n, entry_size), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    distinct = min(distinct, batch)
    ks = [dpf.gen((i * 0x9E3779B1) % n, n, seed=b"tune-%d" % i)[0]
          for i in range(distinct)]
    keys = [ks[i % distinct] for i in range(batch)]
    oracle_distinct = np.asarray(dpf.eval_cpu(ks))
    oracle = oracle_distinct[[i % distinct for i in range(batch)]]
    return table, keys, oracle


def tune_eval(n: int, batch: int, *, entry_size: int = 16,
              prf_method: int = 0, scheme: str = "logn", radix: int = 2,
              reps: int = 3, distinct: int = 32,
              cache: TuningCache | None = None, force: bool = False,
              stages=None, log=None) -> dict:
    """Tune the fused-eval knobs for one (N, E, B, prf, scheme, radix).

    ``stages=None`` picks the scheme's own coordinate-descent order
    (``STAGES`` for the logn constructions, ``SQRT_STAGES`` for sqrtn).
    Returns the cache record (knobs + measurements) with a transient
    ``searched`` field: False when a warm cache answered and no program
    ran.  ``force=True`` re-measures and overwrites.
    """
    if stages is None:
        stages = SQRT_STAGES if scheme == "sqrtn" else STAGES
    cache = cache if cache is not None else default_cache()
    from ..core.u128 import next_pow2
    # the PADDED batch: eval_tpu pads every dispatch to the next power
    # of two, so the program the tuner times — and the batch every
    # later lookup resolves with, and the one the memory-bound chunk
    # candidates must be generated against — is the pow2 one
    pb = next_pow2(batch)
    key = cache_key("eval", n=n, entry_size=entry_size, batch=pb,
                    prf_method=prf_method, scheme=scheme, radix=radix)
    if not force:
        rec = cache.lookup(key)
        if rec is not None:
            return {**rec, "searched": False}

    table, keys, oracle = _workload(n, batch, entry_size, prf_method,
                                    scheme, radix, distinct)
    from ..api import DPF
    tried = rejected = 0

    def measure(knobs: dict) -> float | None:
        """Equality-gate then time one candidate; None = rejected."""
        nonlocal tried, rejected
        tried += 1
        cfg = EvalConfig(prf_method=prf_method, batch_size=batch,
                         radix=radix, scheme=scheme, **knobs)
        try:
            with cfg.applied():
                dpf = DPF(config=cfg)
                dpf.eval_init(table)
                # pin the dispatch to EXACTLY these knobs: candidate
                # configs leave e.g. dispatch_group at auto, and the
                # resolver must not backfill them from a stale cache
                # entry mid-search (--force re-tunes would self-bias)
                from ..core import prf as _prf
                dpf._tuned_cache[dpf._pow2_domain(batch)] = {
                    **knobs, "round_unroll": _prf.ROUND_UNROLL}
                out = np.asarray(dpf.eval_tpu(keys))  # compile + warm
                if out.shape != oracle.shape or not np.array_equal(
                        out, oracle):
                    rejected += 1
                    if log:
                        log("  reject (oracle mismatch): %r" % (knobs,))
                    return None
                best = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    np.asarray(dpf.eval_tpu(keys))
                    best = min(best, time.perf_counter() - t0)
            return best
        except Exception as exc:  # invalid combo for this shape/backend
            rejected += 1
            if log:
                log("  reject (%s): %r" % (type(exc).__name__, knobs))
            return None

    current = heuristic_knobs(n, pb, prf_method=prf_method,
                              radix=radix, scheme=scheme)
    heuristic_s = measure(dict(current))
    if heuristic_s is None:
        raise AssertionError(
            "static-heuristic config failed the oracle gate for "
            "n=%d batch=%d prf=%s — tuner refuses to search from a "
            "broken baseline" % (n, batch, PRF_NAMES[prf_method]))
    best_s = heuristic_s
    timings = {_knob_tag(current): round(heuristic_s, 6)}
    for stage in stages:
        cands = stage_candidates(stage, current, n=n, batch=pb,
                                 prf_method=prf_method, radix=radix)
        for cand in cands:
            if cand == current.get(stage):
                continue  # already measured as part of `current`
            knobs = {**current, stage: cand}
            t = measure(knobs)
            if t is None:
                continue
            timings[_knob_tag(knobs)] = round(t, 6)
            if t < best_s:
                best_s, current = t, knobs
                if log:
                    log("  %s=%r -> %.4fs (new best)" % (stage, cand, t))

    record = {
        "knobs": current,
        "heuristic": heuristic_knobs(n, pb, prf_method=prf_method,
                                     radix=radix, scheme=scheme),
        "measured": {
            "best_s": round(best_s, 6),
            "heuristic_s": round(heuristic_s, 6),
            "speedup_vs_heuristic": round(heuristic_s / best_s, 4),
            "reps": reps, "batch": batch, "entries": n,
            "entry_size": entry_size, "prf": PRF_NAMES[prf_method],
            "scheme": scheme, "radix": radix,
            "candidates_tried": tried, "rejected": rejected,
            "timings": timings,
        },
        "fingerprint": device_fingerprint(),
        "gated": True,  # every timed candidate matched the scalar oracle
    }
    cache.store(key, record)
    return {**record, "searched": True}


def _knob_tag(knobs: dict) -> str:
    if "row_chunk" in knobs:  # the sqrtn knob space
        tag = "rc%s.%s" % (knobs.get("row_chunk"), knobs.get("dot_impl"))
        kern = knobs.get("kernel_impl")
        if kern not in (None, "xla"):
            # backward-compatible grammar growth: the xla scan keeps
            # the pre-kernel "rc%s.%s" spelling, so old tuning.json
            # entries (no kernel_impl field) still read as "xla"
            tag += ".%s" % kern
        return tag
    return "c%s.%s.%s.g%s.%s" % (
        knobs.get("chunk_leaves"), knobs.get("dot_impl"),
        knobs.get("kernel_impl"), knobs.get("dispatch_group"),
        knobs.get("aes_impl"))


# --------------------------------------------------------------------- sweep

DEFAULT_SWEEP = ((4096, 128), (16384, 512))


def autotune_sweep(shapes=DEFAULT_SWEEP, *, prf_method: int = 0,
                   entry_size: int = 16, reps: int = 3,
                   serve: bool = True, force: bool = False,
                   cache: TuningCache | None = None, out: str | None = None,
                   quiet: bool = False) -> dict:
    """``benchmark.py --autotune``: tune every (N, B) point, then the
    serving knobs at the largest point, and emit one self-describing
    JSON record (committed as ``BENCH_TUNE_r07.json``).

    Also enables the persistent XLA compilation cache, so the sweep's
    own compiles seed the cache the serve path reads.
    """
    compcache.enable()
    cache = cache if cache is not None else default_cache()
    log = None if quiet else (lambda m: print(m, flush=True))
    points = []
    for n, batch in shapes:
        if log:
            log("tuning eval n=%d batch=%d prf=%s ..."
                % (n, batch, PRF_NAMES[prf_method]))
        rec = tune_eval(n, batch, entry_size=entry_size,
                        prf_method=prf_method, reps=reps, cache=cache,
                        force=force, log=log)
        m = rec["measured"]
        points.append({
            "entries": n, "batch": batch,
            "tuned_knobs": rec["knobs"],
            "heuristic_knobs": rec["heuristic"],
            "tuned_s": m["best_s"], "heuristic_s": m["heuristic_s"],
            "speedup_vs_heuristic": m["speedup_vs_heuristic"],
            "tuned_qps": int(batch / m["best_s"]),
            "heuristic_qps": int(batch / m["heuristic_s"]),
            "candidates_tried": m["candidates_tried"],
            "rejected": m["rejected"],
            "from_cache": not rec["searched"],
        })
    serve_rec = None
    if serve:
        n, batch = max(shapes, key=lambda s: s[0] * s[1])
        if log:
            log("tuning serving knobs at n=%d cap=%d ..." % (n, batch))
        from .serve_tune import tune_serving_shape
        serve_rec = tune_serving_shape(
            n=n, cap=batch, entry_size=entry_size, prf_method=prf_method,
            cache=cache, force=force, reps=max(2, reps - 1))
    record = {
        "metric": "autotuned fused-eval + serving knobs vs static "
                  "heuristics (equality-gated, best-of-%d reps)" % reps,
        "fingerprint": device_fingerprint(),
        "prf": PRF_NAMES[prf_method],
        "eval_points": points,
        "serve": serve_rec,
        "tuning_cache": cache.path,
        "compilation_cache": compcache.enabled_dir(),
        "cache_counters": CACHE_COUNTERS.as_dict(),
        "checked": True,  # every timed candidate passed the oracle gate
    }
    if not quiet:
        print(json.dumps(record), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record


# -------------------------------------------------------- scheme sweep

#: the constructions the scheme-level sweep races per (N, E, B, prf):
#: (scheme, radix, label) — "radix4" is spelled scheme="logn", radix=4
CONSTRUCTIONS = (("logn", 2, "logn"), ("logn", 4, "radix4"),
                 ("sqrtn", 2, "sqrtn"))


def scheme_cache_key(*, n: int, entry_size: int, batch: int,
                     prf_method: int) -> str:
    """Tuning-cache key for the scheme-level winner.  scheme/radix are
    the ANSWER of this entry, not part of its shape, so the key pins
    them to the ``any``/0 sentinels (``fingerprint.cache_key`` keeps
    one key grammar for all kinds)."""
    return cache_key("scheme", n=n, entry_size=entry_size, batch=batch,
                     prf_method=prf_method, scheme="any", radix=0)


def scheme_sweep(shapes=DEFAULT_SWEEP, *, prf_method: int = 0,
                 entry_size: int = 16, reps: int = 3,
                 force: bool = False, cache: TuningCache | None = None,
                 out: str | None = None, quiet: bool = False) -> dict:
    """``benchmark.py --autotune-scheme``: the tuner answers "which
    construction", not just "which knobs" (the ROADMAP "sqrtn scheme
    sweep" item; per-shape construction search is the AlphaEvolve
    TPU-FHE move, PAPERS.md arXiv:2605.14718).

    Races the three constructions — binary GGM, radix-4, sqrt-N — per
    (N, B) point.  Each is first knob-tuned by ``tune_eval`` (so every
    timed candidate passed the scalar-oracle equality gate and tuned <=
    heuristic seconds by construction), then the best tuned time picks
    the winner, persisted in the tuning cache under the ``scheme|...``
    key (``tune.cache.lookup_scheme`` answers later processes).  Also
    measures the sqrt-N batched-ingest codec against the scalar decode
    loop.  The CPU record is committed as ``BENCH_SCHEME_r08.json``.
    """
    compcache.enable()
    cache = cache if cache is not None else default_cache()
    log = None if quiet else (lambda m: print(m, flush=True))
    from ..core.u128 import next_pow2
    points = []
    for n, batch in shapes:
        rows = []
        for scheme, radix, label in CONSTRUCTIONS:
            if log:
                log("tuning %s at n=%d batch=%d prf=%s ..."
                    % (label, n, batch, PRF_NAMES[prf_method]))
            rec = tune_eval(n, batch, entry_size=entry_size,
                            prf_method=prf_method, scheme=scheme,
                            radix=radix, reps=reps, cache=cache,
                            force=force, log=log)
            m = rec["measured"]
            rows.append({
                "construction": label, "scheme": scheme, "radix": radix,
                "tuned_knobs": rec["knobs"],
                "tuned_s": m["best_s"], "heuristic_s": m["heuristic_s"],
                "speedup_vs_heuristic": m["speedup_vs_heuristic"],
                "tuned_qps": int(batch / m["best_s"]),
                "candidates_tried": m["candidates_tried"],
                "rejected": m["rejected"],
                "from_cache": not rec["searched"],
            })
        win = min(rows, key=lambda r: r["tuned_s"])
        if log:
            log("winner at n=%d batch=%d: %s (%d qps)"
                % (n, batch, win["construction"], win["tuned_qps"]))
        cache.store(
            scheme_cache_key(n=n, entry_size=entry_size,
                             batch=next_pow2(batch),
                             prf_method=prf_method),
            {"knobs": {"scheme": win["scheme"], "radix": win["radix"],
                       "construction": win["construction"]},
             "measured": {"per_construction": rows, "entries": n,
                          "batch": batch, "entry_size": entry_size,
                          "prf": PRF_NAMES[prf_method], "reps": reps},
             "fingerprint": device_fingerprint(),
             "gated": True})
        points.append({"entries": n, "batch": batch,
                       "winner": win["construction"],
                       "winner_qps": win["tuned_qps"],
                       "constructions": rows})
    from ..serve.bench_serve import sqrt_ingest_microbench
    n_mb, b_mb = max(shapes, key=lambda s: s[0] * s[1])
    micro = sqrt_ingest_microbench(B=b_mb, n=n_mb)
    record = {
        "metric": "scheme-level autotune: logn vs radix-4 vs sqrtn per "
                  "(N, B), equality-gated, best-of-%d reps" % reps,
        "fingerprint": device_fingerprint(),
        "prf": PRF_NAMES[prf_method],
        "points": points,
        "sqrt_ingest_microbench": micro,
        "tuning_cache": cache.path,
        "compilation_cache": compcache.enabled_dir(),
        "cache_counters": CACHE_COUNTERS.as_dict(),
        "checked": True,  # every timed candidate passed the oracle gate
    }
    if not quiet:
        print(json.dumps(record), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record
