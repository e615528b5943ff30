"""Multichip rehearsal benchmark: the whole mesh matrix, tuned vs
heuristic, equality-gated.

``benchmark.py --multichip`` runs every (construction x mesh split x
shape) cell of the scale-out path through the mesh autotuner
(``tune.mesh_tune``): per cell the mesh heuristic opener and every
searched candidate are equality-gated against the scalar host oracle
(bit-identical [B, E] shares — that IS the correctness matrix, a
rejected candidate is recorded and never timed), the per-shape split
winner is raced (``tune_mesh_shape``, warm-cache from the matrix), and
the serving-engine ladder is tuned on the winning split's batch axis
(``tune_mesh_serving``).  One self-describing JSON record comes out —
committed as ``MULTICHIP_r06.json`` for the forced-8-device CPU
rehearsal; the SAME command with ``--native`` uses the real device mesh
and produces the TPU record (the fingerprint tells the
records apart).

  JAX_PLATFORMS=cpu \
      python benchmark.py --multichip [--out MULTICHIP_r06.json]
"""

from __future__ import annotations

import json
import time


DEFAULT_SHAPES = ((2048, 8), (8192, 32))

#: (scheme, radix, label) — the same three constructions the
#: single-device scheme sweep races (search.CONSTRUCTIONS)
CONSTRUCTIONS = (("logn", 2, "logn"), ("logn", 4, "radix4"),
                 ("sqrtn", 2, "sqrtn"))


def multichip_bench(shapes=DEFAULT_SHAPES, *, n_devices: int = 8,
                    native: bool = False, prf: int = 1,
                    entry_size: int = 16, reps: int = 2,
                    force: bool = False, out: str | None = None,
                    quiet: bool = False) -> dict:
    """Run the rehearsal matrix and return (and optionally write) the
    record.  ``native=False`` forces ``n_devices`` virtual CPU devices
    before any backend init (``utils.hermetic.force_cpu_mesh`` — the
    same recipe as tests/conftest.py); ``native=True`` keeps whatever
    devices the backend exposes."""
    if not native:
        from ..utils.hermetic import force_cpu_mesh
        force_cpu_mesh(n_devices)
    import jax

    from ..core.prf_ref import PRF_NAMES
    from ..parallel.sharded import make_mesh
    from ..tune import compcache
    from ..tune.cache import default_cache
    from ..tune.fingerprint import device_fingerprint
    from ..tune.mesh_tune import (mesh_split_candidates, tune_mesh_eval,
                                  tune_mesh_serving, tune_mesh_shape)
    from ..utils.profiling import CACHE_COUNTERS

    compcache.enable()
    cache = default_cache()
    devices = jax.devices()
    n_devices = len(devices) if native else min(n_devices, len(devices))
    log = None if quiet else (lambda m: print(m, flush=True))
    splits = mesh_split_candidates(n_devices)

    t_start = time.perf_counter()
    points = []
    total_rejected = 0
    for n, batch in shapes:
        constructions = []
        for scheme, radix, label in CONSTRUCTIONS:
            rows = []
            for nb, nt in splits:
                mesh = make_mesh(n_table=nt, n_batch=nb,
                                 devices=devices[:n_devices])
                if log:
                    log("tuning %s n=%d batch=%d mesh=%dx%d ..."
                        % (label, n, batch, nb, nt))
                try:
                    rec = tune_mesh_eval(
                        n, batch, mesh=mesh, entry_size=entry_size,
                        prf_method=prf, scheme=scheme, radix=radix,
                        reps=reps, cache=cache, force=force, log=log)
                except AssertionError:
                    raise  # oracle mismatch: a correctness bug — abort
                except Exception as exc:
                    # split invalid for this construction (e.g. a
                    # sqrt-N grid whose rows don't divide over the
                    # shards): record the cell, keep the matrix going
                    if log:
                        log("  invalid split: %s" % exc)
                    rows.append({"mesh": "%dx%d" % (nb, nt),
                                 "invalid": str(exc)})
                    continue
                m = rec["measured"]
                total_rejected += m["rejected"]
                rows.append({
                    "mesh": m["mesh"],
                    "tuned_knobs": rec["knobs"],
                    "heuristic_knobs": rec["heuristic"],
                    "tuned_s": m["best_s"],
                    "heuristic_s": m["heuristic_s"],
                    "speedup_vs_heuristic": m["speedup_vs_heuristic"],
                    "tuned_qps": int(batch / m["best_s"]),
                    "heuristic_qps": int(batch / m["heuristic_s"]),
                    "candidates_tried": m["candidates_tried"],
                    "rejected": m["rejected"],
                    "from_cache": not rec["searched"],
                })
            row = {"construction": label, "scheme": scheme,
                   "radix": radix, "splits": rows}
            if any("tuned_s" in r for r in rows):
                # the split race re-reads the warm matrix entries
                # (free); force re-derives its winner record from the
                # cells this run just re-measured rather than serving a
                # stale one
                split_rec = tune_mesh_shape(
                    n, batch, devices=devices[:n_devices],
                    entry_size=entry_size, prf_method=prf, scheme=scheme,
                    radix=radix, reps=reps, cache=cache, force=force)
                row["winning_split"] = split_rec["knobs"]
            constructions.append(row)
        timed = [c for c in constructions
                 if any("tuned_s" in r for r in c["splits"])]
        if not timed:
            raise AssertionError(
                "no (construction, split) cell was valid at n=%d "
                "batch=%d on %d devices" % (n, batch, n_devices))
        best = min(
            timed,
            key=lambda c: min(r["tuned_s"] for r in c["splits"]
                              if "tuned_s" in r))
        points.append({"entries": n, "batch": batch,
                       "constructions": constructions,
                       "winner": best["construction"]})

    # serving-engine ladder on the mesh batch axis: largest point,
    # winning construction, its winning split
    head = max(points, key=lambda p: p["entries"] * p["batch"])
    n, batch = head["entries"], head["batch"]
    win_c = next(c for c in head["constructions"]
                 if c["construction"] == head["winner"])
    nb, nt = (win_c["winning_split"]["n_batch"],
              win_c["winning_split"]["n_table"])
    if log:
        log("tuning mesh serving ladder: %s n=%d cap=%d mesh=%dx%d ..."
            % (head["winner"], n, batch, nb, nt))
    import numpy as np

    import dpf_tpu
    from dpf_tpu.parallel.sharded import ShardedDPFServer
    from dpf_tpu.utils.config import EvalConfig
    dpf = dpf_tpu.DPF(config=EvalConfig(
        prf_method=prf, scheme=win_c["scheme"], radix=win_c["radix"]))
    table = np.random.default_rng(n ^ 0x3a7).integers(
        0, 2 ** 31, (n, entry_size), dtype=np.int32, endpoint=False)
    srv = ShardedDPFServer(
        table, make_mesh(n_table=nt, n_batch=nb,
                         devices=devices[:n_devices]),
        prf_method=prf, batch_size=batch, radix=win_c["radix"],
        scheme=win_c["scheme"])
    serve_rec = tune_mesh_serving(srv, dpf, cap=batch, reps=reps,
                                  cache=cache, force=force, log=log)
    sm = serve_rec["measured"]
    total_rejected += sm["rejected"]

    record = {
        "metric": "mesh-path autotune matrix: %d constructions x %d "
                  "mesh splits x %d shapes, tuned vs mesh heuristic, "
                  "every timed candidate equality-gated against the "
                  "scalar oracle" % (len(CONSTRUCTIONS), len(splits),
                                     len(shapes)),
        "n_devices": n_devices,
        "forced_cpu_mesh": not native,
        "fingerprint": device_fingerprint(),
        "prf": PRF_NAMES[prf],
        "points": points,
        "serve": {
            "construction": head["winner"],
            "mesh": sm["mesh"], "cap": sm["cap"],
            "tuned_knobs": serve_rec["knobs"],
            "qps": sm["qps"], "elapsed_s": sm["elapsed_s"],
            "candidates_tried": sm["candidates_tried"],
            "rejected": sm["rejected"],
            "from_cache": not serve_rec["searched"],
        },
        "total_rejected": total_rejected,
        "elapsed_s": round(time.perf_counter() - t_start, 1),
        "tuning_cache": cache.path,
        "compilation_cache": compcache.enabled_dir(),
        "cache_counters": CACHE_COUNTERS.as_dict(),
        "checked": True,  # gate-first: no candidate timed un-verified
    }
    if not quiet:
        print(json.dumps(record), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8,
                    help="forced virtual CPU device count (default 8)")
    ap.add_argument("--native", action="store_true",
                    help="use the real device mesh (the TPU "
                         "record) instead of forcing a CPU mesh")
    ap.add_argument("--shapes", default=None,
                    help="comma list of N:B points (default %s)"
                         % ",".join("%d:%d" % s for s in DEFAULT_SHAPES))
    ap.add_argument("--prf", type=int, default=1,
                    help="PRF id (default 1=Salsa20; 0=DUMMY, "
                         "3=AES128)")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--force", action="store_true",
                    help="re-measure even with a warm tuning cache")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    shapes = DEFAULT_SHAPES
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in p.split(":"))
                       for p in args.shapes.split(","))
    return multichip_bench(shapes, n_devices=args.devices,
                           native=args.native, prf=args.prf,
                           reps=args.reps, force=args.force,
                           out=args.out)


if __name__ == "__main__":
    main()
