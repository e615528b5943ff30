# benchmark.py — sweep table sizes x PRFs and print dpfs/sec
# (mirrors the reference's benchmark.py:1-7 sweep protocol).
#
# Bench modes and their committed records:
#
#   flag               driver                       committed record
#   (default sweep)    utils/bench.test_dpf_perf    (none)
#   --serve            serve/bench_serve.py         BENCH_SERVE_r06.json
#   --autotune         tune/search.autotune_sweep   BENCH_TUNE_r07.json
#   --autotune-scheme  tune/search.scheme_sweep     BENCH_SCHEME_r13.json
#   --autotune-kernel  tune/kernel_search           BENCH_KSEARCH_r15.json
#     --family=...                                  BENCH_KSEARCH2_r18.json
#   --batch-pir        serve/bench_pir.py           BENCH_PIR_r09.json
#   --multichip        serve/bench_multichip.py     MULTICHIP_r06.json
#   --load             serve/bench_load.py          BENCH_LOAD_r10.json
#   --chaos            serve/bench_chaos.py         BENCH_CHAOS_r11.json
#   --multihost        serve/bench_multihost.py     MULTIHOST_r14.json
#   --multitenant      serve/bench_multitenant.py   MULTITENANT_r16.json
#   --plan             plan/bench_plan.py           PLAN_r17.json
#   --bigtable         serve/bench_bigtable.py      BIGTABLE_r19.json
#
# --serve: streaming serving benchmark (blocking loop vs pipelined
# ServingEngine).  See docs/SERVING.md.
#
# --autotune: hardware-aware autotuner (dpf_tpu/tune/): staged
# coordinate descent over the fused-eval knobs per (N, B) point plus a
# serving-knob grid search, every timed candidate equality-gated
# against the scalar oracle; winners persist in the tuning cache and
# the sweep record is written with --out.  See docs/TUNING.md.
#
# --autotune-scheme: one level up — races the three constructions
# (logn vs radix-4 vs sqrtn) per (N, B) point, each knob-tuned and
# equality-gated first, and persists the per-shape winning
# construction in the same tuning cache.
#
# --autotune-kernel: one level down — generative search over
# STRUCTURED kernel variants, seeded from the staged descent winner,
# mutate/tournament selection, every timed candidate equality-gated
# against its scalar oracle and every Pallas variant additionally
# gated via interpret-mode parity.  --family picks the space:
# "sqrtn" (default; the PR-15 PRF->contract space: tile shape, VMEM
# cell budget, grid order/dimension semantics, limb emission,
# codeword-select fusion for the Pallas family; scan row_chunk x
# dot_impl for the XLA family), "logn" (the GGM expansion space:
# chunk_leaves x f_levels level fusion x fused/dispatch/subtree-kernel
# drive x dot_impl), "keygen" (the batched-keygen space: SHAKE squeeze
# batching x prf_v call grouping x target-path reuse; fitness keys/s,
# key bytes invariant), or "all"/comma lists.  Winners persist as
# kvariant cache entries that resolve with
# kernel_resolved_from="searched" (eval) or ride DPF.gen_batch
# (keygen).  The multi-family record is BENCH_KSEARCH2_r18.json.
# See docs/TUNING.md.
#
# --multichip: the mesh rehearsal matrix (all three constructions x
# every mesh split x shape through the mesh autotuner) on a forced-
# 8-device CPU mesh; --native uses the real device mesh and produces
# the TPU record with the same command.  See docs/SHARDING.md.
#
# --batch-pir: end-to-end batch-PIR (plan -> keygen -> answer ->
# recover on the production path vs the pre-PR scalar loops,
# equality-gated).  See docs/BATCH_PIR.md.
#
# --load: traffic-shaped serving — the runtime cost-model scheme
# router vs the sticky cached-winner engine over one seeded open-loop
# bursty trace, with p50/p99 + deadline-miss/shed SLO accounting and
# every served batch gated against the scalar oracle; --dryrun is the
# seconds-long CI smoke.  See docs/SERVING.md "Load testing & SLOs".
#
# --chaos: fault-tolerant serving — the same seeded bursty trace
# replayed under escalating fault plans (injected dispatch failures,
# stragglers, corrupted shares, a full engine death), reporting
# availability (correct-within-SLO), retries, failovers, breaker
# transitions and engine restarts, every served batch still gated;
# --dryrun is the seconds-long CI smoke.  See docs/SERVING.md "Fault
# tolerance & chaos testing".
#
# --multihost: multi-host serving cluster — the row-sharded table
# behind a scatter/gather front-end (parallel/cluster.py), replaying
# the seeded bursty trace through a baseline leg and two host-death
# chaos legs (recovery by degrade-to-spare and by re-shard over the
# survivors), one OS process per host by default (--simulate for the
# in-process tier), availability + decision attribution via the flight
# recorder, every merged answer gated against the scalar oracle;
# --dryrun is the seconds-long CI smoke.  See docs/MULTIHOST.md.
#
# --multitenant: multi-tenant serving isolation — >= 3 distinct-(N,E)
# tenant tables (plus one table-sharing tenant) behind one
# TenantRouter (serve/tenant.py) over a TableRegistry, replayed solo /
# combined / noisy-neighbor-chaos (4x victim burst + seeded fault
# plan); gates that every non-victim holds availability 1.0 and p99
# within 1.5x of its solo baseline while the victim degrades, every
# served batch gated against the scalar oracle; --dryrun is the
# seconds-long CI smoke.  See docs/MULTITENANT.md.
#
# --plan: capacity planning — the digital twin of the serve stack
# (dpf_tpu/plan/: seeded discrete-event simulator over the router's
# serializable cost table, zero JAX dispatches) gated for p99/shed-rate
# fidelity against the real open-loop harness on identical seeded
# traces, plus the headroom planner (monotone-in-load fleet sizing) and
# the autoscaler evaluated in the twin (two diurnal days + one engine
# death vs the static peak fleet on engine-hours) and against real
# ServingEngine replicas; --dryrun is the seconds-long CI smoke.  See
# docs/PLANNING.md.
#
# --bigtable: the billion-row table tier — hosts ASSIGNED more table
# bytes than their device budget (granule-level paging through
# serve/registry.GranuleStore, every merged answer bit-gated against
# the scalar oracle), prefetch-on vs prefetch-off p99 under periodic
# residency pressure, the 2D row x entry-byte mesh programs
# (parallel/sharded.eval_sharded_2d) gated against the 1D path and
# the single-chip oracle on the forced 8-device CPU mesh, and
# memory-aware fleet planning (plan_fleet with a binding HBM floor +
# the twin's paging-stall fidelity legs); --dryrun is the seconds-long
# CI smoke.  See docs/SHARDING.md "2D sharding" and docs/PLANNING.md
# "Memory-aware planning".

import sys

import dpf_tpu
from dpf_tpu.utils.bench import test_dpf_perf


def _autotune_main(argv):
    import argparse

    from dpf_tpu.tune.search import DEFAULT_SWEEP, autotune_sweep

    ap = argparse.ArgumentParser(
        description="hardware-aware autotune sweep (docs/TUNING.md)")
    ap.add_argument("--shapes", default=None,
                    help="comma list of N:B points (default %s)"
                         % ",".join("%d:%d" % s for s in DEFAULT_SWEEP))
    ap.add_argument("--prf", type=int, default=0,
                    help="PRF id (default 0=DUMMY; 2=ChaCha20, 3=AES128)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--force", action="store_true",
                    help="re-measure even with a warm tuning cache")
    ap.add_argument("--no-serve", action="store_true",
                    help="skip the serving-knob grid search")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    shapes = DEFAULT_SWEEP
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in p.split(":"))
                       for p in args.shapes.split(","))
    autotune_sweep(shapes, prf_method=args.prf, reps=args.reps,
                   serve=not args.no_serve, force=args.force,
                   out=args.out)


def _autotune_kernel_main(argv):
    import argparse

    from dpf_tpu.tune.kernel_search import kernel_search_sweep
    from dpf_tpu.tune.search import DEFAULT_SWEEP

    ap = argparse.ArgumentParser(
        description="generative kernel-variant search over the "
                    "PRF->contract kernel space (docs/TUNING.md)")
    ap.add_argument("--shapes", default=None,
                    help="comma list of N:B points (default %s)"
                         % ",".join("%d:%d" % s for s in DEFAULT_SWEEP))
    ap.add_argument("--prf", type=int, default=2,
                    help="PRF id (default 2=ChaCha20 — the Pallas "
                         "family needs a plane-core PRF; 0=DUMMY, "
                         "3=AES128 time the XLA family only)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--generations", type=int, default=3)
    ap.add_argument("--population", type=int, default=6)
    ap.add_argument("--family", default="sqrtn",
                    help="variant space(s): sqrtn|logn|keygen|all or a "
                         "comma list (default sqrtn — the PR-15 space; "
                         "logn searches the GGM expansion, keygen the "
                         "batched generators)")
    ap.add_argument("--force", action="store_true",
                    help="re-search even with a warm kvariant cache")
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny shapes + search budget smoke (CI): same "
                         "record shape and invariants (0 rejections, "
                         "0 gate escapes, persisted winner), no perf "
                         "claims")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    shapes = None
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in p.split(":"))
                       for p in args.shapes.split(","))
    kernel_search_sweep(shapes, prf_method=args.prf, reps=args.reps,
                        generations=args.generations,
                        population=args.population, family=args.family,
                        force=args.force, dryrun=args.dryrun,
                        out=args.out)


def _autotune_scheme_main(argv):
    import argparse

    from dpf_tpu.tune.search import DEFAULT_SWEEP, scheme_sweep

    ap = argparse.ArgumentParser(
        description="scheme-level autotune: logn vs radix-4 vs sqrtn "
                    "per (N, B) point (docs/TUNING.md)")
    ap.add_argument("--shapes", default=None,
                    help="comma list of N:B points (default %s)"
                         % ",".join("%d:%d" % s for s in DEFAULT_SWEEP))
    ap.add_argument("--prf", type=int, default=0,
                    help="PRF id (default 0=DUMMY; 2=ChaCha20, 3=AES128)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--force", action="store_true",
                    help="re-measure even with a warm tuning cache")
    ap.add_argument("--out", help="also write the JSON record to a file")
    args = ap.parse_args(argv)
    shapes = DEFAULT_SWEEP
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in p.split(":"))
                       for p in args.shapes.split(","))
    scheme_sweep(shapes, prf_method=args.prf, reps=args.reps,
                 force=args.force, out=args.out)


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        # must run before anything touches a JAX backend: the bench
        # forces the virtual CPU mesh first (utils/hermetic.py)
        from dpf_tpu.serve.bench_multichip import main
        main([a for a in sys.argv[1:] if a != "--multichip"])
        sys.exit(0)
    if "--multihost" in sys.argv:
        # also before any backend touch: worker processes must inherit
        # an environment whose jax state the parent has not finalized
        from dpf_tpu.serve.bench_multihost import main
        main([a for a in sys.argv[1:] if a != "--multihost"])
        sys.exit(0)
    if "--bigtable" in sys.argv:
        # also before any backend touch: the 2D mesh leg forces the
        # virtual 8-device CPU mesh first (utils/hermetic.py)
        from dpf_tpu.serve.bench_bigtable import main
        main([a for a in sys.argv[1:] if a != "--bigtable"])
        sys.exit(0)
    if "--batch-pir" in sys.argv:
        from dpf_tpu.serve.bench_pir import main
        main([a for a in sys.argv[1:] if a != "--batch-pir"])
        sys.exit(0)
    if "--load" in sys.argv:
        from dpf_tpu.serve.bench_load import main
        main([a for a in sys.argv[1:] if a != "--load"])
        sys.exit(0)
    if "--chaos" in sys.argv:
        from dpf_tpu.serve.bench_chaos import main
        main([a for a in sys.argv[1:] if a != "--chaos"])
        sys.exit(0)
    if "--multitenant" in sys.argv:
        from dpf_tpu.serve.bench_multitenant import main
        main([a for a in sys.argv[1:] if a != "--multitenant"])
        sys.exit(0)
    if "--plan" in sys.argv:
        from dpf_tpu.plan.bench_plan import main
        main([a for a in sys.argv[1:] if a != "--plan"])
        sys.exit(0)
    if "--autotune-kernel" in sys.argv:
        _autotune_kernel_main(
            [a for a in sys.argv[1:] if a != "--autotune-kernel"])
        sys.exit(0)
    if "--autotune-scheme" in sys.argv:
        _autotune_scheme_main(
            [a for a in sys.argv[1:] if a != "--autotune-scheme"])
        sys.exit(0)
    if "--autotune" in sys.argv:
        _autotune_main([a for a in sys.argv[1:] if a != "--autotune"])
        sys.exit(0)
    if "--serve" in sys.argv:
        from dpf_tpu.serve.bench_serve import main
        main([a for a in sys.argv[1:] if a != "--serve"])
        sys.exit(0)
    for n in [16384, 65536, 262144, 1048576]:
        for prf in [dpf_tpu.PRF_AES128, dpf_tpu.PRF_SALSA20,
                    dpf_tpu.PRF_CHACHA20]:
            test_dpf_perf(N=n, prf=prf)
