"""Billion-row table tier tests: 2D row x entry-byte sharding parity,
granule-level HBM paging (``serve.registry.GranuleStore``), the
arrival-rate estimators (``loadgen.bucket_rates`` offline /
``SchemeRouter.note_arrival`` live), the device-memory probe, and
memory-aware fleet planning (``plan_fleet`` + the twin's paging
stall)."""

import os

import numpy as np
import pytest

from dpf_tpu import DPF
from dpf_tpu.core import expand
from dpf_tpu.serve import loadgen
from dpf_tpu.serve.buckets import Buckets
from dpf_tpu.serve.registry import GranulePrefetcher, GranuleStore


@pytest.fixture(scope="module")
def eight_devices():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return jax.devices()


def _table(n, entry=8, seed=19):
    return np.random.default_rng(seed).integers(
        -2 ** 31, 2 ** 31, (n, entry), dtype=np.int64).astype(np.int32)


# ------------------------------------------- arrival-rate estimators


def test_bucket_rates_counts_dispatches_deterministically():
    bk = Buckets((4, 8))
    trace = [loadgen.Arrival(0.5, None, 3),    # -> one 4-dispatch
             loadgen.Arrival(1.0, None, 8),    # -> one 8-dispatch
             loadgen.Arrival(2.0, None, 20)]   # -> 8+8+4 chunks
    rates = loadgen.bucket_rates(trace, bk)
    assert rates == loadgen.bucket_rates(trace, bk)   # deterministic
    assert rates == {4: 2 / 2.0, 8: 3 / 2.0}          # t_last = 2.0
    # raw ints and an explicit duration work too; every rung reported
    assert loadgen.bucket_rates([4], (4, 8), duration_s=2.0) == \
        {4: 0.5, 8: 0.0}
    with pytest.raises(ValueError):
        loadgen.bucket_rates([4], (4, 8), duration_s=0.0)


def test_bucket_rates_duration_floor():
    # sub-second traces use a 1 s floor, not a divide-by-near-zero
    assert loadgen.bucket_rates(
        [loadgen.Arrival(0.001, None, 4)], (4,)) == {4: 1.0}


def test_router_arrival_estimator_pure_function_of_timestamps():
    from dpf_tpu.serve.router import SchemeRouter
    rt = SchemeRouter(_table(256, 5), prf=DPF.PRF_DUMMY, cap=8,
                      buckets=(4, 8))
    assert rt.arrival_rates() == {}
    assert rt.arrival_rate(4) is None
    for i in range(5):
        rt.note_arrival(4, t=10.0 + 0.5 * i)      # steady 2 Hz
    assert rt.arrival_rate(4) == pytest.approx(2.0)
    rt.note_arrival(8, t=0.0)
    assert rt.arrival_rate(8) is None             # one sample: no rate
    rt.note_arrival(8, t=0.25)
    rates = rt.arrival_rates()
    assert rates[4] == pytest.approx(2.0)
    assert rates[8] == pytest.approx(4.0)
    # the estimate reaches the stats surface (and is JSON-shaped)
    assert rt.stats()["arrival_rate_hz"]["4"] == pytest.approx(2.0)


def test_router_route_feeds_estimator():
    from dpf_tpu.serve.router import SchemeRouter
    rt = SchemeRouter(_table(256, 5), prf=DPF.PRF_DUMMY, cap=8,
                      buckets=(4, 8))
    rt.route(3)
    rt.route(4)
    assert rt.arrival_rate(4) is not None


def test_device_memory_stats_contract():
    """None-or-dict, never raises — on the CPU mesh it may be either
    (old jaxlibs return None; newer ones report host 'device' stats)."""
    from dpf_tpu.utils.compat import device_memory_stats
    st = device_memory_stats()
    assert st is None or isinstance(st, dict)
    assert device_memory_stats(device=object()) is None   # no raise
    from dpf_tpu.plan.capacity import detect_hbm_budget
    hbm = detect_hbm_budget()
    assert hbm is None or (isinstance(hbm, int) and hbm > 0)


# -------------------------------------------------- mesh-tag grammar


def test_mesh_tag_2d_grammar_and_old_tags_unchanged(eight_devices):
    from dpf_tpu.parallel import sharded
    from dpf_tpu.tune.fingerprint import mesh_tag
    assert mesh_tag(sharded.make_mesh(n_table=4, n_batch=2)) == "2x4"
    # byte=1 degenerates to the pre-2D tag: tuned entries are shared
    assert mesh_tag(sharded.make_mesh_2d(n_table=4, n_byte=1,
                                         n_batch=2)) == "2x4"
    assert mesh_tag(sharded.make_mesh_2d(n_table=4, n_byte=2,
                                         n_batch=1)) == "1x4b2"
    assert mesh_tag(sharded.make_mesh_2d(n_table=2, n_byte=2,
                                         n_batch=2)) == "2x2b2"


# ----------------------------------------------------- granule store


def _store(n=1024, entry=8, granule=128, budget_granules=None,
           seed=3):
    perm = expand.permute_table(_table(n, entry, seed))
    gb = granule * entry * 4
    budget = None if budget_granules is None else budget_granules * gb
    return GranuleStore(perm, granule, budget_bytes=budget), perm


def test_granule_lease_bytes_bit_identical_and_lru_evicts():
    store, perm = _store(budget_granules=2)
    g = store.granule
    with store.lease(0) as l0:
        assert np.array_equal(np.asarray(l0.table), perm[0:g])
    with store.lease(g):
        pass
    with store.lease(2 * g) as l2:    # budget 2: LRU (row0=0) evicted
        assert np.array_equal(np.asarray(l2.table),
                              perm[2 * g:3 * g])
    assert store.counters["evictions"] == 1
    assert 0 not in store.resident_row0s()
    # re-promotion across the eviction boundary is bit-identical
    with store.lease(0) as l0again:
        assert np.array_equal(np.asarray(l0again.table), perm[0:g])


def test_pinned_granule_survives_pressure():
    store, _ = _store(budget_granules=1)
    lease = store.lease(0)
    assert not store.demote(0)                 # pinned: deferred
    assert store.counters["deferred_demotions"] == 1
    assert 0 in store.resident_row0s()
    # budget 1 and the only resident granule pinned: leasing another
    # overcommits rather than evicting the pinned one
    other = store.lease(store.granule)
    assert store.counters["overcommits"] == 1
    assert 0 in store.resident_row0s()
    other.release()
    lease.release()                            # deferred demote fires
    assert 0 not in store.resident_row0s()
    assert store.counters["demotions"] >= 1


def test_prefetch_never_evicts_and_scoreboard_counts():
    store, _ = _store(budget_granules=2)
    g = store.granule
    assert store.prefetch(0) and store.prefetch(g)
    assert not store.prefetch(2 * g)           # budget full: refused
    assert store.resident_row0s() == (0, g)
    with store.lease(0):                       # prefetched then used
        pass
    assert store.counters["prefetch_hits"] == 1
    with store.lease(2 * g):                   # demand miss
        pass
    assert store.counters["prefetch_misses"] == 1
    assert store.counters["prefetches"] == 2


def test_prefetcher_tick_and_rate_sized_budget():
    store, _ = _store(budget_granules=None)
    pf = GranulePrefetcher(store, max_per_tick=3)
    assert pf.budget_this_tick() == 3          # no rates: the cap
    assert pf.tick() == 3
    assert store.resident_row0s() == (0, 128, 256)
    # a measured page time + a hot arrival rate shrinks the window
    store._page_s = 0.050
    fast = GranulePrefetcher(store, rates_fn=lambda: {8: 20.0},
                             max_per_tick=8, slack=0.5)
    assert fast.budget_this_tick() == 1        # 0.5/20 / 0.05 = 0.5
    slow = GranulePrefetcher(store, rates_fn=lambda: {8: 2.0},
                             max_per_tick=8, slack=0.5)
    assert slow.budget_this_tick() == 5        # 0.5/2 / 0.05 = 5
    # a broken estimator degrades to the cap, never raises
    broken = GranulePrefetcher(store, rates_fn=lambda: 1 / 0,
                               max_per_tick=2)
    assert broken.budget_this_tick() == 2


def test_granule_store_metrics_export():
    from dpf_tpu.obs.metrics import (MetricsRegistry,
                                     register_granule_store)
    store, _ = _store(budget_granules=2)
    mr = MetricsRegistry()
    register_granule_store(store, registry=mr)
    store.lease(0).release()
    snap = mr.snapshot()
    assert any(v == 1 for v in
               snap["dpf_registry_granules_resident"]["series"].values())
    assert any(v == 1 for v in
               snap["dpf_registry_granule_promotions"]["series"].values())
    labels = "".join(snap["dpf_registry_granules_resident"]["series"])
    assert 'store="table"' in labels


def test_registry_granule_store_construction():
    from dpf_tpu.serve.registry import TableRegistry
    reg = TableRegistry()
    tbl = _table(256, 4)
    reg.register("big", tbl)
    store = reg.granule_store("big", granule=64)
    assert store.n == 256 and store.granule == 64
    with store.lease(64) as l:
        assert np.array_equal(np.asarray(l.table),
                              expand.permute_table(tbl)[64:128])


# ------------------------------------------------ paged cluster tier


def test_paged_shard_server_parity_and_churn():
    """A paged host assigned 4 granules with budget for 2 serves the
    full-domain batch bit-identical to the oracle, twice in a row
    (granules cross eviction boundaries mid-stream)."""
    from dpf_tpu.parallel.cluster import ClusterShardServer
    n, entry = 1024, 8
    tbl = _table(n, entry)
    dpf = DPF(prf=DPF.PRF_DUMMY)
    dpf.eval_init(tbl)
    keys = [dpf.gen((i * 97) % n, n)[0] for i in range(4)]
    ref = np.asarray(dpf.eval_cpu(keys))
    g = n // 4
    srv = ClusterShardServer(expand.permute_table(tbl),
                             tuple(range(0, n, g)), g,
                             prf_method=DPF.PRF_DUMMY,
                             budget_bytes=2 * g * entry * 4)
    assert srv.paged and srv.granules == tuple(range(0, n, g))
    pk = srv._decode_batch(keys)
    for _ in range(2):
        assert np.array_equal(np.asarray(srv._dispatch_packed(pk)), ref)
    st = srv.store.stats()
    assert st["counters"]["evictions"] > 0     # budget 2 < 4 assigned
    assert st["counters"]["prefetches"] > 0    # next-granule overlap


def test_paged_cluster_end_to_end_parity():
    from dpf_tpu.parallel.cluster import ClusterRouter
    n, entry = 512, 4
    tbl = _table(n, entry)
    dpf = DPF(prf=DPF.PRF_DUMMY)
    dpf.eval_init(tbl)
    # budget below one granule: the host must page (overcommitting
    # around its single pinned granule) yet answers stay bit-exact
    cluster = ClusterRouter.local(
        tbl, hosts=2, oracle=dpf, buckets=(4,),
        host_budget_bytes=(n // 2) * entry * 2)
    try:
        idxs = [3, 250, n - 1, 77]
        keys = [dpf.gen(i, n)[0] for i in idxs]
        out = np.asarray(cluster.submit(keys).result())
        assert np.array_equal(out, np.asarray(dpf.eval_cpu(keys)))
        assert all(nd.server.paged for nd in cluster.hosts.values())
    finally:
        cluster.close()


# --------------------------------------------------- 2D mesh parity


@pytest.mark.parametrize("mesh_shape", [(1, 4, 2), (1, 2, 4),
                                        (2, 2, 2), (1, 8, 1)])
@pytest.mark.parametrize("psum_group", [0, 2])
def test_2d_matches_1d_and_single_chip(eight_devices, mesh_shape,
                                       psum_group):
    from dpf_tpu.parallel import sharded
    nb, nt, nby = mesh_shape
    n, batch, entry = 512, 8, 8
    tbl = _table(n, entry)
    dpf = DPF(prf=DPF.PRF_DUMMY)
    idxs = [(i * 97) % n for i in range(batch)]
    keys = [dpf.gen(i, n) for i in idxs]
    k0s = [k[0] for k in keys]
    dpf.eval_init(tbl)
    single = np.asarray(dpf.eval_tpu(k0s))
    one_d = np.asarray(sharded.ShardedDPFServer(
        tbl, sharded.make_mesh(n_table=8), prf_method=DPF.PRF_DUMMY,
        batch_size=batch).eval(k0s))
    mesh = sharded.make_mesh_2d(n_table=nt, n_byte=nby, n_batch=nb)
    srv = sharded.ShardedDPFServer(tbl, mesh, prf_method=DPF.PRF_DUMMY,
                                   batch_size=batch,
                                   psum_group=psum_group)
    a = np.asarray(srv.eval(k0s))
    assert np.array_equal(a, single)
    assert np.array_equal(a, one_d)
    b = np.asarray(srv.eval([k[1] for k in keys]))
    assert ((a.astype(np.int64) - b).astype(np.int32)
            == tbl[idxs]).all()


def test_2d_rejects_indivisible_entries_and_wrong_scheme(eight_devices):
    from dpf_tpu.parallel import sharded
    mesh = sharded.make_mesh_2d(n_table=4, n_byte=2)
    with pytest.raises(ValueError):
        sharded.place_table(_table(256, 7), mesh)   # 7 % 2 != 0
    with pytest.raises(ValueError):
        sharded.ShardedDPFServer(_table(256, 8), mesh,
                                 prf_method=DPF.PRF_DUMMY,
                                 scheme="sqrtn")


@pytest.mark.skipif(
    not os.environ.get("DPF_RUN_SLOW"),
    reason="large-N 2D fuzz (~1 min of XLA-CPU work); the small-N "
           "parity matrix above pins the program — this runs in the "
           "DPF_RUN_SLOW lane")
def test_2d_large_n_fuzz(eight_devices):
    from dpf_tpu.parallel import sharded
    n, batch, entry = 1 << 16, 4, 16
    tbl = _table(n, entry)
    dpf = DPF(prf=DPF.PRF_CHACHA20)
    idxs = [0, 12345, n - 1, 9999]
    keys = [dpf.gen(i, n) for i in idxs]
    dpf.eval_init(tbl)
    single = np.asarray(dpf.eval_tpu([k[0] for k in keys]))
    for nt, nby in ((4, 2), (2, 4)):
        mesh = sharded.make_mesh_2d(n_table=nt, n_byte=nby)
        srv = sharded.ShardedDPFServer(tbl, mesh,
                                       prf_method=DPF.PRF_CHACHA20,
                                       batch_size=batch)
        assert np.array_equal(np.asarray(srv.eval([k[0] for k in keys])),
                              single), (nt, nby)


# --------------------------------------------- memory-aware planning


def _cost_table():
    from dpf_tpu.plan.twin import CostTable
    return CostTable({("logn", 64): 0.002, ("logn", 128): 0.0035},
                     overhead_s=0.0005)


def test_min_hosts_for_memory():
    from dpf_tpu.plan.capacity import min_hosts_for_memory
    gib = 1 << 30
    assert min_hosts_for_memory(0, gib) == 1
    assert min_hosts_for_memory(gib, gib) == 1
    assert min_hosts_for_memory(gib + 1, gib) == 2
    with pytest.raises(ValueError):
        min_hosts_for_memory(1, 0)


def test_plan_fleet_jointly_monotone_in_load_and_table_bytes():
    from dpf_tpu.plan.capacity import plan_fleet
    trace = [(i * 0.01, 64) for i in range(100)]
    hbm = 1 << 30
    prev_hosts = 0
    for tb in (0, 4 * hbm, 16 * hbm):
        plan = plan_fleet(trace, _cost_table(), label="logn",
                          slo_s=0.05, table_bytes=tb,
                          hbm_bytes_per_host=hbm)
        assert plan["monotone"]                       # in load
        curve = plan["headroom_curve"]
        assert all(curve[i]["hosts"] <= curve[i + 1]["hosts"]
                   for i in range(len(curve) - 1))
        assert plan["hosts"] >= prev_hosts            # in table bytes
        assert plan["hosts"] >= plan["memory"]["hosts_memory_floor"]
        assert plan["memory"]["hbm_source"] == "explicit"
        prev_hosts = plan["hosts"]


def test_plan_fleet_without_table_bytes_unchanged():
    from dpf_tpu.plan.capacity import plan_fleet
    plan = plan_fleet([(i * 0.01, 64) for i in range(50)],
                      _cost_table(), label="logn", slo_s=0.05)
    assert "memory" not in plan
    assert plan["monotone"]


def test_twin_paging_stall_raises_p99_and_overlap_hides_it():
    from dpf_tpu.plan.twin import FleetConfig, simulate
    ct = _cost_table()
    trace = [(i * 0.01, 64) for i in range(150)]
    base = dict(replicas={"logn": 2}, dispatch_blocking=False)
    f0 = FleetConfig(**base)
    assert f0.paging_stall_s() == 0.0
    paged = dict(base, table_bytes=8 << 30,
                 hbm_bytes_per_replica=4 << 30, page_gbps=1024.0)
    f1 = FleetConfig(**paged)
    f2 = FleetConfig(**paged, prefetch_overlap=0.9)
    assert f1.paging_stall_s() == pytest.approx(4 / 1024)
    assert f2.paging_stall_s() == pytest.approx(0.4 / 1024)
    p0, p1, p2 = (simulate(trace, ct, f, seed=0,
                           record_events=False).summary()["p99_ms"]
                  for f in (f0, f1, f2))
    assert p1 > p0                        # under-budget replicas stall
    assert p0 <= p2 < p1                  # prefetch overlap hides most
    # serialization round-trips the paging fields
    fr = FleetConfig.from_dict(f2.as_dict())
    assert fr.paging_stall_s() == f2.paging_stall_s()
    with pytest.raises(ValueError):
        FleetConfig(**dict(base, prefetch_overlap=1.5))
    with pytest.raises(ValueError):
        FleetConfig(**dict(base, page_gbps=0.0))
