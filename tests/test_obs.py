"""Observability stack (dpf_tpu/obs/, docs/OBSERVABILITY.md): span
tracer nesting/ring, its profiler annotations and GC spans, the metrics
registry's OpenMetrics rendering and weakref collector pruning, the
flight recorder ring, the span wiring of ``DPF.eval_tpu`` and the
serving engine end to end, and the device program's named scopes."""

import gc
import json
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from dpf_tpu.obs import tracer as obs_tracer
from dpf_tpu.obs.flight import FLIGHT, FlightRecorder, flight_dump
from dpf_tpu.obs.metrics import (MetricsRegistry, register_engine,
                                 register_router)
from dpf_tpu.obs.tracer import NULL_SPAN, Tracer, span


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test leaves the process tracer the way it found it: off."""
    yield
    obs_tracer.disable()


# ---------------------------------------------------------------- tracer

def test_span_is_noop_when_disabled():
    obs_tracer.disable()
    assert not obs_tracer.tracing()
    s = span("submit", batch=4)
    assert s is NULL_SPAN                 # shared instance, no alloc
    with s as sp:
        assert sp.set(bucket=8) is sp     # set() chains on the no-op too


def test_enable_records_disable_reverts():
    t = obs_tracer.enable()
    assert obs_tracer.tracing() and obs_tracer.get_tracer() is t
    assert obs_tracer.enable() is t       # idempotent at same capacity
    with span("submit", batch=4):
        pass
    assert t.events()[-1]["name"] == "submit"
    assert t.events()[-1]["attrs"] == {"batch": 4}
    obs_tracer.disable()
    assert span("submit") is NULL_SPAN


def test_nested_spans_parenting_and_self_time():
    t = Tracer()
    with t.span("outer") as outer:
        time.sleep(0.002)
        with t.span("inner") as inner:
            time.sleep(0.002)
    evs = {e["name"]: e for e in t.events()}
    assert evs["inner"]["parent_id"] == outer.span_id
    assert evs["outer"]["parent_id"] is None
    assert inner.parent_id == outer.span_id
    # self time = duration minus direct children; 0.1 us rounding
    assert evs["outer"]["self_us"] == pytest.approx(
        evs["outer"]["dur_us"] - evs["inner"]["dur_us"], abs=0.5)
    assert evs["inner"]["self_us"] == evs["inner"]["dur_us"]


def test_ring_bounded_drop_accounting_and_clear():
    t = Tracer(capacity=4)
    for i in range(6):
        with t.span("s%d" % i):
            pass
    assert len(t.events()) == 4
    assert [e["name"] for e in t.events()] == ["s2", "s3", "s4", "s5"]
    assert t.recorded == 6 and t.dropped == 2
    t.clear()
    assert t.events() == [] and t.recorded == 0 and t.dropped == 0


def test_span_records_exception_class():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("nope")
    assert t.events()[-1]["attrs"]["error"] == "ValueError"


def test_digest_aggregates_self_time_per_name():
    t = Tracer()
    for _ in range(3):
        with t.span("submit"):
            with t.span("pack"):
                pass
    d = t.digest()
    assert d["spans_recorded"] == 6 and d["spans_dropped"] == 0
    by = {s["span"]: s for s in d["top_spans"]}
    assert by["submit"]["count"] == 3 and by["pack"]["count"] == 3
    assert d["host_ms"] >= 0
    assert Tracer().digest() is None      # empty tracer digests to None


def test_threads_get_their_own_nesting_stacks():
    t = Tracer()

    def other():
        with t.span("worker"):
            pass
    with t.span("main"):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    evs = {e["name"]: e for e in t.events()}
    # the worker span must NOT be parented under "main" (other thread)
    assert evs["worker"]["parent_id"] is None
    assert evs["worker"]["tid"] != evs["main"]["tid"]


def _host_events(trace_dir):
    """Every host-plane event of a profiler capture as (name, start_ns,
    end_ns, stats)."""
    import glob

    from jax.profiler import ProfileData
    path = glob.glob(str(trace_dir) + "/**/*.xplane.pb", recursive=True)[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    t = obs_tracer.enable()
    t.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("submit", batch=4, rid=7):
            with span("pack", phase="decode") as sp:
                sp.set(bucket=8)
    finally:
        jax.profiler.stop_trace()
    evs = {n: (s0, s1, st) for n, s0, s1, st in _host_events(tmp_path)
           if n.startswith("dpf.")}
    assert set(evs) == {"dpf.submit", "dpf.pack"}
    (o0, o1, ost), (i0, i1, ist) = evs["dpf.submit"], evs["dpf.pack"]
    assert o0 <= i0 <= i1 <= o1            # nested as in the ring
    assert ost == {"batch": 4, "rid": 7}
    assert ist == {"phase": "decode", "bucket": 8}
    ring = {e["name"]: e for e in t.events()}
    assert ring["pack"]["parent_id"] == ring["submit"]["span_id"]


def test_no_tracer_means_no_span_and_no_gc_hook():
    obs_tracer.disable()
    before = list(gc.callbacks)
    assert span("submit") is NULL_SPAN
    obs_tracer.enable()
    assert obs_tracer._on_gc in gc.callbacks
    obs_tracer.enable()                   # idempotent: hooked once
    assert gc.callbacks.count(obs_tracer._on_gc) == 1
    obs_tracer.disable()
    assert gc.callbacks == before
    assert span("submit") is NULL_SPAN


def test_full_collection_is_a_gc_span_and_younger_ones_are_not():
    t = obs_tracer.enable()
    t.clear()
    gc.collect(0)
    gc.collect(1)
    with span("outer"):
        gc.collect(2)
    gcs = [e for e in t.events() if e["name"] == "gc"]
    assert len(gcs) == 1
    assert gcs[0]["attrs"]["generation"] == 2
    assert gcs[0]["attrs"]["collected"] >= 0
    outer = [e for e in t.events() if e["name"] == "outer"][0]
    assert gcs[0]["parent_id"] == outer["span_id"]


def _small_dpf(n=1024, e=7):
    from dpf_tpu import DPF
    dpf = DPF(prf=DPF.PRF_DUMMY)
    table = np.random.default_rng(3).integers(
        0, 2 ** 31, (n, e), dtype=np.int64).astype(np.int32)
    dpf.eval_init(table)
    return dpf, [dpf.gen((i * 31) % n, n)[0] for i in range(6)]


def test_eval_tpu_spans_decode_dispatch_fetch_per_chunk():
    dpf, keys = _small_dpf()
    dpf.BATCH_SIZE = 4                    # six keys: two chunks
    ref = np.asarray(dpf.eval_tpu(keys))  # compile outside the record
    t = obs_tracer.enable()
    t.clear()
    assert np.array_equal(np.asarray(dpf.eval_tpu(keys)), ref)
    evs = t.events()
    top = [e for e in evs if e["name"] == "eval_tpu"]
    assert len(top) == 1 and top[0]["attrs"] == {"batch": 6}
    for part in ("decode", "dispatch", "fetch"):
        kids = [e for e in evs if e["name"] == "eval_tpu." + part]
        assert len(kids) == 2, part       # once per chunk
        assert all(k["parent_id"] == top[0]["span_id"] for k in kids)
    assert [e["attrs"]["batch"] for e in evs
            if e["name"] == "eval_tpu.decode"] == [4, 2]


@pytest.mark.parametrize("construction,kernel", [
    ("logn", "xla"), ("logn.dispatch", "dispatch"), ("radix4", "xla"),
    ("sqrtn", "xla")])
def test_dispatch_spans_carry_the_resolved_kernel(construction, kernel):
    """``eval_tpu.dispatch`` and the engine's ``dispatch`` name the kernel
    each dispatch resolved, so a trace shows which path ran."""
    from dpf_tpu import DPF
    from dpf_tpu.utils.config import EvalConfig
    cfg = {"logn": None,
           "logn.dispatch": EvalConfig(prf_method=DPF.PRF_DUMMY,
                                       kernel_impl="dispatch"),
           "radix4": EvalConfig(prf_method=DPF.PRF_DUMMY, radix=4),
           "sqrtn": EvalConfig(prf_method=DPF.PRF_DUMMY, scheme="sqrtn"),
           }[construction]
    dpf = DPF(prf=DPF.PRF_DUMMY, config=cfg)
    n = 1024
    dpf.eval_init(np.arange(n * 3, dtype=np.int32).reshape(n, 3))
    keys = [dpf.gen((i * 31) % n, n)[0] for i in range(3)]
    engine = dpf.serving_engine(buckets=(4,), max_in_flight=1)
    ref = np.asarray(dpf.eval_tpu(keys))  # compile outside the record
    engine.submit(keys).result()
    t = obs_tracer.enable()
    t.clear()
    assert np.array_equal(np.asarray(dpf.eval_tpu(keys)), ref)
    assert np.array_equal(engine.submit(keys).result(), ref)
    evs = t.events()
    for name in ("eval_tpu.dispatch", "dispatch"):
        spans = [e for e in evs if e["name"] == name]
        assert len(spans) == 1, name
        assert spans[0]["attrs"]["kernel"] == kernel, name


def test_mesh_eval_spans_decode_dispatch_fetch():
    """``ShardedDPFServer.eval`` records ``mesh_eval`` >
    ``mesh_eval.decode`` / ``.dispatch`` (naming the resolved kernel) /
    ``.fetch``, as ``DPF.eval_tpu`` does."""
    import jax
    from dpf_tpu import DPF
    from dpf_tpu.parallel import sharded
    n = 1024
    dpf = DPF(prf=DPF.PRF_DUMMY)
    keys = [dpf.gen((i * 31) % n, n)[0] for i in range(3)]
    srv = sharded.ShardedDPFServer(
        np.arange(n * 3, dtype=np.int32).reshape(n, 3),
        sharded.make_mesh(n_table=4, devices=jax.devices()[:4]),
        prf_method=DPF.PRF_DUMMY, batch_size=3)
    ref = srv.eval(keys)                  # compile outside the record
    t = obs_tracer.enable()
    t.clear()
    assert np.array_equal(srv.eval(keys), ref)
    evs = t.events()
    top = [e for e in evs if e["name"] == "mesh_eval"]
    assert len(top) == 1 and top[0]["attrs"] == {"batch": 3}
    for part in ("decode", "dispatch", "fetch"):
        kids = [e for e in evs if e["name"] == "mesh_eval." + part]
        assert len(kids) == 1, part
        assert kids[0]["parent_id"] == top[0]["span_id"]
    dispatch = [e for e in evs if e["name"] == "mesh_eval.dispatch"][0]
    assert dispatch["attrs"]["kernel"] == "xla"


def test_backpressure_span_only_when_the_window_is_full():
    dpf, keys = _small_dpf()
    engine = dpf.serving_engine(buckets=(4,), max_in_flight=1)
    engine.submit(keys[:1]).result()      # compile outside the record
    t = obs_tracer.enable()
    t.clear()
    first = engine.submit(keys[:1])
    second = engine.submit(keys[1:2])
    second.result()
    evs = t.events()
    subs = {e["attrs"]["rid"]: e for e in evs if e["name"] == "submit"}
    bps = [e for e in evs if e["name"] == "backpressure"]
    assert len(bps) == 1                  # the second submit's, only
    assert bps[0]["parent_id"] == subs[second.rid]["span_id"]
    waits = [e for e in evs if e["name"] == "wait"
             and e["parent_id"] == bps[0]["span_id"]]
    assert [w["attrs"]["rid"] for w in waits] == [first.rid]


def test_spans_of_one_request_share_its_rid():
    dpf, keys = _small_dpf()
    engine = dpf.serving_engine(buckets=(2, 4), max_in_flight=2)
    engine.submit(keys[:4]).result()      # compile outside the record
    t = obs_tracer.enable()
    t.clear()
    futs = [engine.submit(keys[:6]), engine.submit(keys[:1])]
    engine.drain()
    assert futs[1].rid == futs[0].rid + 1  # counted per engine
    for fut, parts in zip(futs, (2, 1)):
        mine = [e for e in t.events()
                if e.get("attrs", {}).get("rid") == fut.rid]
        names = [e["name"] for e in mine]
        assert names.count("submit") == 1 and names.count("decode") == 1
        assert names.count("wait") == parts
    assert np.array_equal(futs[0].result(),
                          np.asarray(dpf.eval_tpu(keys[:6])))


# -------------------------------------------- device program named scopes

def _scopes(hlo_text):
    import re
    return {p for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for p in name.split("/") if p.startswith("dpf.")}


def test_fused_program_carries_dpf_scopes():
    import jax
    import jax.numpy as jnp

    from dpf_tpu.core import expand
    n, u32 = 1 << 10, jnp.uint32

    def fn(cw1, cw2, last, t):
        return expand.expand_and_contract(
            cw1, cw2, last, t, depth=10, prf_method=2, chunk_leaves=256)
    S = jax.ShapeDtypeStruct
    text = jax.jit(fn).lower(S((4, 64, 4), u32), S((4, 64, 4), u32),
                             S((4, 4), u32),
                             S((n, 16), jnp.int32)).compile().as_text()
    assert {"dpf.frontier", "dpf.subtree", "dpf.contract", "dpf.prf",
            "dpf.cw_add"} <= _scopes(text)


def test_mesh_program_carries_frontier_subtree_psum_scopes():
    """The mesh program names its phases on the device trace: the
    frontier, each shard's subtrees and the cross-chip psum."""
    import jax
    import jax.numpy as jnp

    from dpf_tpu.parallel import sharded
    n, u32 = 1 << 10, jnp.uint32
    mesh = sharded.make_mesh(n_table=4, devices=jax.devices()[:4])

    def fn(cw1, cw2, last, t):
        return sharded.eval_sharded(cw1, cw2, last, t, depth=10,
                                    prf_method=2, chunk_leaves=64,
                                    mesh=mesh)
    S = jax.ShapeDtypeStruct
    text = jax.jit(fn).lower(S((4, 64, 4), u32), S((4, 64, 4), u32),
                             S((4, 4), u32),
                             S((n, 16), jnp.int32)).compile().as_text()
    assert {"dpf.frontier", "dpf.subtree", "dpf.psum"} <= _scopes(text)


def test_dispatch_path_level_programs_carry_their_phase():
    import jax
    import jax.numpy as jnp

    from dpf_tpu.core import expand
    S, u32 = jax.ShapeDtypeStruct, jnp.uint32
    args = (S((4, 8, 4), u32), S((4, 2, 4), u32), S((4, 2, 4), u32),
            2, None, None)
    for fn, scope in ((expand._frontier_step_jit, "dpf.frontier"),
                      (expand._subtree_step_jit, "dpf.subtree")):
        got = _scopes(fn.lower(*args).compile().as_text())
        assert {scope, "dpf.prf", "dpf.cw_add"} <= got, (scope, got)


class _Fake:
    """Attribute bag that supports weak references (register_engine /
    register_router hold their subject weakly; SimpleNamespace cannot
    be weak-referenced)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# --------------------------------------------------------------- metrics

def test_counter_gauge_basics_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("req", "requests")
    c.inc()
    c.labels(construction="logn").inc(2)
    assert c.value == 1
    assert c.labels(construction="logn").value == 2
    with pytest.raises(ValueError):
        c.inc(-1)                         # counters only go up
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    assert g.value == 4.0
    assert reg.counter("req") is c        # create-or-return by name
    with pytest.raises(ValueError):
        reg.gauge("req")                  # one meaning per name


def test_histogram_buckets_cumulative_and_fold():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    rows = h.samples()
    by = {extra: v for _, _, extra, v in rows}
    assert by[(("le", "0.1"),)] == 1      # cumulative le-bucket counts
    assert by[(("le", "1"),)] == 2
    assert by[(("le", "+Inf"),)] == 3
    assert by[()] in (3, 5.55)            # _sum and _count rows
    h.observe_counts([1, 0, 0], 0.05, 1)  # fold pre-aggregated counts
    assert h.samples()[0][3] == 2         # le=0.1 now cumulative 2


def test_openmetrics_text_format():
    reg = MetricsRegistry()
    reg.counter("dpf_x", "help text").labels(k="v").inc(2)
    reg.gauge("dpf_y").set(1.5)
    text = reg.openmetrics()
    assert "# HELP dpf_x help text" in text
    assert "# TYPE dpf_x counter" in text
    assert 'dpf_x_total{k="v"} 2' in text
    assert "# TYPE dpf_y gauge" in text
    assert "dpf_y 1.5" in text
    assert text.endswith("# EOF\n")


def test_snapshot_is_json_ready():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["c"]["kind"] == "counter"
    assert snap["h"]["series"]["()"]["count"] == 1


def test_weakref_collector_prunes_on_gc():
    reg = MetricsRegistry()

    class Obj:
        pass
    obj = Obj()
    reg.watch(obj, lambda o: [("dpf_live", "gauge", "", {}, 1.0)])
    assert "dpf_live 1" in reg.openmetrics()
    del obj
    gc.collect()
    assert "dpf_live" not in reg.openmetrics()
    assert reg._collectors == []          # pruned, not just skipped


def test_broken_collector_never_breaks_the_scrape():
    from dpf_tpu.utils.profiling import swallowed_snapshot
    reg = MetricsRegistry()
    reg.counter("dpf_ok").inc()
    reg.register_collector(lambda: 1 / 0)
    with pytest.warns(RuntimeWarning):
        text = reg.openmetrics()
    assert "dpf_ok_total 1" in text
    assert "ZeroDivisionError" in str(
        swallowed_snapshot().get("obs.metrics.collector", {}))


def test_register_engine_exports_counters_and_histogram():
    from dpf_tpu.utils.profiling import EngineCounters
    reg = MetricsRegistry()
    stats = EngineCounters(batches_submitted=3, queries_submitted=40)
    stats.note_latency(0.003)
    eng = _Fake(label="e1", stats=stats)
    register_engine(eng, reg)
    text = reg.openmetrics()
    assert 'dpf_engine_batches_submitted_total{engine="e1"} 3' in text
    assert 'dpf_engine_latency_p50_seconds{engine="e1"}' in text
    assert ('dpf_engine_latency_seconds_bucket{engine="e1",le="0.005"} 1'
            in text)
    assert 'dpf_engine_latency_seconds_count{engine="e1"} 1' in text


def test_register_router_exports_breaker_and_cost_series():
    reg = MetricsRegistry()
    rt = _Fake(
        breakers={"logn": SimpleNamespace(state="open", opens=2)},
        _costs={("logn", 16): 0.001},
        route_counts={"logn": 3},
        routed_from_counts={"cost-model": 3})
    register_router(rt, reg)
    text = reg.openmetrics()
    assert 'dpf_breaker_state{construction="logn"} 1' in text
    assert 'dpf_breaker_opens_total{construction="logn"} 2' in text
    assert ('dpf_router_cost_seconds{bucket="16",construction="logn"} '
            '0.001' in text)
    assert 'dpf_router_routes_total{construction="logn"} 3' in text
    assert 'dpf_router_routed_from_total{source="cost-model"} 3' in text


# ---------------------------------------------------------------- flight

def test_flight_ring_seq_dump_and_clear(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(6):
        fr.record("route", construction="logn", arrival=i)
    evs = fr.dump()
    assert len(evs) == 4 and fr.recorded == 6
    assert [e["seq"] for e in evs] == [3, 4, 5, 6]  # oldest first
    assert [e["arrival"] for e in fr.dump(last=2)] == [4, 5]
    assert all(e["t"] >= 0 for e in evs)
    p = tmp_path / "flight.jsonl"
    assert fr.export_jsonl(str(p)) == 4
    assert json.loads(p.read_text().splitlines()[-1])["seq"] == 6
    fr.clear()
    assert fr.dump() == [] and fr.recorded == 6  # monotonic metric


def test_flight_record_never_raises():
    fr = FlightRecorder(capacity=2)
    fr.record("weird", payload=object())  # non-JSON attr still records
    assert fr.dump()[-1]["kind"] == "weird"


def test_global_flight_dump_tail():
    mark = FLIGHT.recorded
    FLIGHT.record("shed", reason="test", batch=9)
    tail = flight_dump(last=1)
    assert tail[-1]["kind"] == "shed" and tail[-1]["seq"] == mark + 1


# ----------------------------------------------- engine wiring (e2e)

def test_engine_emits_spans_and_registers_metrics():
    from dpf_tpu import DPF
    from dpf_tpu.obs.metrics import REGISTRY
    dpf = DPF(prf=DPF.PRF_DUMMY)
    table = np.random.default_rng(3).integers(
        0, 2 ** 31, (256, 7), dtype=np.int64).astype(np.int32)
    dpf.eval_init(table)
    keys = [dpf.gen((i * 31) % 256, 256)[0] for i in range(6)]
    engine = dpf.serving_engine(buckets=(4, 8), max_in_flight=2)
    t = obs_tracer.enable()
    t.clear()
    futs = [engine.submit(keys[:b]) for b in (1, 3, 6)]
    engine.drain()
    for b, fut in zip((1, 3, 6), futs):
        ref = np.asarray(dpf.eval_tpu(keys[:b]))
        assert np.array_equal(fut.result(), ref)
    names = {e["name"] for e in t.events()}
    assert {"submit", "admit", "pack", "dispatch",
            "wait", "decode"} <= names
    subs = [e for e in t.events() if e["name"] == "submit"]
    assert [e["attrs"]["batch"] for e in subs] == [1, 3, 6]
    # children of submit are parented under it (host-side flame graph)
    packs = [e for e in t.events() if e["name"] == "pack"]
    assert all(e["parent_id"] is not None for e in packs)
    # the engine self-registered: its series are scrapeable
    assert "dpf_engine_batches_submitted_total" in REGISTRY.openmetrics()


# ----------------------------------------------------- ring capacity knobs

def test_flight_ring_env_knob_and_drop_accounting(monkeypatch):
    from dpf_tpu.obs import flight as flight_mod
    monkeypatch.setenv("DPF_FLIGHT_RING", "4")
    fr = FlightRecorder()
    assert fr.capacity == 4
    for i in range(6):
        fr.record("x", i=i)
    assert fr.recorded == 6 and fr.dropped == 2
    assert [e["i"] for e in fr.dump()] == [2, 3, 4, 5]
    # an explicit capacity beats the env knob; garbage falls back to
    # the default
    assert FlightRecorder(capacity=7).capacity == 7
    monkeypatch.setenv("DPF_FLIGHT_RING", "not-a-number")
    assert FlightRecorder().capacity == flight_mod.FLIGHT_RING


def test_span_ring_env_knob(monkeypatch):
    monkeypatch.setenv("DPF_SPAN_RING", "16")
    assert Tracer()._ring.maxlen == 16
    assert Tracer(capacity=5)._ring.maxlen == 5
    t = obs_tracer.enable()
    try:
        assert t._ring.maxlen == 16
    finally:
        obs_tracer.disable()
    monkeypatch.delenv("DPF_SPAN_RING")
    assert Tracer()._ring.maxlen == obs_tracer.SPAN_RING


def test_flight_dropped_metric_exported():
    # the process collector (global REGISTRY) exports the global
    # flight recorder's drop counter; the drop path itself is covered
    # by test_flight_ring_env_knob_and_drop_accounting
    from dpf_tpu.obs.metrics import REGISTRY
    snap = REGISTRY.snapshot()
    assert snap["dpf_flight_events_dropped"]["kind"] == "counter"
    assert "dpf_flight_events_dropped_total" in REGISTRY.openmetrics()
