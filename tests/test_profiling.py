"""Host-side measurement helpers (``utils/profiling.py``): ``Timer``'s
exit barrier, the nearest-rank quantile, ``EngineCounters`` (latency
ring and histogram, merge, reset, thread safety), ``CacheCounters`` and
the swallowed-error registry."""

import pytest

from dpf_tpu.utils.profiling import Timer


# ----------------------------------------------------------------- Timer

def test_timer_blocks_on_device():
    with Timer() as t:
        pass
    assert t.elapsed >= 0


def test_timer_exit_uses_effects_barrier(monkeypatch):
    import jax

    called = []
    monkeypatch.setattr(jax, "effects_barrier",
                        lambda: called.append(True))
    with Timer():
        pass
    assert called == [True]


def test_timer_exit_blocks_on_noted_outputs(monkeypatch):
    import jax

    blocked = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or x)
    a, b = object(), object()
    with Timer(a).note(b):                # outputs via ctor AND note()
        pass
    assert blocked == [[a, b]]
    blocked.clear()
    with Timer():                         # no outputs: the barrier alone
        pass
    assert blocked == []


# ------------------------------------------------------- EngineCounters

def test_quantile_nearest_rank():
    from dpf_tpu.utils.profiling import quantile
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert quantile(xs, 0.0) == 1.0
    assert quantile(xs, 0.5) == 3.0
    assert quantile(xs, 1.0) == 5.0
    import pytest
    with pytest.raises(ValueError):
        quantile([], 0.5)
    with pytest.raises(ValueError):
        quantile(xs, 1.5)


def test_counters_latency_ring_is_bounded():
    from dpf_tpu.utils.profiling import LATENCY_RING, EngineCounters
    c = EngineCounters()
    assert c.p50 is None and c.quantile(0.99) is None
    for i in range(LATENCY_RING + 10):
        c.note_latency(float(i))
    assert len(c._latencies) == LATENCY_RING
    # the oldest samples were overwritten, not the newest
    assert max(c._latencies) == LATENCY_RING + 9
    assert c.p50 is not None and c.p50 <= c.p95 <= c.p99


def test_counters_reset_zeroes_everything():
    from dpf_tpu.utils.profiling import EngineCounters
    c = EngineCounters(batches_submitted=3, pack_time_s=1.5,
                      deadline_misses=2, shed_batches=1)
    c.note_latency(0.5)
    c.note_dispatch(padded=4, in_flight=3)
    c.reset()
    assert c == EngineCounters()
    assert c._latencies == [] and c.p50 is None


def test_counters_merge_sums_and_pools():
    from dpf_tpu.utils.profiling import EngineCounters
    a = EngineCounters(batches_submitted=2, queries_submitted=10,
                      wait_time_s=0.5, in_flight_hwm=1,
                      shed_queries=3)
    a.note_latency(0.1)
    b = EngineCounters(batches_submitted=4, queries_submitted=7,
                      wait_time_s=0.25, in_flight_hwm=5,
                      deadline_misses=1)
    b.note_latency(0.3)
    b.note_latency(0.2)
    out = a.merge(b)
    assert out is a                       # merges in place, returns self
    assert a.batches_submitted == 6 and a.queries_submitted == 17
    assert a.wait_time_s == 0.75 and a.shed_queries == 3
    assert a.deadline_misses == 1
    assert a.in_flight_hwm == 5           # max, not sum
    assert sorted(a._latencies) == [0.1, 0.2, 0.3]  # rings pooled
    # fold many into one without hand-copying fields
    from functools import reduce
    total = reduce(EngineCounters.merge,
                   [EngineCounters(dispatches=1) for _ in range(3)],
                   EngineCounters())
    assert total.dispatches == 3


def test_counters_merge_downsamples_full_rings_proportionally():
    """Merging two FULL rings must keep samples from both (stride
    downsample), not silently reduce the aggregate quantiles to the
    last ring merged."""
    from dpf_tpu.utils.profiling import LATENCY_RING, EngineCounters
    a, b = EngineCounters(), EngineCounters()
    for _ in range(LATENCY_RING):
        a.note_latency(1.0)               # engine A: all 1 s
        b.note_latency(3.0)               # engine B: all 3 s
    a.merge(b)
    assert len(a._latencies) == LATENCY_RING
    ones = sum(1 for x in a._latencies if x == 1.0)
    threes = sum(1 for x in a._latencies if x == 3.0)
    assert ones > 0 and threes > 0        # both engines represented
    assert abs(ones - threes) <= 2        # ... proportionally
    assert a.p50 in (1.0, 3.0) and a.quantile(0.25) == 1.0


def test_counters_as_dict_rounds_all_floats_generically():
    import dataclasses

    from dpf_tpu.utils.profiling import EngineCounters
    c = EngineCounters(pack_time_s=0.12345678901,
                      dispatch_time_s=1 / 3, wait_time_s=2 / 3)
    d = c.as_dict()
    for f in dataclasses.fields(EngineCounters):
        if f.name.startswith("_"):
            assert f.name not in d        # raw ring stays out
            continue
        assert f.name in d
        v = d[f.name]
        if isinstance(v, float):          # every float field rounded
            assert v == round(v, 6)
    assert d["pack_time_s"] == 0.123457
    assert "latency_ms" not in d          # empty ring -> no quantiles


def test_counters_latency_histogram_accumulates_and_merges():
    from dpf_tpu.utils.profiling import (LATENCY_HIST_BUCKETS_S,
                                         EngineCounters)
    a, b = EngineCounters(), EngineCounters()
    a.note_latency(0.003)                 # le=0.005 bucket
    a.note_latency(0.02)                  # le=0.025
    b.note_latency(0.003)
    b.note_latency(99.0)                  # +Inf bucket
    h = a.merge(b).latency_histogram()
    assert h["buckets"] == list(LATENCY_HIST_BUCKETS_S)
    assert h["count"] == 4 and h["sum"] == pytest.approx(99.026)
    assert h["counts"][LATENCY_HIST_BUCKETS_S.index(0.005)] == 2
    assert h["counts"][LATENCY_HIST_BUCKETS_S.index(0.025)] == 1
    assert h["counts"][-1] == 1           # +Inf
    # the histogram accumulates while the ring forgets: reset drops both
    a.reset()
    assert a.latency_histogram()["count"] == 0


def test_counters_inc_and_notes_are_thread_safe():
    import threading

    from dpf_tpu.utils.profiling import EngineCounters
    c = EngineCounters()

    def work():
        for _ in range(1000):
            c.inc("retries")
            c.note_latency(0.001)
            c.note_dispatch(padded=1, in_flight=2)
    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.retries == 8000              # no lost += updates
    assert c.dispatches == 8000 and c.padded_queries == 8000
    assert c.latency_histogram()["count"] == 8000


def test_note_swallowed_is_thread_safe_and_feeds_stats():
    import threading
    import warnings

    from dpf_tpu.utils.profiling import (EngineCounters, note_swallowed,
                                         swallowed_snapshot)
    site = "test.profiling.swallow-race"
    stats = EngineCounters()
    # absorb the once-per-(site, cls) warning in the main thread first
    # (warnings.catch_warnings mutates global state, so the worker
    # threads must not race through it)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        note_swallowed(site, ValueError("x"), stats)

    def work():
        for _ in range(500):
            note_swallowed(site, ValueError("x"), stats)
    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert swallowed_snapshot()[site] == {"ValueError": 4001}
    assert stats.swallowed_errors == 4001


def test_cache_counters_reset():
    from dpf_tpu.utils.profiling import CacheCounters
    c = CacheCounters(tuning_hits=2, compile_misses=5,
                      compile_time_saved_s=1.5)
    assert c.reset() is c
    assert c == CacheCounters()
    assert c.as_dict()["compile_time_saved_s"] == 0.0


def test_engine_counters_self_merge_is_noop():
    from dpf_tpu.utils.profiling import EngineCounters
    c = EngineCounters()
    c.inc("retries", 3)
    c.note_dispatch(padded=8, in_flight=2)
    c.note_latency(0.01)
    before = c.as_dict()
    assert c.merge(c) is c
    assert c.as_dict() == before


def test_engine_counters_threaded_reset_merge_stress():
    import threading

    from dpf_tpu.utils.profiling import EngineCounters
    workers = [EngineCounters() for _ in range(4)]
    agg = EngineCounters()
    errors = []
    per = 1500

    def write(c):
        try:
            for _ in range(per):
                c.inc("retries")
                c.note_dispatch(padded=4, in_flight=1)
                c.note_latency(1e-4)
        except Exception as e:  # pragma: no cover - the assert below
            errors.append(e)

    def scrape():
        try:
            for _ in range(300):
                snap = EngineCounters()
                for c in workers:
                    snap.merge(c)
                agg.merge(snap)
                agg.as_dict()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def wipe():
        try:
            for _ in range(200):
                agg.reset()
                agg.as_dict()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=write, args=(c,))
               for c in workers]
    threads += [threading.Thread(target=scrape),
                threading.Thread(target=wipe)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    # merge/reset of the aggregate never mutated the sources: the
    # quiesced per-worker totals are exact
    final = EngineCounters()
    for c in workers:
        final.merge(c)
    d = final.as_dict()
    assert d["retries"] == 4 * per
    assert d["dispatches"] == 4 * per
    assert d["padded_queries"] == 4 * per * 4
