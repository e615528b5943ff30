"""The readers of the per-layer metrics that come from the program's own
spans, each fed a record shaped like ``harness.span_totals``: the value,
the reading where the span is absent, and ``None`` where nothing was
recorded."""

import os

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import ROOT


def read(metric, record):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", metric + ".py"),
        "span_metric_" + metric.replace(".", "_")).read(record)


def totals(**spans):
    """{name: (count, total_s)} -> what ``harness.span_totals`` makes."""
    return {"spans": {k.replace("__", "."): {"count": c, "total_s": t,
                                             "self_s": t}
                      for k, (c, t) in spans.items()}}


def test_api_host_ms_per_call():
    rec = totals(eval_tpu=(4, 0.82), eval_tpu__fetch=(4, 0.80),
                 eval_tpu__decode=(4, 0.008))
    assert read("api_host_ms_per_call.batch", rec) == pytest.approx(5.0)
    # every call fetched nothing: the whole call is host time
    rec = totals(eval_tpu=(2, 0.004))
    assert read("api_host_ms_per_call.batch", rec) == pytest.approx(2.0)
    # a program without the span, and a record without spans
    assert read("api_host_ms_per_call.batch",
                totals(submit=(3, 0.1))) is None
    assert read("api_host_ms_per_call.batch", {}) is None


def test_engine_backpressure_ms_per_submit():
    rec = totals(submit=(10, 1.0), backpressure=(4, 0.05),
                 wait=(12, 0.2))
    got = read("engine_backpressure_ms_per_submit.serve", rec)
    assert got == pytest.approx(5.0)     # submits that did not wait count
    assert read("engine_backpressure_ms_per_submit.serve",
                totals(submit=(10, 1.0))) == 0.0
    assert read("engine_backpressure_ms_per_submit.serve",
                totals(eval_tpu=(1, 0.1))) is None
    assert read("engine_backpressure_ms_per_submit.serve", {}) is None


def test_host_gc_ms():
    rec = totals(submit=(10, 1.0), gc=(2, 0.25))
    assert read("host_gc_ms.serve", rec) == pytest.approx(250.0)
    assert read("host_gc_ms.serve", totals(submit=(10, 1.0))) == 0.0
    assert read("host_gc_ms.serve", {"spans": {}}) is None
    assert read("host_gc_ms.serve", {}) is None


def test_each_new_metric_is_declared_for_its_cell():
    want = {"api_host_ms_per_call.batch": "chacha20-n16.batch512",
            "engine_backpressure_ms_per_submit.serve": "chacha20-n16.open1",
            "host_gc_ms.serve": "chacha20-n16.open1"}
    for metric, cell in want.items():
        names = [m["name"] for m in harness.find_cell(cell).per_layer]
        assert metric in names
        assert callable(harness.find_cell(cell).reader(metric).read)
