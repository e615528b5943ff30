"""The four-chip cell's readers on hand-made records, and the subtree
kernel's byte count by hand."""

import os

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import ROOT


def read(metric, record):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", metric + ".py"),
        "m_" + metric.replace(".", "_")).read(record)


def test_kernel_bytes_by_hand():
    kb = harness.load_module(os.path.join(
        ROOT, "benchmarks", "metrics", "subtree_hbm_share.shard4.py"),
        "m_subtree_hbm").kernel_bytes
    # 2^26 rows of 16 words a chip, 64 keys in tiles of 8, subtrees of
    # 4096 leaves (12 levels): each tile reads the 4.29e9 bytes of digit
    # planes, 16,384 subtrees' seeds, its 24 codeword slots twice, and
    # writes its answer
    per_tile = 4 * 2 ** 26 * 16 + 2 ** 14 * 8 * 16 + 2 * 4 * 8 * 24 * 4 \
        + 8 * 16 * 4
    assert kb(10, 64, 2 ** 26, 16, 8, 4096, 12) == 10 * 8 * per_tile
    assert kb(1, 13, 2 ** 10, 16, 8, 256, 8) == 2 * (
        4 * 2 ** 10 * 16 + 4 * 8 * 16 + 2 * 4 * 8 * 16 * 4 + 8 * 16 * 4)


def test_shard4_readers_on_hand_made_records():
    tr = {"busy_s": 30.0, "window_s": 32.0, "ops_s": 30.0,
          "top_ops": [["%dpf_subtree_contract.1", 100.0],
                      ["%copy.1", 0.5]]}
    rec = {"trace": tr, "answered": 640, "calls": 10, "chips": 4,
           "batch": 64, "shard_rows": 2 ** 26, "entry_words": 16,
           "device_kind": "TPU v5 lite"}
    assert read("device_idle_share.shard4", rec) == 6.25
    assert read("device_us_per_dpf.shard4", rec) == pytest.approx(
        1e6 * 30.0 / 640)
    share = read("subtree_hbm_share.shard4", rec)
    assert share == pytest.approx(100.0 * 80 * (
        4 * 2 ** 26 * 16 + 2 ** 14 * 8 * 16 + 2 * 4 * 8 * 24 * 4
        + 8 * 16 * 4) / (25.0 * 819e9))
    spans = {"mesh_eval": {"count": 4, "total_s": 13.0},
             "mesh_eval.fetch": {"count": 4, "total_s": 12.992}}
    assert read("mesh_host_ms_per_call.shard4",
                {"spans": spans}) == pytest.approx(2.0)


def test_shard4_readers_find_nothing_where_nothing_ran():
    for m in ("device_idle_share.shard4", "device_us_per_dpf.shard4",
              "mesh_host_ms_per_call.shard4", "subtree_hbm_share.shard4"):
        assert read(m, {}) is None
    # the xla scan (the parent's mesh path): no subtree kernel op
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0, "ops_s": 1.0,
                     "top_ops": [["%fusion.3", 1.0]]},
           "calls": 1, "chips": 4, "batch": 64, "shard_rows": 2 ** 26,
           "entry_words": 16, "device_kind": "TPU v5 lite"}
    assert read("subtree_hbm_share.shard4", rec) is None
    assert read("subtree_hbm_share.shard4",
                dict(rec, device_kind="cpu")) is None
