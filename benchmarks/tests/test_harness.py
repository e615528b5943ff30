"""The harness finds every piece by name, takes a new cell from new files
alone, refuses to measure without a chip, and reduces recorded runs to
fixed numbers."""

import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks import harness, tracereduce
from benchmarks.tests.conftest import ROOT, copy_benchmark, cpu_device

FIXTURES = os.path.join(ROOT, "benchmarks", "fixtures")
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = harness.find_cell(cell)
    drv = c.driver()
    for fn in ("setup", "window", "server1", "release"):
        assert callable(getattr(drv, fn))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)
        assert m["moves"] in e2e
    assert isinstance(c.config["prf_id"], int)
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "prfs",
                                       c.config["prf"] + ".py"))


def test_names_and_files_follow_the_contract():
    ok = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.-"
    names = [m["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for m in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert set(n) <= set(ok) and len(n) <= 64
    for path in glob.glob(os.path.join(ROOT, "benchmarks", "**", "*"),
                          recursive=True):
        rel = os.path.relpath(path, ROOT)
        if "__pycache__" not in rel:
            assert set(rel) <= set(ok + "/"), rel
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_a_new_cell_takes_new_files_only(tmp_path):
    """A deployment on another PRF (AES-128, the program's PRF 3), a mix
    and a metric, each added as a new file, run as a new cell."""
    root = copy_benchmark(str(tmp_path))
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(root, "**", "*.*"), recursive=True)}
    bench = os.path.join(root, "benchmarks")
    cfg = json.load(open(os.path.join(bench, "configs",
                                      "ref-chacha20-n16.json")))
    cfg.update(name="new-aes128-n10", log2_rows=10, prf="aes128", prf_id=3,
               check={"recover": 32, "reference": 2})
    json.dump(cfg, open(os.path.join(bench, "configs",
                                     "new-aes128-n10.json"), "w"))
    json.dump({"driver": "closed_loop", "batch": 64, "pool_keys": 512},
              open(os.path.join(bench, "traffic", "batch64.json"), "w"))
    with open(os.path.join(bench, "metrics", "calls.batch64.py"), "w") as f:
        f.write("def read(record):\n    return record.get('calls')\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "new-aes128-n10",
                            "file": "benchmarks/configs/new-aes128-n10.json",
                            "source": "x", "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new.batch64",
                              "config": "new-aes128-n10",
                              "traffic": "batch64", "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append("new.batch64")
    spec["per_layer"].append({"name": "calls.batch64", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "dpfs_per_s",
                              "workloads": ["new.batch64"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data
    cell = harness.find_cell("new.batch64", root)
    assert cell.config["prf_id"] == 3 and cell.traffic["batch"] == 64
    assert [m["name"] for m in cell.per_layer] == ["calls.batch64"]
    assert cell.reader("calls.batch64").read({"calls": 7}) == 7
    out = harness.run_cell("new.batch64", 99, 0.5, False, time.monotonic(),
                           root=root, look=cpu_device)
    assert out["correct"] is True and out["attempted"] % 64 == 0
    assert set(out["metrics"]) == {"dpfs_per_s", "setup_s"}


def _no_result(proc):
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "chacha20-n16.batch512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and _no_result(p)
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """Past the look for a chip, a checkout without the program fails
    before it prints anything."""
    root = copy_benchmark(str(tmp_path))
    code = ("import sys, time; sys.path.insert(0, %r)\n"
            "from benchmarks import harness\n"
            "harness.run_cell('chacha20-n16.batch512', 1, 1.0, False, "
            "time.monotonic(), root=%r, look=lambda c, r: {})\n"
            % (root, root))
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""))
    assert p.returncode != 0 and _no_result(p)
    assert "dpf_tpu" in p.stderr


@pytest.mark.parametrize("block_bytes", [8 * 16 * 100 + 8, 8 * 16 * 333,
                                         harness.TABLE_BLOCK_BYTES])
def test_table_in_row_blocks_is_the_one_shot_table(monkeypatch,
                                                   block_bytes):
    """Drawn in row blocks (here 100, 333 and all 4,096 rows), the table is
    the one-shot int64 draw cast to int32."""
    monkeypatch.setattr(harness, "TABLE_BLOCK_BYTES", block_bytes)
    cfg = {"log2_rows": 12, "entry_words": 16}
    seed = 2 ** 33 + 5
    want = harness.rng_for(seed, 0).integers(
        -2 ** 31, 2 ** 31, (4096, 16), dtype=np.int64).astype(np.int32)
    got = harness.make_table(cfg, seed)
    assert got.dtype == np.int32 and np.array_equal(got, want)


def test_existing_table_is_unchanged():
    """The ``ref-chacha20-n16`` table of seed 1, byte for byte as the
    one-shot draw made it."""
    import hashlib
    cfg = harness.find_cell("chacha20-n16.batch512").config
    digest = hashlib.sha256(harness.make_table(cfg, 1).tobytes())
    assert digest.hexdigest() == ("4b17359bf81459239398ea0ff4b7afc1"
                                  "d72dadf61abfc08d67297b9c85a7e7a2")


def test_existing_configs_compare_on_the_host():
    for cell in SPEC["workloads"]:
        assert harness.reference_place(
            harness.find_cell(cell["name"]).config) == "host"


def test_arrivals_and_keys_follow_the_seed():
    from benchmarks.drivers import open_loop
    a = open_loop.arrival_times(30.0, 10.0, 2 ** 33 + 1)
    b = open_loop.arrival_times(30.0, 10.0, 2 ** 33 + 1)
    c = open_loop.arrival_times(30.0, 10.0, 7)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == 300 and a[-1] < 10.5
    # every seed offers the same gaps, in another order
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(c, prepend=0)))
    from dpf_tpu import DPF
    d = DPF(prf=DPF.PRF_CHACHA20)
    k1 = harness.mint_keys(d, 1 << 10, 9, 2 ** 33 + 1, 2)
    k2 = harness.mint_keys(d, 1 << 10, 9, 2 ** 33 + 1, 2)
    k3 = harness.mint_keys(d, 1 << 10, 9, 8, 2)
    for x, y in zip(k1, k2):
        assert np.array_equal(x, y)
    assert not np.array_equal(k1[1], k3[1])
    assert len({r.tobytes() for r in k1[1]}) == 9


def test_trace_reduction_by_hand():
    ms = 1_000_000
    compact = {
        "device": {"/device:TPU:0": {"ops": [
            ["outer", 0, 40 * ms], ["inner", 10 * ms, 10 * ms],
            ["b", 60 * ms, 25 * ms], ["late", 150 * ms, 10 * ms]],
            "modules": [["jit_f", 0, 40 * ms], ["jit_f", 55 * ms, 30 * ms]]}},
        "host": [["bench.window", 0, 100 * ms],
                 ["bench.eval_tpu", 0, 45 * ms],
                 ["bench.result", 38 * ms, 30 * ms]]}
    r = tracereduce.reduce(compact)
    assert r["window_s"] == pytest.approx(0.1)
    # the second program run starts 5 ms before its first op
    assert r["busy_s"] == pytest.approx(0.07)
    assert r["ops_s"] == pytest.approx(0.065)
    assert r["program_s"] == pytest.approx(0.07)
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["top_ops"] == [["outer", pytest.approx(0.03)],
                            ["b", pytest.approx(0.025)],
                            ["inner", pytest.approx(0.01)]]
    assert r["top_gaps"] == [["bench.result", pytest.approx(0.015)],
                             ["(no bench span)", pytest.approx(0.015)]]


def test_no_device_op_in_the_window_is_an_error():
    with pytest.raises(ValueError):
        tracereduce.reduce({"device": {}, "host": [["bench.window", 0, 9]]})


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIXTURES, "*.record.json"))))
def test_readers_on_recorded_runs(path):
    """Each per-layer metric, read again from a recorded traced run on
    the chip, gives the value that run printed."""
    rec = json.load(open(path))
    cell = harness.find_cell(rec["cell"])
    assert {m["name"] for m in cell.per_layer} == set(rec["metrics"])
    for m in cell.per_layer:
        got = cell.reader(m["name"]).read(rec["record"])
        assert got == pytest.approx(rec["metrics"][m["name"]]["value"],
                                    rel=1e-12)


def test_recorded_chip_trace_reduces_to_fixed_numbers():
    """600 ms of a traced `chacha20-n16.batch512` run on one TPU v5 lite:
    43,623 op events, 3 program runs, 3 idle gaps between calls."""
    r = tracereduce.reduce(tracereduce.load_saved(os.path.join(
        FIXTURES, "chacha20-n16.batch512.window600ms.trace.json.gz")))
    assert r["window_s"] == pytest.approx(0.6, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.592023341, rel=1e-9)
    assert r["ops_s"] == pytest.approx(0.592021044, rel=1e-9)
    assert r["idle_share"] == pytest.approx(0.013294431666666662, rel=1e-6)
    assert r["top_ops"][0] == ["%shift-right-logical_or_fusion.8836",
                               pytest.approx(0.016862649, rel=1e-9)]
    assert [n for n, _ in r["top_gaps"]] == ["bench.eval_tpu"] * 3
    assert r["top_gaps"][0][1] == pytest.approx(0.003339503, rel=1e-9)


def test_readers_on_hand_made_records():
    def read(metric, record):
        return harness.load_module(
            os.path.join(ROOT, "benchmarks", "metrics", metric + ".py"),
            "m_" + metric.replace(".", "_")).read(record)
    tr = {"busy_s": 0.75, "window_s": 1.0}
    assert read("device_idle_share.batch", {"trace": tr}) == 25.0
    assert read("device_idle_share.serve", {"trace": tr}) == 25.0
    assert read("device_us_per_dpf.batch",
                {"trace": tr, "answered": 1500}) == 500.0
    assert read("engine_pad_share.serve", {"engine": {
        "padded_queries": 630, "queries_submitted": 10,
        "dispatches": 10}}) == 98.4375
    spans = {"submit": {"count": 4, "total_s": 1.0},
             "admit": {"count": 4, "total_s": 0.001},
             "pack": {"count": 8, "total_s": 0.004},
             "dispatch": {"count": 4, "total_s": 0.003},
             "wait": {"count": 2, "total_s": 0.5}}
    assert read("engine_host_ms_per_submit.serve",
                {"spans": spans}) == pytest.approx(2.0)
    late = [0.001 * i for i in range(101)]
    assert read("client_late_ms.serve",
                {"client_late_s": late}) == pytest.approx(95.0)
    for m in ("device_idle_share.batch", "device_us_per_dpf.batch",
              "engine_pad_share.serve", "engine_host_ms_per_submit.serve",
              "client_late_ms.serve"):
        assert read(m, {}) is None
