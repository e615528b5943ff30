"""Utility-layer tests: scrape protocol, plots, checkpointing, CPU baseline."""

import json
import os

import numpy as np
import pytest

from dpf_tpu.utils import scrape


def test_scrape_roundtrip(tmp_path):
    log = tmp_path / "run1.log"
    log.write_text("noise\n{'entries': 128, 'dpfs_per_sec': 10}\n"
                   "more noise\n"
                   + json.dumps({"entries": 256, "dpfs_per_sec": 20}) + "\n")
    d = scrape.scrape_file(str(log))
    assert d == {"entries": 256, "dpfs_per_sec": 20}  # last line wins

    (tmp_path / "run2.log").write_text("{'entries': 512, 'x': 1}\n")
    rows = scrape.scrape_dir(str(tmp_path / "*.log"))
    assert len(rows) == 2
    out = scrape.to_csv(rows, str(tmp_path / "out.csv"))
    text = open(out).read()
    assert "entries" in text and "512" in text


def test_scrape_ignores_non_dicts(tmp_path):
    log = tmp_path / "bad.log"
    log.write_text("{not a dict\n[1,2,3]\nplain\n")
    assert scrape.scrape_file(str(log)) is None


def test_plots(tmp_path):
    pytest.importorskip("matplotlib")
    from dpf_tpu.apps import plots
    sweep_results = [
        {"config": {"bin_fraction": 0.1, "queries_to_hot": q},
         "mean_recovered": 0.2 * q} for q in (1, 2, 4)]
    p1 = plots.plot_recovery_vs_queries(sweep_results,
                                        str(tmp_path / "r.png"))
    pts = [{"latency_ms": 10.0 * i, "mean_recovered": 0.3 + 0.2 * i}
           for i in (1, 2, 3)]
    p2 = plots.plot_latency_vs_recovery(pts, str(tmp_path / "l.png"),
                                        frontier=pts[:2])
    p3 = plots.plot_throughput_table(
        [{"prf": "AES128", "entries": 2 ** k, "dpfs_per_sec": 10 ** k}
         for k in (14, 16)], str(tmp_path / "t.png"))
    for p in (p1, p2, p3):
        assert os.path.getsize(p) > 1000


def test_checkpoint_roundtrip(tmp_path):
    from dpf_tpu.models import checkpoint, datasets, rec
    ds = datasets.make_rec_dataset(n_items=50, n_users=10,
                                   samples_per_user=2)
    model, params = rec.train_rec_model(ds, epochs=1)
    path = str(tmp_path / "ckpt")
    checkpoint.save_params(path, params)
    restored = checkpoint.load_params(path, like=params)
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        assert np.allclose(np.asarray(a), np.asarray(b))

    # train_or_restore must hit the checkpoint, not retrain
    calls = {"train": 0}

    def init_fn():
        return model, params

    def train_fn():
        calls["train"] += 1
        return model, params

    _, p2 = checkpoint.train_or_restore(path, init_fn, train_fn)
    assert calls["train"] == 0
    assert np.allclose(np.asarray(jax.tree_util.tree_leaves(p2)[0]),
                       np.asarray(jax.tree_util.tree_leaves(params)[0]))


def test_latency_benchmark():
    from dpf_tpu import PRF_DUMMY
    from dpf_tpu.utils.bench import test_dpf_latency
    r = test_dpf_latency(N=256, entrysize=4, prf=PRF_DUMMY, reps=2,
                         quiet=True)
    assert r["mode"] == "latency" and r["latency_ms"] > 0


def test_cpu_baseline_harness():
    from dpf_tpu import native
    if not native.available():
        pytest.skip("native library unavailable")
    import cpu_baseline
    r = cpu_baseline.run(n_entries=512, entry_size=4, batch=8, reps=1,
                         threads=2, prf=0)
    assert r["dpfs_per_sec"] > 0 and r["backend"] == "cpu-native"
