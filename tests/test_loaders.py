"""Real-data loaders (models/loaders.py) against tiny checked-in fixtures.

Each loader must produce the same dataclass contract as the synthetic
generators (datasets.py) so the whole experiment stack runs unchanged on
real files.
"""

import os

import numpy as np
import pytest

from dpf_tpu.models import loaders
from dpf_tpu.models.datasets import LMDataset, RecDataset

FIX = os.path.join(os.path.dirname(__file__), "fixtures")


def _check_rec_contract(ds):
    assert isinstance(ds, RecDataset)
    n = ds.hist.shape[0]
    assert ds.hist.shape == (n, ds.max_hist)
    assert ds.hist_len.max() <= ds.max_hist
    assert 0 <= ds.target.min() and ds.target.max() < ds.n_items
    assert set(np.unique(ds.label)) <= {0.0, 1.0}
    assert len(ds.train_idx) + len(ds.val_idx) == n
    # access patterns: one list of table rows per example
    ap = ds.access_patterns("train")
    assert len(ap) == len(ds.train_idx)
    for row in ap:
        assert all(0 <= x < ds.n_items for x in row)


def test_taobao_loader():
    ds = loaders.load_taobao(os.path.join(FIX, "taobao"))
    _check_rec_contract(ds)
    # ad 999 has no feature row -> dropped, ids remapped densely
    assert ds.n_items <= 30
    # histories only contain clicked ads from strictly earlier timestamps
    for i in range(ds.hist.shape[0]):
        sl = ds.hist[i, :ds.hist_len[i]]
        assert (sl < ds.n_items).all()


def test_taobao_history_is_causal():
    """First interaction of each user must have an empty history."""
    ds = loaders.load_taobao(os.path.join(FIX, "taobao"))
    assert (ds.hist_len == 0).any()


def test_movielens_loader():
    ds = loaders.load_movielens(os.path.join(FIX, "ml-20m"))
    _check_rec_contract(ds)
    # click iff rating >= 4: fixture mixes both -> both labels present
    assert 0.0 in ds.label and 1.0 in ds.label


def test_wikitext_loader():
    ds = loaders.load_wikitext(os.path.join(FIX, "wikitext-2"), seq_len=8)
    assert isinstance(ds, LMDataset)
    assert ds.train_tokens.shape[1] == 9
    assert ds.val_tokens.shape[1] == 9
    assert ds.train_tokens.max() < ds.vocab_size
    assert ds.val_tokens.max() < ds.vocab_size
    ap = ds.access_patterns("val")
    assert len(ap) == ds.val_tokens.shape[0]


def test_wikitext_vocab_cap():
    ds = loaders.load_wikitext(os.path.join(FIX, "wikitext-2"), seq_len=8,
                               vocab_limit=5)
    assert ds.vocab_size == 5
    assert ds.train_tokens.max() < 5


def test_fallback_is_synthetic(monkeypatch, tmp_path):
    monkeypatch.setattr(loaders, "DATA_ROOT", str(tmp_path))
    ds = loaders.load_taobao_or_synthetic()
    _check_rec_contract(ds)
    lm = loaders.load_wikitext_or_synthetic()
    assert isinstance(lm, LMDataset)


def test_real_path_is_used_when_present(monkeypatch):
    monkeypatch.setattr(loaders, "DATA_ROOT", FIX)
    ds = loaders.load_movielens_or_synthetic()
    # fixture has < 40 movies; the synthetic fallback has 1500
    assert ds.n_items < 100


def test_loaded_dataset_feeds_batch_pir():
    """The loaded access patterns drive the batch-PIR optimizer end to
    end (the reference's actual consumption of these datasets)."""
    from dpf_tpu.apps.batch_pir import BatchPIROptimize
    ds = loaders.load_movielens(os.path.join(FIX, "ml-20m"))
    opt = BatchPIROptimize(ds.access_patterns("train"),
                           ds.access_patterns("val"))
    recovered, cost = opt.fetch(ds.access_patterns("val")[0])
    assert cost.computation >= 0
    assert isinstance(recovered, set)
