"""The plain reference: known answers, agreement with keys the program
mints, and independence from the program."""

import ast
import os

import numpy as np
import pytest

from benchmarks import reference as R
from benchmarks.prfs import aes128
from benchmarks.tests.conftest import ROOT

# the program's number for each PRF of the reference
PROGRAM_PRF = {"aes128": 3, "chacha20": 2}


def test_aes128_fips197_known_answer():
    # FIPS-197 appendix C.1
    key = np.frombuffer(bytes(range(16)), np.uint8)[None]
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"),
                       np.uint8)[None]
    (ct,) = aes128.encrypt(key, [pt])
    assert ct.tobytes().hex() == "69c4e0d86a7b0430d8cdb78070b4c55a"


def test_aes128_pair_is_aes_of_the_position():
    rng = np.random.default_rng(3)
    seeds = rng.integers(0, 2 ** 32, (8, 4), dtype=np.uint64).astype(
        np.uint32)
    keys = seeds.view(np.uint8).reshape(8, 16)
    for pos, got in enumerate(aes128.pair(seeds)):
        pt = np.zeros((8, 16), np.uint8)
        pt[:, 0] = pos
        (want,) = aes128.encrypt(keys, [pt])
        assert np.array_equal(got.view(np.uint8).reshape(8, 16), want)


@pytest.mark.parametrize("prf", sorted(PROGRAM_PRF))
def test_program_keys_recover_rows_under_the_reference(prf):
    """Keys minted by the program's keygen, evaluated by the reference
    alone, recover the table rows exactly."""
    from dpf_tpu import DPF
    n = 1 << 9
    rng = np.random.default_rng(5)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 16),
                         dtype=np.int64).astype(np.int32)
    rows = rng.integers(0, n, 6)
    k0, k1 = DPF(prf=PROGRAM_PRF[prf]).gen_batch(
        rows, n, seeds=[rng.bytes(16) for _ in rows])
    s0 = R.share(np.asarray(k0), table, prf)
    s1 = R.share(np.asarray(k1), table, prf)
    assert np.array_equal((s0.astype(np.int64) - s1).astype(np.int32),
                          table[rows])
    ctrl = R.share(np.asarray(k0), table, prf, contraction="float32")
    assert (ctrl != s0).any(axis=1).all()


@pytest.mark.parametrize("prf", sorted(PROGRAM_PRF))
def test_reference_matches_program_leaves(prf):
    from dpf_tpu import DPF
    n = 1 << 8
    d = DPF(prf=PROGRAM_PRF[prf])
    k0, _ = d.gen_batch([3, 200], n, seeds=[b"a" * 16, b"b" * 16])
    hots = np.asarray(d.eval_cpu(list(k0), one_hot_only=True))
    assert np.array_equal(hots.view(np.uint32),
                          R.leaves_low32(np.asarray(k0), prf))


@pytest.mark.parametrize("path", ["reference.py"] + sorted(
    os.path.join("prfs", p) for p in os.listdir(
        os.path.join(ROOT, "benchmarks", "prfs")) if p.endswith(".py")))
def test_reference_imports_nothing_of_the_program(path):
    tree = ast.parse(open(os.path.join(ROOT, "benchmarks", path)).read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "importlib", "numpy", "os"}


def test_a_new_prf_takes_a_new_file_only(tmp_path, monkeypatch):
    """The reference finds a PRF by the configuration's name, in a file
    of its own: here a copy of ChaCha under another name."""
    src = os.path.join(ROOT, "benchmarks", "prfs", "chacha20.py")
    (tmp_path / "chacha20copy.py").write_text(open(src).read())
    monkeypatch.setattr(R, "PRF_DIR", str(tmp_path))
    seeds = np.arange(8, dtype=np.uint32).reshape(2, 4)
    got = R.prf_pair("chacha20copy", seeds)
    from benchmarks.prfs import chacha20
    for a, b in zip(got, chacha20.pair(seeds)):
        assert np.array_equal(a, b)
    with pytest.raises(ValueError):
        R.prf_pair("no-such-prf", seeds)


def test_wire_key_checks():
    with pytest.raises(ValueError):
        R.parse_keys(np.zeros((2, 10), np.int32))
    bad = np.zeros((1, R.KEY_WORDS), np.int32)
    bad[0, 0] = 4                       # depth 4 ...
    bad[0, 130 * 4] = 8                 # ... but n = 8
    with pytest.raises(ValueError):
        R.parse_keys(bad)
