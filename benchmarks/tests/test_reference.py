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


@pytest.mark.parametrize("path", [
    "reference.py", "reference_devices.py"] + sorted(
    os.path.join("prfs", p) for p in os.listdir(
        os.path.join(ROOT, "benchmarks", "prfs")) if p.endswith(".py")))
def test_reference_imports_nothing_of_the_program(path):
    """The reference's files import the standard library, NumPy, JAX
    (on the devices) and one another, nothing else."""
    tree = ast.parse(open(os.path.join(ROOT, "benchmarks", path)).read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.update((node.module or "") + "." + a.name
                         for a in node.names)
    allowed = {"__future__", "functools", "importlib", "numpy", "os",
               "concurrent", "benchmarks.reference"}
    if path == "reference_devices.py":
        allowed.add("jax")
    tops = {n if n.startswith("benchmarks.") else n.split(".")[0]
            for n in names}
    assert tops <= allowed, tops - allowed


def test_a_new_prf_takes_a_new_file_only(tmp_path, monkeypatch):
    """The reference finds a PRF by the configuration's name, in a file
    of its own: here a copy of ChaCha under another name."""
    src = os.path.join(ROOT, "benchmarks", "prfs", "chacha20.py")
    (tmp_path / "chacha20copy.py").write_text(open(src).read())
    monkeypatch.setattr(R, "PRF_DIR", str(tmp_path))
    seeds = np.arange(8, dtype=np.uint32).reshape(2, 4)
    got = R.prf_pair("chacha20copy", seeds)
    assert R.runs_under("chacha20copy", "jax.numpy")
    from benchmarks.prfs import chacha20
    for a, b in zip(got, chacha20.pair(seeds)):
        assert np.array_equal(a, b)
    with pytest.raises(ValueError):
        R.prf_pair("no-such-prf", seeds)
    assert not R.runs_under("aes128", "jax.numpy")


def test_wire_key_checks():
    with pytest.raises(ValueError):
        R.parse_keys(np.zeros((2, 10), np.int32))
    bad = np.zeros((1, R.KEY_WORDS), np.int32)
    bad[0, 0] = 4                       # depth 4 ...
    bad[0, 130 * 4] = 8                 # ... but n = 8
    with pytest.raises(ValueError):
        R.parse_keys(bad)


# ----------------------------------------------- the walk before blocks
# The unblocked walk and contraction as the reference ran them before
# it ran block by block (carries through uint64), kept as the oracle
# that the blocked walk has to equal.

def _add128_uint64(a, b):
    out = np.empty(np.broadcast_shapes(a.shape, b.shape), np.uint32)
    carry = np.zeros(out.shape[:-1], np.uint64)
    for j in range(4):
        s = a[..., j].astype(np.uint64) + b[..., j] + carry
        out[..., j] = s.astype(np.uint32)
        carry = s >> np.uint64(32)
    return out


def _unblocked_share(keys, table, prf, contraction):
    k = R.parse_keys(keys)
    s_keys = k["seed"].shape[0]
    seeds = k["seed"][:, None, :]
    for step in range(k["depth"]):
        i = k["depth"] - 1 - step
        width = seeds.shape[1]
        flat = seeds.reshape(-1, 4)
        odd = (flat[:, 0] & 1).astype(bool).reshape(s_keys, width, 1)
        kids = []
        for b, out in enumerate(R.prf_pair(prf, flat)):
            cw = np.where(odd, k["cw"][1][:, None, 2 * i + b],
                          k["cw"][0][:, None, 2 * i + b])
            kids.append(_add128_uint64(out.reshape(s_keys, width, 4), cw))
        seeds = np.concatenate(kids, axis=1)
    low = np.ascontiguousarray(seeds[..., 0])
    if contraction == "exact":
        prod = low @ np.ascontiguousarray(table).view(np.uint32)
        return prod.astype(np.uint32).view(np.int32)
    f = low.view(np.int32).astype(np.float32) @ table.astype(np.float32)
    wrapped = np.fmod(f.astype(np.float64), 2.0 ** 32).astype(np.int64)
    return (wrapped & 0xFFFFFFFF).astype(np.uint32).view(np.int32)


def _keys_and_table(prf, logn, count, seed):
    from dpf_tpu import DPF
    n = 1 << logn
    rng = np.random.default_rng(seed)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 16),
                         dtype=np.int64).astype(np.int32)
    rows = rng.integers(0, n, count)
    k0, k1 = DPF(prf=PROGRAM_PRF[prf]).gen_batch(
        rows, n, seeds=[rng.bytes(16) for _ in rows])
    return np.asarray(k0), np.asarray(k1), rows, table


def test_add128_carries_without_uint64():
    rng = np.random.default_rng(9)
    a = rng.integers(0, 2 ** 32, (64, 4), dtype=np.uint64).astype(np.uint32)
    b = rng.integers(0, 2 ** 32, (64, 4), dtype=np.uint64).astype(np.uint32)
    a[:8] = 0xFFFFFFFF                      # carries through every limb
    b[:8, 0] = 1
    b[:8, 1:] = 0
    b[8:16] = 0xFFFFFFFF
    assert np.array_equal(R.add128(a, b), _add128_uint64(a, b))
    assert (R.add128(a[:8], b[:8]) == 0).all()


@pytest.mark.parametrize("contraction", ["exact", "float32"])
@pytest.mark.parametrize("prf", sorted(PROGRAM_PRF))
@pytest.mark.parametrize("logn", [10, 12, 14])
def test_blocked_walk_equals_the_unblocked_one(logn, prf, contraction):
    """In one block (the existing cells' sizes) the blocked walk is the
    unblocked one bit for bit in both contractions.  Over many blocks the
    exact share still is; the float32 control, wrapped block by block,
    still fails every key."""
    k0, _, _, table = _keys_and_table(prf, logn, 3, logn)
    want = _unblocked_share(k0, table, prf, contraction)
    assert R.split(logn, 3) == 0
    assert np.array_equal(R.share(k0, table, prf, contraction), want)
    for block_seeds in (3 * 2 ** (logn - 3), 3 * 2 ** (logn // 2), 3):
        assert R.split(logn, 3, block_seeds) > 0
        got = R.share(k0, table, prf, contraction, block_seeds)
        if contraction == "exact":
            assert np.array_equal(got, want)
        else:
            exact = _unblocked_share(k0, table, prf, "exact")
            assert (got != exact).any(axis=1).all()


def test_frontier_node_roots_its_strided_rows():
    """Node ``r`` after ``t`` steps roots the leaves of rows r::2^t."""
    k0, _, _, _ = _keys_and_table("chacha20", 10, 2, 1)
    k = R.parse_keys(k0)
    leaves = R.leaves_low32(k0, "chacha20")
    front = R.frontier(k, 4, "chacha20")
    assert front.shape == (2, 16, 4)
    for r in (0, 5, 15):
        sub = R.walk(front[:, r:r + 1], k["cw"], 6, 0, "chacha20")
        assert np.array_equal(sub[..., 0], leaves[:, r::16])


def test_block_split():
    assert R.split(16, 8) == 0
    assert R.split(28, 32) == 11
    assert R.split(28, 8) == 9
    assert R.split(28, 1) == 6
    assert R.split(10, 3, 3) == 10
    with pytest.raises(ValueError):
        R.contract(np.zeros((1, 1), np.uint32), np.zeros((1, 1), np.int32),
                   "bfloat16")
