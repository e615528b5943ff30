"""Mesh-sharded evaluation tests on the virtual 8-device CPU mesh
(the TPU answer to "multi-node without a cluster", SURVEY.md §4)."""

import os

import numpy as np
import pytest

from dpf_tpu import DPF
from dpf_tpu.parallel import sharded


@pytest.fixture(scope="module")
def eight_devices():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return jax.devices()


def _setup(n, batch, prf, entry=7):
    dpf = DPF(prf=prf)
    table = np.random.randint(-2 ** 31, 2 ** 31, (n, entry),
                              dtype=np.int64).astype(np.int32)
    keys, idxs = [], []
    for i in range(batch):
        idx = (i * 997) % n
        idxs.append(idx)
        keys.append(dpf.gen(idx, n))
    return dpf, table, keys, idxs


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4), (8, 1)])
def test_sharded_matches_single_chip(eight_devices, mesh_shape):
    nb, nt = mesh_shape
    n, batch = 2048, 8
    dpf, table, keys, idxs = _setup(n, batch, DPF.PRF_SALSA20)
    mesh = sharded.make_mesh(n_table=nt, n_batch=nb)
    srv = sharded.ShardedDPFServer(table, mesh, prf_method=DPF.PRF_SALSA20,
                                   batch_size=batch)
    a = srv.eval([k[0] for k in keys])
    b = srv.eval([k[1] for k in keys])
    rec = (a - b).astype(np.int32)
    assert (rec == table[idxs]).all()

    # must agree bit-exactly with the single-chip path per server
    dpf.eval_init(table)
    single = np.asarray(dpf.eval_tpu([k[0] for k in keys]))
    assert (a == single).all()


def test_sharded_batch_not_multiple_of_mesh(eight_devices):
    n = 1024
    dpf, table, keys, idxs = _setup(n, 5, DPF.PRF_DUMMY)
    mesh = sharded.make_mesh(n_table=4, n_batch=2)
    srv = sharded.ShardedDPFServer(table, mesh, prf_method=DPF.PRF_DUMMY)
    rec = (srv.eval([k[0] for k in keys])
           - srv.eval([k[1] for k in keys])).astype(np.int32)
    assert rec.shape == (5, 7)
    assert (rec == table[idxs]).all()


def test_sharded_large_table_small_shards(eight_devices):
    """Each chip owns multiple frontier subtrees (scan path)."""
    n = 8192
    dpf, table, keys, idxs = _setup(n, 3, DPF.PRF_CHACHA20, entry=16)
    mesh = sharded.make_mesh(n_table=8, n_batch=1)
    srv = sharded.ShardedDPFServer(table, mesh,
                                   prf_method=DPF.PRF_CHACHA20)
    srv.chunk = 256  # force f_local = (8192/8)/256 = 4 subtrees per chip
    rec = (srv.eval([k[0] for k in keys])
           - srv.eval([k[1] for k in keys])).astype(np.int32)
    assert (rec == table[idxs]).all()


def test_mesh_validation():
    with pytest.raises(AssertionError):
        sharded.make_mesh(n_table=3, n_batch=2)  # 6 != 8 devices


def test_sharded_large_table_smoke(eight_devices):
    """Scaled-down rehearsal of the 2^26-rows-over-8-chips config
    (BASELINE config 4): a table big enough that each chip owns many
    frontier subtrees and the scan path streams dozens of tiles."""
    n = 1 << 16
    dpf = DPF(prf=DPF.PRF_DUMMY)
    table = np.random.randint(-2 ** 31, 2 ** 31, (n, 16),
                              dtype=np.int64).astype(np.int32)
    idxs = [0, 12345, n - 1]
    keys = [dpf.gen(i, n) for i in idxs]
    mesh = sharded.make_mesh(n_table=8, n_batch=1)
    srv = sharded.ShardedDPFServer(table, mesh, prf_method=DPF.PRF_DUMMY,
                                   batch_size=4)
    srv.chunk = 1024  # 8 subtrees per chip
    rec = (srv.eval([k[0] for k in keys])
           - srv.eval([k[1] for k in keys])).astype(np.int32)
    assert (rec == table[idxs]).all()


@pytest.mark.skipif(
    not os.environ.get("DPF_RUN_SLOW"),
    reason="~100 s of 1-core XLA-CPU work; the scan/shard legs are "
           "pinned by the smaller mesh tests above — this largest-N "
           "rehearsal runs in the DPF_RUN_SLOW lane")
def test_sharded_multi_million_rows_functional(eight_devices):
    """Largest-N functional run the CPU mesh comfortably allows
    2^21 rows x 16 cols (128 MiB) row-sharded over all
    8 devices with a real cipher (ChaCha20-12), exact recovery checked.
    Each device owns 2^18 rows — the per-chip shape of a 2^24-row
    8-chip TPU config."""
    n = 1 << 21
    dpf = DPF(prf=DPF.PRF_CHACHA20)
    rng = np.random.default_rng(0)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 16),
                         dtype=np.int64).astype(np.int32)
    idxs = [1, n // 2 + 17, n - 2]
    keys = [dpf.gen(i, n) for i in idxs]
    mesh = sharded.make_mesh(n_table=8, n_batch=1)
    srv = sharded.ShardedDPFServer(table, mesh,
                                   prf_method=DPF.PRF_CHACHA20,
                                   batch_size=4)
    rec = (srv.eval([k[0] for k in keys])
           - srv.eval([k[1] for k in keys])).astype(np.int32)
    assert (rec == table[idxs]).all()


def test_single_query_whole_mesh_latency_path(eight_devices):
    """The coop-kernel analogue (reference dpf_gpu/dpf/dpf_coop.cu):
    batch=1, every chip works on the one query via table sharding."""
    n = 4096
    dpf = DPF(prf=DPF.PRF_SALSA20)
    table = np.random.randint(0, 2 ** 31, (n, 8),
                              dtype=np.int64).astype(np.int32)
    k1, k2 = dpf.gen(2025, n)
    srv = sharded.ShardedDPFServer(table, sharded.make_mesh(n_table=8),
                                   prf_method=DPF.PRF_SALSA20, batch_size=1)
    rec = (srv.eval([k1]) - srv.eval([k2])).astype(np.int32)
    assert rec.shape == (1, 8)
    assert (rec[0] == table[2025]).all()


# --------------------------------------------------- mesh-shape parity fuzz

# (n_table, n_batch) — including the degenerate 1-device mesh and a
# 2-device subset mesh: the sharded program must agree with the
# single-device oracle bit for bit on EVERY split, not just full meshes
PARITY_SHAPES = [(1, 1), (2, 1), (4, 2), (8, 1)]


def _construction_dpf(label, prf):
    from dpf_tpu.utils.config import EvalConfig
    if label == "radix4":
        return DPF(config=EvalConfig(prf_method=prf, radix=4))
    return DPF(prf=prf, scheme="sqrtn" if label == "sqrtn" else "logn")


def _parity_case(label, nt, nb, n, batch, prf, entry=5, seed=0):
    """One fuzz cell: random table + random indices, sharded eval must
    be bit-identical to the single-device path per server AND recover
    the table rows across servers."""
    import jax
    rng = np.random.default_rng(seed ^ hash((label, nt, nb)) % (1 << 31))
    dpf = _construction_dpf(label, prf)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, entry),
                         dtype=np.int64).astype(np.int32)
    idxs = [int(x) for x in rng.integers(0, n, batch)]
    keys = [dpf.gen(i, n) for i in idxs]
    dpf.eval_init(table)
    single = np.asarray(dpf.eval_tpu([k[0] for k in keys]))
    mesh = sharded.make_mesh(n_table=nt, n_batch=nb,
                             devices=jax.devices()[:nt * nb])
    srv = sharded.ShardedDPFServer(
        table, mesh, prf_method=prf, batch_size=batch,
        radix=4 if label == "radix4" else 2,
        scheme="sqrtn" if label == "sqrtn" else "logn")
    a = srv.eval([k[0] for k in keys])
    b = srv.eval([k[1] for k in keys])
    assert (a == single).all(), \
        "%s mesh %dx%d diverged from the single-device oracle" \
        % (label, nb, nt)
    assert ((a - b).astype(np.int32) == table[idxs]).all()


@pytest.mark.parametrize("mesh_shape", PARITY_SHAPES)
@pytest.mark.parametrize("label", ["logn", "radix4", "sqrtn"])
def test_mesh_parity_fuzz(eight_devices, label, mesh_shape):
    nt, nb = mesh_shape
    _parity_case(label, nt, nb, n=1024, batch=5, prf=DPF.PRF_SALSA20)


@pytest.mark.skipif(
    not os.environ.get("DPF_RUN_SLOW"),
    reason="large-N parity fuzz: minutes of 1-core XLA-CPU work; the "
           "small-N cells above pin the same program legs")
@pytest.mark.parametrize("label", ["logn", "radix4", "sqrtn"])
def test_mesh_parity_fuzz_large(eight_devices, label):
    _parity_case(label, 4, 2, n=1 << 16, batch=8, prf=DPF.PRF_CHACHA20,
                 entry=16, seed=7)


def test_sharded_chunked_psum_matches_terminal(eight_devices):
    """psum_group variants are bit-identical to the terminal psum AND
    the single-device oracle for all three constructions — int32 adds
    wrap, so collective grouping must not change a single bit.  Every
    cell here genuinely runs the grouped-psum scan (steps > 1 and the
    group divides it; an invalid group silently degrades to the
    terminal psum, which would make the comparison vacuous)."""
    n, batch, prf = 2048, 4, DPF.PRF_DUMMY
    rng = np.random.default_rng(3)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 6),
                         dtype=np.int64).astype(np.int32)
    idxs = [1, 17, 1400, n - 1]
    for label in ("logn", "radix4", "sqrtn"):
        dpf = _construction_dpf(label, prf)
        keys = [dpf.gen(i, n)[0] for i in idxs]
        dpf.eval_init(table)
        oracle = np.asarray(dpf.eval_tpu(keys))
        kw = dict(prf_method=prf, batch_size=batch,
                  radix=4 if label == "radix4" else 2,
                  scheme="sqrtn" if label == "sqrtn" else "logn")
        if label == "sqrtn":
            # n=2048 -> K=64, R=32 -> 8 grid rows per shard with
            # n_table=4: rc=4 -> steps=2, so psum_group=1 psums per step
            mesh = sharded.make_mesh(n_table=4, n_batch=2)
            knobs = [dict(row_chunk=4, psum_group=0),
                     dict(row_chunk=4, psum_group=1)]
        else:
            # shard_rows=512, chunk 128 -> 4 chunks per shard
            mesh = sharded.make_mesh(n_table=4, n_batch=2)
            knobs = [dict(chunk_leaves=128, psum_group=0),
                     dict(chunk_leaves=128, psum_group=1),
                     dict(chunk_leaves=128, psum_group=2)]
        outs = [sharded.ShardedDPFServer(table, mesh, **kw, **k).eval(keys)
                for k in knobs]
        assert (outs[0] == oracle).all(), label  # multi-step scan itself
        for o in outs[1:]:
            assert (o == outs[0]).all(), label


def test_sharded_tuned_chunk_clamps_to_shard_rows(
        eight_devices, monkeypatch, tmp_path):
    """A tuned SINGLE-DEVICE chunk_leaves bigger than a shard's leaf
    range must clamp against shard_rows (the per-shard heuristic), not
    the full table; a mesh-tuned entry for this split wins over it; an
    explicit ctor value wins over both."""
    from dpf_tpu.tune.cache import TuningCache
    from dpf_tpu.tune.fingerprint import cache_key
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", path)
    n, batch, prf = 1024, 8, DPF.PRF_DUMMY
    shape = dict(n=n, entry_size=7, batch=batch, prf_method=prf,
                 scheme="logn", radix=2)
    c = TuningCache(path)
    c.store(cache_key("eval", **shape), {"knobs": {"chunk_leaves": 1024}})
    table = np.zeros((n, 7), np.int32)
    mesh = sharded.make_mesh(n_table=8, n_batch=1)
    srv = sharded.ShardedDPFServer(table, mesh, prf_method=prf,
                                   batch_size=batch)
    kn = srv.resolved_eval_knobs(batch)
    assert kn["chunk_leaves"] <= srv.shard_rows == 128
    assert srv.shard_rows % kn["chunk_leaves"] == 0

    # mesh-tuned (this device x mesh split) beats the single-device
    # entry (fresh server: the lookups memoize per batch on hot paths)
    c.store(cache_key("mesh", **shape, mesh="1x8"),
            {"knobs": {"chunk_leaves": 32, "psum_group": 2}})
    from dpf_tpu.tune.cache import default_cache
    default_cache(refresh=True)
    srv = sharded.ShardedDPFServer(table, mesh, prf_method=prf,
                                   batch_size=batch)
    kn = srv.resolved_eval_knobs(batch)
    assert kn["chunk_leaves"] == 32 and kn["psum_group"] == 2

    # explicit ctor pin beats the caches
    srv2 = sharded.ShardedDPFServer(table, mesh, prf_method=prf,
                                    batch_size=batch, chunk_leaves=64,
                                    psum_group=0)
    kn2 = srv2.resolved_eval_knobs(batch)
    assert kn2["chunk_leaves"] == 64 and kn2["psum_group"] == 0


def test_sharded_scheme_auto_resolves_from_cache(
        eight_devices, monkeypatch, tmp_path):
    """ShardedDPFServer(scheme='auto') resolves the construction the
    DPF way: scheme tuning cache first, conservative logn heuristic on
    a cold cache."""
    from dpf_tpu.tune.cache import TuningCache, default_cache
    from dpf_tpu.tune.search import scheme_cache_key
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", path)
    default_cache(refresh=True)
    n = 1024
    table = np.zeros((n, 16), np.int32)
    mesh = sharded.make_mesh(n_table=4, n_batch=2)
    srv = sharded.ShardedDPFServer(table, mesh, prf_method=0,
                                   scheme="auto")
    assert (srv.scheme, srv.scheme_resolved_from) == ("logn", "heuristic")

    c = TuningCache(path)
    c.store(scheme_cache_key(n=n, entry_size=16, batch=8, prf_method=0),
            {"knobs": {"scheme": "sqrtn", "radix": 2}})
    default_cache(refresh=True)
    srv = sharded.ShardedDPFServer(table, mesh, prf_method=0,
                                   batch_size=8, scheme="auto")
    assert (srv.scheme, srv.scheme_resolved_from) == ("sqrtn", "cache")
    with pytest.raises(ValueError):
        sharded.ShardedDPFServer(table, mesh, scheme="auto", radix=4)


def test_sharded_sqrt_split_validation(eight_devices):
    """Invalid sqrt-N shard splits fail fast with a clear error."""
    from dpf_tpu.core import sqrtn
    import jax
    n = 512  # default split: K=32, R=16 -> R does not divide 32 shards
    dpf = DPF(prf=DPF.PRF_DUMMY, scheme="sqrtn")
    k1, _ = dpf.gen(3, n)
    mesh = sharded.make_mesh(n_table=8, n_batch=1)
    pk = sqrtn.decode_sqrt_keys_batched([k1])
    # R=16 over 8 shards is fine; fake a narrower split via slicing R=4
    bad = sqrtn.PackedSqrtKeys(pk.seeds, pk.cw1[:, :4], pk.cw2[:, :4],
                               n=n)
    with pytest.raises(ValueError, match="divide over"):
        import numpy as _np
        tbl = jax.numpy.asarray(_np.zeros((n, 4), _np.int32))
        sqrtn.eval_sharded_sqrt(bad.seeds, bad.cw1, bad.cw2, tbl,
                                prf_method=DPF.PRF_DUMMY, mesh=mesh,
                                row_chunk=None)


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_sharded_radix4_matches_single_chip(eight_devices, mesh_shape):
    """Radix-4 construction over the mesh: recovery + bit-exact agreement
    with the single-chip radix-4 path per server."""
    from dpf_tpu.utils.config import EvalConfig
    nb, nt = mesh_shape
    n, batch = 2048, 8
    cfg = EvalConfig(prf_method=DPF.PRF_CHACHA20, radix=4)
    dpf = DPF(config=cfg)
    table = np.random.randint(-2 ** 31, 2 ** 31, (n, 7),
                              dtype=np.int64).astype(np.int32)
    keys, idxs = [], []
    for i in range(batch):
        idx = (i * 997) % n
        idxs.append(idx)
        keys.append(dpf.gen(idx, n))
    mesh = sharded.make_mesh(n_table=nt, n_batch=nb)
    srv = sharded.ShardedDPFServer(table, mesh,
                                   prf_method=DPF.PRF_CHACHA20,
                                   batch_size=batch, radix=4)
    a = srv.eval([k[0] for k in keys])
    b = srv.eval([k[1] for k in keys])
    rec = (a - b).astype(np.int32)
    assert (rec == table[idxs]).all()

    dpf.eval_init(table)
    single = np.asarray(dpf.eval_tpu([k[0] for k in keys]))
    assert (a == single).all()


# ------------------------------------- the subtree kernel under the mesh

def _four_chip_mesh():
    import jax
    return sharded.make_mesh(n_table=4, devices=jax.devices()[:4])


@pytest.mark.parametrize("chunk", [None, 64, 256])
def test_each_chip_holds_its_slice_of_the_whole_layout(eight_devices, chunk):
    """``place_table`` builds every chip's block from the host rows: the
    int32 BFS rows of ``permute_table``, or the subtree kernel's digit
    planes, each chip's the matching slice of ``subtree_digits`` of the
    whole permuted table (its last two axes swapped)."""
    from dpf_tpu.core import expand
    from dpf_tpu.ops.pallas_level import subtree_digits
    n, e = 1 << 12, 16
    table = np.random.default_rng(5).integers(
        -2 ** 31, 2 ** 31, (n, e), dtype=np.int64).astype(np.int32)
    perm = expand.permute_table(table)
    want = perm if chunk is None else np.swapaxes(np.asarray(subtree_digits(
        perm, n // chunk, (2,) * (chunk.bit_length() - 1))), 1, 2)
    placed = sharded.place_table(table, _four_chip_mesh(), chunk=chunk)
    assert placed.shape == want.shape
    for sh in placed.addressable_shards:
        assert (np.asarray(sh.data) == want[sh.index]).all()


def test_placement_and_eval_copy_nothing_between_chips(eight_devices,
                                                     monkeypatch):
    """Each chip's block goes from the host to that chip alone (no
    table-sized buffer staged on chip 0 for the others), and a call
    moves nothing from chip to chip but its psum."""
    import jax
    from dpf_tpu.utils import compat
    n = 1 << 12
    table = np.random.default_rng(6).integers(
        -2 ** 31, 2 ** 31, (n, 16), dtype=np.int64).astype(np.int32)
    dpf = DPF(prf=DPF.PRF_DUMMY)
    keys = [dpf.gen(i, n)[0] for i in (3, 1000, 4000)]
    dpf.eval_init(table)
    with jax.transfer_guard_device_to_device("disallow"):
        srv = sharded.ShardedDPFServer(table, _four_chip_mesh(),
                                       prf_method=DPF.PRF_DUMMY,
                                       batch_size=3)
        assert (srv.eval(keys) == np.asarray(dpf.eval_tpu(keys))).all()
        monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                            lambda backend=None: True)
        srv = sharded.ShardedDPFServer(table, _four_chip_mesh(),
                                       prf_method=DPF.PRF_CHACHA20,
                                       batch_size=8)
        assert srv.table_sharded.dtype == np.int8


# (case id) -> (server kwargs, probe reports a TPU, planted mesh-tuned
# entry, kernel, provenance): test_pallas_level's rule, on the mesh
_MESH_RULE_CASES = {
    "chacha.tpu": (dict(prf_method=2), True, None, "pallas", "heuristic"),
    "salsa.tpu": (dict(prf_method=1), True, None, "pallas", "heuristic"),
    "salsa_blk.tpu": (dict(prf_method=4), True, None, "pallas",
                      "heuristic"),
    "chacha_blk.tpu": (dict(prf_method=5), True, None, "pallas",
                       "heuristic"),
    "aes.tpu": (dict(prf_method=3), True, None, "xla", "heuristic"),
    "dummy.tpu": (dict(prf_method=0), True, None, "xla", "heuristic"),
    "radix4.tpu": (dict(prf_method=2, radix=4), True, None, "xla",
                   "heuristic"),
    "config_xla.tpu": (dict(prf_method=2, kernel_impl="xla"), True, None,
                       "xla", "config"),
    "tuned_xla.tpu": (dict(prf_method=2), True,
                      {"kernel_impl": "xla", "chunk_leaves": 256},
                      "xla", "tuned"),
    # a tuned chunk naming no kernel was timed on the scan: it never
    # rides the subtree kernel
    "tuned_chunk_only.tpu": (dict(prf_method=2), True,
                             {"chunk_leaves": 256}, "pallas", "heuristic"),
    "tuned_pallas.cpu": (dict(prf_method=2), False,
                         {"kernel_impl": "pallas"}, "xla", "degraded"),
    "chacha.cpu": (dict(prf_method=2), False, None, "xla", "heuristic"),
    "salsa.cpu": (dict(prf_method=1), False, None, "xla", "heuristic"),
    "aes.cpu": (dict(prf_method=3), False, None, "xla", "heuristic"),
}


@pytest.mark.parametrize("case", sorted(_MESH_RULE_CASES))
def test_mesh_kernel_rule(eight_devices, monkeypatch, case):
    """``ShardedDPFServer`` resolves the kernel by the one-chip rule
    (explicit > mesh-tuned > tuned > ``heuristic_kernel``): binary
    Salsa/ChaCha GGM on the subtree kernel where it compiles, with its
    chunk clamped to the shard; everything else on the xla scan."""
    from dpf_tpu.ops.pallas_level import pallas_chunk_leaves
    from dpf_tpu.utils import compat
    kwargs, on_tpu, tuned, kernel, prov = _MESH_RULE_CASES[case]
    if on_tpu:
        monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                            lambda backend=None: True)
    n, batch = 1 << 12, 8
    srv = sharded.ShardedDPFServer(np.zeros((n, 16), np.int32),
                                   _four_chip_mesh(), batch_size=batch,
                                   **kwargs)
    if tuned is not None:
        srv._tuned_memo[batch] = (dict(tuned), {})
    kn = srv.resolved_eval_knobs(batch)
    assert (kn["kernel_impl"], kn["kernel_resolved_from"]) == (kernel, prov)
    if kernel == "pallas":
        assert kn["chunk_leaves"] == pallas_chunk_leaves(srv.shard_rows)
    elif tuned is not None and prov == "tuned":
        assert kn["chunk_leaves"] == tuned["chunk_leaves"]
