"""Block-PRG ("wide") stream-cipher PRFs: ids 4 (SALSA20_BLK) and
5 (CHACHA20_BLK).

One 512-bit Salsa/ChaCha core block serves four GGM children (child
``pos`` = word group ``pos % 4`` of the block at counter ``pos // 4`` —
``core/prf_ref.py::prf_salsa20_12_blk``), where the reference's kernels
keep 128 of the 512 bits per call (``dpf_gpu/prf/prf.cu:46-96``): a
radix-4 level costs ONE core call per node, 6x fewer core calls per
leaf than the reference's binary scheme.  These tests pin:

* scalar ground truth structure (block-word consistency, distinct
  children, 12-round core equality with the classic PRFs);
* vectorized (NumPy + jitted JAX) vs scalar, static and traced pos;
* the fused ``prf_multi`` (one core call) vs per-pos evaluation;
* exhaustive small-N DPF exactness for both servers, binary + radix-4;
* full PIR round trips through the DPF API on the xla and dispatch
  engines, and the Pallas subtree kernel (TPU-semantics interpreter);
* native C++ keygen/expansion parity.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dpf_tpu
from dpf_tpu.core import expand, keygen, prf, prf_ref, radix4, u128
from dpf_tpu.utils.config import EvalConfig

BLK = (prf_ref.PRF_SALSA20_BLK, prf_ref.PRF_CHACHA20_BLK)


def _seeds(n=16, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2 ** 32, (n, 4), dtype=np.uint32)


def test_blk_scalar_structure():
    s = 0x0123456789ABCDEF0011223344556677
    # child b of counter 0 = word group b of one block; the ChaCha
    # classic PRF at pos 0 is exactly group 1 (same state: ctr words 0)
    assert (prf_ref.prf_chacha20_12_blk(s, 1)
            == prf_ref.prf_chacha20_12(s, 0))
    # all four children of a counter are pairwise distinct
    for m in BLK:
        kids = [prf_ref.prf(m, s, b) for b in range(4)]
        assert len(set(kids)) == 4
        # counter 1 children differ from counter 0 children
        kids1 = [prf_ref.prf(m, s, 4 + b) for b in range(4)]
        assert not set(kids) & set(kids1)


def test_blk_vectorized_matches_scalar():
    seeds = _seeds()
    ints = u128.limbs_to_ints(seeds)
    for m in BLK:
        for pos in (0, 1, 2, 3, 6, 11):
            want = [prf_ref.prf(m, s, pos) for s in ints]
            got = list(u128.limbs_to_ints(prf.prf_v(m, seeds, pos)))
            assert got == want, (m, pos)
            gotj = list(u128.limbs_to_ints(np.asarray(
                jax.jit(lambda s, m=m, p=pos: prf.prf_v(m, s, p))(seeds))))
            assert gotj == want, (m, pos, "jax")


def test_blk_traced_pos():
    """sqrt-N-style traced position arrays: dynamic group select."""
    seeds = _seeds()
    ints = u128.limbs_to_ints(seeds)
    posv = np.arange(16, dtype=np.uint32)
    for m in BLK:
        want = [prf_ref.prf(m, s, int(p)) for s, p in zip(ints, posv)]
        got = list(u128.limbs_to_ints(prf.prf_v(m, seeds, posv)))
        assert got == want, m
        gotj = list(u128.limbs_to_ints(np.asarray(
            jax.jit(lambda s, p, m=m: prf.prf_v(m, s, p))(seeds, posv))))
        assert gotj == want, (m, "jax")


def test_blk_multi_is_one_block():
    """prf_multi == per-pos results AND costs one core call: all four
    children must come from the same block (checked by value against the
    scalar block)."""
    seeds = _seeds(8)
    ints = u128.limbs_to_ints(seeds)
    for m in BLK:
        for arity in (2, 4):
            outs = prf.prf_multi(m, seeds, arity)
            assert len(outs) == arity
            for b in range(arity):
                want = [prf_ref.prf(m, s, b) for s in ints]
                assert list(u128.limbs_to_ints(outs[b])) == want, (m, b)
            outs_j = jax.jit(
                lambda s, m=m, a=arity: prf.prf_multi(m, s, a))(seeds)
            for b in range(arity):
                want = [prf_ref.prf(m, s, b) for s in ints]
                assert list(u128.limbs_to_ints(
                    np.asarray(outs_j[b]))) == want, (m, b, "jax")


def test_blk_exhaustive_small_n_binary():
    n = 64
    for m in BLK:
        for alpha in (0, 1, 31, 63):
            k0, k1 = keygen.generate_keys(alpha, n, b"blk", m)
            from dpf_tpu.core import evalref
            h = (evalref.eval_one_hot_i32(k0, m).astype(np.int64)
                 - evalref.eval_one_hot_i32(k1, m).astype(np.int64))
            want = np.zeros(n, np.int64)
            want[alpha] = 1
            assert (h == want).all(), (m, alpha)


def test_blk_exhaustive_small_n_radix4():
    n = 64
    for m in BLK:
        for alpha in (0, 5, 42, 63):
            k0, k1 = radix4.generate_keys_r4(alpha, n, b"blkr4", m)
            cw1, cw2, last = radix4.pack_mixed_keys([k0, k1])
            hots = np.asarray(radix4.expand_leaves_mixed(
                cw1, cw2, last, n=n, prf_method=m))
            h = hots[0].astype(np.int64) - hots[1].astype(np.int64)
            want = np.zeros(n, np.int64)
            want[alpha] = 1
            assert (h == want).all(), (m, alpha)


def _round_trip(cfg, n=256, alpha=42):
    rng = np.random.default_rng(11)
    table = rng.integers(0, 2 ** 31, (n, 16)).astype(np.int32)
    d = dpf_tpu.DPF(config=cfg)
    d.eval_init(table)
    k1, k2 = d.gen(alpha, n)
    rec = (np.asarray(d.eval_tpu([k1, k1]))
           - np.asarray(d.eval_tpu([k2, k2])))
    assert (np.int32(rec) == table[alpha]).all()
    recc = np.asarray(d.eval_cpu([k1])) - np.asarray(d.eval_cpu([k2]))
    assert (np.int32(recc[0]) == table[alpha]).all()


def test_blk_api_round_trip_engines():
    """One point per (prf, engine-family) diagonal — the full matrix is
    covered cheaply by the exhaustive/evalref tests above; each api
    round trip costs several XLA-CPU compiles on this 1-core host."""
    cc, ss = BLK[1], BLK[0]
    _round_trip(EvalConfig(prf_method=cc, radix=4, kernel_impl="xla",
                           batch_size=4))
    _round_trip(EvalConfig(prf_method=cc, radix=2, kernel_impl="dispatch",
                           batch_size=4))
    _round_trip(EvalConfig(prf_method=ss, radix=4, kernel_impl="dispatch",
                           batch_size=4))
    _round_trip(EvalConfig(prf_method=ss, radix=2, kernel_impl="xla",
                           batch_size=4))


def test_blk_pallas_subtree_interpret():
    """Fused Pallas subtree kernel with the block core (one core call
    per node per level) vs the XLA path — TPU-semantics interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    from dpf_tpu.ops import pallas_level
    n, chunk = 128, 64
    depth = n.bit_length() - 1
    for m in BLK:
        flat = [keygen.generate_keys((i * 37) % n, n, b"pblk%d" % i, m)[0]
                for i in range(2)]
        cw1, cw2, last = expand.pack_keys(flat)
        rng = np.random.default_rng(5)
        table = rng.integers(-2 ** 31, 2 ** 31, (n, 16), dtype=np.int32)
        tperm = jnp.asarray(expand.permute_table(table))
        want = expand.expand_and_contract(
            cw1, cw2, last, tperm, depth=depth, prf_method=m,
            chunk_leaves=chunk)
        f_levels = int(np.log2(n // chunk))
        seeds = jnp.asarray(last)[:, None, :]
        for l in range(f_levels):
            seeds = expand._level_step(seeds, jnp.asarray(cw1),
                                       jnp.asarray(cw2), depth - 1 - l, m)
        with pltpu.force_tpu_interpret_mode():
            got = pallas_level.subtree_contract_pallas(
                seeds, jnp.asarray(cw1), jnp.asarray(cw2), tperm,
                depth=depth, f_levels=f_levels, prf_method=m)
        assert (np.asarray(got) == np.asarray(want)).all(), m


def test_blk_sqrtn_grid():
    """Sqrt-N scheme with block-PRG ids: the 4-rows-per-block grid fast
    path (one core per FOUR codeword rows) recovers the exact point
    function, on both the numpy grid and the batched device contraction."""
    from dpf_tpu.core import sqrtn
    n = 256
    rng = np.random.default_rng(8)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 8), dtype=np.int32)
    for m in BLK:
        k0, k1 = sqrtn.generate_sqrt_keys(42, n, b"sqblk", m)
        h = (np.asarray(sqrtn.eval_grid(k0, m)).astype(np.int64)
             - np.asarray(sqrtn.eval_grid(k1, m)).astype(np.int64))
        want = np.zeros(n, np.int64)
        want[42] = 1
        assert (h == want).all(), m
        s0, c1, c2 = sqrtn.pack_sqrt_keys([k0])
        s1, _, _ = sqrtn.pack_sqrt_keys([k1])
        a = np.asarray(sqrtn.eval_contract_batched(
            s0, c1, c2, jnp.asarray(table), prf_method=m, dot_impl="i32"))
        b = np.asarray(sqrtn.eval_contract_batched(
            s1, c1, c2, jnp.asarray(table), prf_method=m, dot_impl="i32"))
        assert ((a - b).astype(np.int32)[0] == table[42]).all(), m


def test_blk_grid_vals_row_tail():
    """_grid_vals with a row count NOT a multiple of 4: the last block's
    unused groups are sliced away and every produced row still matches
    the scalar pos semantics."""
    from dpf_tpu.core.sqrtn import _grid_vals
    keys = _seeds(4, seed=9)
    ints = u128.limbs_to_ints(keys)
    for m in BLK:
        for r in (2, 5, 7):
            vals = _grid_vals(
                m, lambda nr: np.broadcast_to(keys[None, :, :],
                                              (nr, 4, 4)), r, np)
            assert vals.shape == (r, 4, 4)
            for row in range(r):
                got = list(u128.limbs_to_ints(vals[row]))
                want = [prf_ref.prf(m, s, row) for s in ints]
                assert got == want, (m, r, row)


def test_blk_native_parity():
    from dpf_tpu import native
    if native.load() is None:  # pragma: no cover - compiler always present
        import pytest
        pytest.skip("native toolchain unavailable")
    seed = bytes(range(128))
    for m in BLK:
        nk = native.gen(42, 256, seed, m)
        k0, k1 = keygen.generate_keys(42, 256, seed, m)
        assert (nk[0] == k0.serialize()).all()
        assert (nk[1] == k1.serialize()).all()
        hot = (native.eval_expand(nk[0].astype(np.int32), m)
               - native.eval_expand(nk[1].astype(np.int32), m))
        want = np.zeros(256, np.int32)
        want[42] = 1
        assert (hot.astype(np.int32) == want).all(), m
