"""Bitsliced AES tests: transpose involution, scalar-reference exactness on
both backends, end-to-end DPF evaluation through the bitsliced path."""

import numpy as np
import pytest

from dpf_tpu.core import aes_bitsliced, prf, prf_ref, u128


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 2 ** 32, 96, dtype=np.uint32)
    back = aes_bitsliced.unpack_planes(aes_bitsliced.pack_planes(vals))
    assert (back == vals).all()


def test_sbox_circuit_vs_table():
    """The derived GF(2^8) inversion circuit must equal the table S-box on
    all 256 inputs."""
    vals = np.arange(256, dtype=np.uint32).repeat(4)[:1024]  # M=1024
    bits = [((vals >> b) & 1).astype(np.uint32) * np.uint32(0xFFFFFFFF)
            for b in range(8)]
    # use unpacked planes (each element replicated over a whole word)
    ones = np.uint32(0xFFFFFFFF) + np.zeros_like(vals)
    out_bits = aes_bitsliced._sbox_bits(bits, ones)
    got = np.zeros_like(vals)
    for b in range(8):
        got |= (out_bits[b] & 1) << b
    want = np.array([prf_ref.SBOX[v] for v in vals], dtype=np.uint32)
    assert (got == want).all()


@pytest.fixture(scope="module")
def seed_ints():
    rng = np.random.default_rng(3)
    return ([int.from_bytes(rng.bytes(16), "little") for _ in range(50)]
            + [0, 1, (1 << 128) - 1])


def test_numpy_backend_exact(seed_ints):
    seeds = u128.ints_to_limbs(seed_ints)
    out0, out1 = aes_bitsliced.aes128_pair_bitsliced(seeds)
    assert u128.limbs_to_ints(out0) == \
        [prf_ref.prf_aes128(s, 0) for s in seed_ints]
    assert u128.limbs_to_ints(out1) == \
        [prf_ref.prf_aes128(s, 1) for s in seed_ints]


def test_jax_backend_exact(seed_ints):
    import jax
    import jax.numpy as jnp
    seeds = jnp.asarray(u128.ints_to_limbs(seed_ints[:33]))
    out0, out1 = jax.jit(aes_bitsliced.aes128_pair_bitsliced)(seeds)
    assert u128.limbs_to_ints(np.asarray(out0)) == \
        [prf_ref.prf_aes128(s, 0) for s in seed_ints[:33]]
    assert u128.limbs_to_ints(np.asarray(out1)) == \
        [prf_ref.prf_aes128(s, 1) for s in seed_ints[:33]]


def test_non_multiple_of_32_and_leading_dims(seed_ints):
    import jax.numpy as jnp
    seeds = jnp.asarray(u128.ints_to_limbs(seed_ints[:10])).reshape(2, 5, 4)
    out0, _ = aes_bitsliced.aes128_pair_bitsliced(seeds)
    assert out0.shape == (2, 5, 4)
    flat = np.asarray(out0).reshape(-1, 4)
    assert u128.limbs_to_ints(flat) == \
        [prf_ref.prf_aes128(s, 0) for s in seed_ints[:10]]


def test_end_to_end_dpf_with_bitsliced_aes():
    """Full share recovery through eval_tpu with the bitsliced AES forced."""
    from dpf_tpu import DPF
    old = prf.AES_PAIR_IMPL
    prf.AES_PAIR_IMPL = "bitsliced"
    try:
        n = 512
        dpf = DPF(prf=DPF.PRF_AES128)
        table = np.random.randint(-2 ** 31, 2 ** 31, (n, 5),
                                  dtype=np.int64).astype(np.int32)
        dpf.eval_init(table)
        idxs = [3, 77, 500]
        ks = [dpf.gen(i, n) for i in idxs]
        a = np.asarray(dpf.eval_tpu([k[0] for k in ks]))
        b = np.asarray(dpf.eval_tpu([k[1] for k in ks]))
        assert ((a - b).astype(np.int32) == table[idxs]).all()
        # and it must agree with the gather path bit-for-bit per server
        prf.AES_PAIR_IMPL = "gather"
        a2 = np.asarray(dpf.eval_tpu([k[0] for k in ks]))
        assert (a == a2).all()
    finally:
        prf.AES_PAIR_IMPL = old


@pytest.mark.parametrize("w0,levels,batch", [(1, 6, 8), (4, 4, 3)])
def test_tiled_levels_match_per_level_steps(monkeypatch, w0, levels, batch):
    """Bitsliced AES runs its levels through one tiled level program
    (``expand._expand_tiled``); with a tile of a few nodes, levels
    narrower than a tile (padded) and wider (several tiles, last first)
    both occur, and the seeds equal the per-level gather-AES chain."""
    import jax.numpy as jnp

    from dpf_tpu.core import expand
    monkeypatch.setattr(expand, "TILE_SEEDS", 8 * batch)
    rng = np.random.default_rng(w0)
    seeds = rng.integers(0, 2 ** 32, (batch, w0, 4), dtype=np.uint32)
    cw1, cw2 = (rng.integers(0, 2 ** 32, (batch, 64, 4), dtype=np.uint32)
                for _ in range(2))
    top = 20
    want = jnp.asarray(seeds)
    for i in range(top, top - levels, -1):
        want = expand._level_step(want, cw1, cw2, i, prf.PRF_AES128,
                                  "gather")
    got = expand.expand_levels(jnp.asarray(seeds), jnp.asarray(cw1),
                               jnp.asarray(cw2), top, levels,
                               prf.PRF_AES128, "bitsliced")
    assert expand._tile(batch, w0 << levels) == 8
    assert np.array_equal(np.asarray(got), np.asarray(want))
