"""Vectorized PRFs over [..., 4]-uint32 limb arrays — the TPU hot path.

Each function maps a batch of 128-bit seeds (trailing axis = 4 little-endian
uint32 limbs) and a position ``pos`` — a static small int (0 or 1 in the
GGM walk) or a traced uint32 array broadcastable against the batch (the
sqrt-N grid eval) — to a batch of 128-bit PRF outputs, matching the scalar
semantics in ``prf_ref.py`` bit-for-bit.

The implementations are backend generic (NumPy for the host reference path,
jax.numpy inside jit for TPU): Salsa/ChaCha are pure 32-bit add/xor/rotate
chains that XLA fuses into long VPU pipelines; AES-128 uses byte-plane
S-box gathers with the key schedule fused per round (and shared between the
two GGM child positions via ``prf_pair``).

Reference semantics: ``dpf_base/dpf.h:65-235`` and ``dpf_gpu/prf/prf.cu``.
"""

from __future__ import annotations

import numpy as np

from . import u128
from .prf_ref import (PRF_AES128, PRF_CHACHA20, PRF_CHACHA20_BLK,
                      PRF_DUMMY, PRF_SALSA20, PRF_SALSA20_BLK, SBOX)

_SIGMA = (0x65787061, 0x6E642033, 0x322D6279, 0x7465206B)


def _rotl(x, b: int):
    return (x << np.uint32(b)) | (x >> np.uint32(32 - b))


# ---------------------------------------------------------------------------
# DUMMY
# ---------------------------------------------------------------------------

def _pos_word(zero, pos, word: int):
    """32-bit word `word` of the 128-bit position, broadcast like `zero`.

    `pos` is either a static Python int (the GGM branch/pos constants) or
    a traced uint32 array of row indices (< 2^32 — the sqrt-N grid eval),
    in which case only word 0 is nonzero.
    """
    if isinstance(pos, (int, np.integer)):
        return zero + np.uint32((int(pos) >> (32 * word)) & 0xFFFFFFFF)
    return zero + pos if word == 0 else zero


def prf_dummy_v(seeds, pos):
    """seed * (pos+4242) + (pos+4242) mod 2^128, vectorized."""
    zero = seeds - seeds
    if isinstance(pos, (int, np.integer)):
        t = int(pos) + 4242
        tb = zero + np.array(u128.int_to_limbs(t))
        return u128.add128(u128.mul128_small(seeds, t), tb)
    t32 = pos + np.uint32(4242)  # row indices < 2^32 - 4242
    tb = u128._stack_last([zero[..., 0] + t32] + [zero[..., i]
                                                 for i in range(1, 4)])
    return u128.add128(u128.mul128_small(seeds, t32), tb)


# ---------------------------------------------------------------------------
# Salsa20/12 & ChaCha20/12
# ---------------------------------------------------------------------------

def _salsa_qr(x, a, b, c, d):
    x[b] = x[b] ^ _rotl(x[a] + x[d], 7)
    x[c] = x[c] ^ _rotl(x[b] + x[a], 9)
    x[d] = x[d] ^ _rotl(x[c] + x[b], 13)
    x[a] = x[a] ^ _rotl(x[d] + x[c], 18)


def _salsa20_12_words_v(seeds, ctr):
    """Full 16-word Salsa20/12 block (elementwise path)."""
    zero = seeds[..., 0] - seeds[..., 0]
    x = [zero] * 16
    x[0] = zero + np.uint32(_SIGMA[0])
    x[5] = zero + np.uint32(_SIGMA[1])
    x[10] = zero + np.uint32(_SIGMA[2])
    x[15] = zero + np.uint32(_SIGMA[3])
    # seed limbs are little-endian; state words 1..4 take MSW..LSW
    x[1] = seeds[..., 3]
    x[2] = seeds[..., 2]
    x[3] = seeds[..., 1]
    x[4] = seeds[..., 0]
    x[8] = _pos_word(zero, ctr, 1)
    x[9] = _pos_word(zero, ctr, 0)
    init = list(x)
    for _ in range(6):
        _salsa_qr(x, 0, 4, 8, 12)
        _salsa_qr(x, 5, 9, 13, 1)
        _salsa_qr(x, 10, 14, 2, 6)
        _salsa_qr(x, 15, 3, 7, 11)
        _salsa_qr(x, 0, 1, 2, 3)
        _salsa_qr(x, 5, 6, 7, 4)
        _salsa_qr(x, 10, 11, 8, 9)
        _salsa_qr(x, 15, 12, 13, 14)
    return [x[i] + init[i] for i in range(16)]


def prf_salsa20_12_v(seeds, pos: int):
    """12-round Salsa20 core; key = seed words MSW-first in state 1..4."""
    out = _salsa20_12_words_v(seeds, pos)
    return u128._stack_last([out[4], out[3], out[2], out[1]])


def _chacha_qr(x, a, b, c, d):
    x[a] = x[a] + x[b]
    x[d] = _rotl(x[d] ^ x[a], 16)
    x[c] = x[c] + x[d]
    x[b] = _rotl(x[b] ^ x[c], 12)
    x[a] = x[a] + x[b]
    x[d] = _rotl(x[d] ^ x[a], 8)
    x[c] = x[c] + x[d]
    x[b] = _rotl(x[b] ^ x[c], 7)


def _chacha20_12_words_v(seeds, ctr):
    """Full 16-word ChaCha20/12 block (elementwise path)."""
    zero = seeds[..., 0] - seeds[..., 0]
    x = [zero] * 16
    for i in range(4):
        x[i] = zero + np.uint32(_SIGMA[i])
    x[4] = seeds[..., 3]
    x[5] = seeds[..., 2]
    x[6] = seeds[..., 1]
    x[7] = seeds[..., 0]
    x[12] = _pos_word(zero, ctr, 1)
    x[13] = _pos_word(zero, ctr, 0)
    init = list(x)
    for _ in range(6):
        _chacha_qr(x, 0, 4, 8, 12)
        _chacha_qr(x, 1, 5, 9, 13)
        _chacha_qr(x, 2, 6, 10, 14)
        _chacha_qr(x, 3, 7, 11, 15)
        _chacha_qr(x, 0, 5, 10, 15)
        _chacha_qr(x, 1, 6, 11, 12)
        _chacha_qr(x, 2, 7, 8, 13)
        _chacha_qr(x, 3, 4, 9, 14)
    return [x[i] + init[i] for i in range(16)]


def prf_chacha20_12_v(seeds, pos: int):
    """12-round ChaCha core; key = seed words MSW-first in state 4..7."""
    out = _chacha20_12_words_v(seeds, pos)
    return u128._stack_last([out[7], out[6], out[5], out[4]])


# ---------------------------------------------------------------------------
# Block-PRG ("wide") variants: child pos = word group pos%4 of the block
# at counter pos//4 (prf_ref.prf_salsa20_12_blk) — one 512-bit core call
# serves four GGM children
# ---------------------------------------------------------------------------

_BLK_WORDS_V = {PRF_SALSA20_BLK: _salsa20_12_words_v,
                PRF_CHACHA20_BLK: _chacha20_12_words_v}


def _blk_group(out, g: int):
    """128-bit child from block words [g, g+3] (MSW-first packing)."""
    return u128._stack_last([out[g + 3], out[g + 2], out[g + 1], out[g]])


def _prf_blk(words_fn, seeds, pos):
    """Child select over a block core: static pos slices a word group at
    trace time; traced pos (sqrt-N grid) selects dynamically.  The ONE
    place the group-to-limb mapping lives for every non-scalar backend
    (``words_fn`` is a ``(seeds, ctr) -> 16 words`` closure — elementwise
    or fori-loop JAX variant)."""
    if isinstance(pos, (int, np.integer)):
        return _blk_group(words_fn(seeds, int(pos) >> 2),
                          4 * (int(pos) & 3))
    out = words_fn(seeds, pos >> np.uint32(2))
    sel = pos & np.uint32(3)
    res = _blk_group(out, 0)
    if isinstance(seeds, np.ndarray):
        where = np.where
    else:
        import jax.numpy as jnp
        where = jnp.where
    for g in (1, 2, 3):
        res = where((sel == np.uint32(g))[..., None],
                    _blk_group(out, 4 * g), res)
    return res


def prf_salsa20_12_blk_v(seeds, pos):
    return _prf_blk(_salsa20_12_words_v, seeds, pos)


def prf_chacha20_12_blk_v(seeds, pos):
    return _prf_blk(_chacha20_12_words_v, seeds, pos)


# ---------------------------------------------------------------------------
# AES-128, byte-gather variant (host / debug)
# ---------------------------------------------------------------------------

_SBOX_NP = np.array(SBOX, dtype=np.uint32)


def _is_np(x):
    return isinstance(x, np.ndarray)


def _take(table_np, idx):
    if _is_np(idx):
        return table_np[idx]
    import jax.numpy as jnp
    return jnp.asarray(table_np)[idx]


def _bytes_of_limbs(seeds):
    """[..., 4]u32 -> [..., 16]u32 little-endian bytes."""
    parts = []
    for i in range(4):
        w = seeds[..., i]
        for s in (0, 8, 16, 24):
            parts.append((w >> np.uint32(s)) & np.uint32(0xFF))
    return u128._stack_last(parts)


def _limbs_of_bytes(b):
    """[..., 16]u32 bytes (LE) -> [..., 4]u32 limbs."""
    limbs = []
    for i in range(4):
        w = (b[..., 4 * i]
             | (b[..., 4 * i + 1] << np.uint32(8))
             | (b[..., 4 * i + 2] << np.uint32(16))
             | (b[..., 4 * i + 3] << np.uint32(24)))
        limbs.append(w)
    return u128._stack_last(limbs)


def _xtime_v(b):
    """GF(2^8) doubling on uint32 byte lanes."""
    d = (b << np.uint32(1)) ^ (((b >> np.uint32(7)) & np.uint32(1))
                               * np.uint32(0x1B))
    return d & np.uint32(0xFF)


def _pos_bytes(zero, pos):
    """16 LE plaintext byte planes of the position (int or uint32 array)."""
    if isinstance(pos, (int, np.integer)):
        pt = (int(pos) & ((1 << 128) - 1)).to_bytes(16, "little")
        return [zero + np.uint32(b) for b in pt]
    lo = [zero + ((pos >> np.uint32(8 * k)) & np.uint32(0xFF))
          for k in range(4)]
    return lo + [zero] * 12


def prf_aes128_v(seeds, pos: int):
    """FIPS-197 AES-128 per seed: key = seed LE bytes, pt = pos LE bytes.

    Gather (S-box lookup) variant.  Per-call key expansion is fused with
    encryption round-by-round so only one round key is live at a time — the
    optimization the reference left as a TODO (``dpf.py:32-33``).
    """
    kb = _bytes_of_limbs(seeds)  # [..., 16] key bytes
    rk = [kb[..., i] for i in range(16)]
    zero = seeds[..., 0] - seeds[..., 0]
    st = _pos_bytes(zero, pos)

    def sub(v):
        return _take(_SBOX_NP, v)

    rcon = 1
    # round 0 key addition
    st = [st[i] ^ rk[i] for i in range(16)]
    for rnd in range(1, 11):
        # SubBytes
        st = [sub(v) for v in st]
        # ShiftRows: byte r of column c comes from column (c+r)%4
        st = [st[(4 * ((i // 4 + i % 4) % 4)) + i % 4] for i in range(16)]
        # MixColumns (skipped in final round)
        if rnd < 10:
            ns = list(st)
            for c in range(4):
                a = st[4 * c:4 * c + 4]
                t = a[0] ^ a[1] ^ a[2] ^ a[3]
                ns[4 * c + 0] = a[0] ^ t ^ _xtime_v(a[0] ^ a[1])
                ns[4 * c + 1] = a[1] ^ t ^ _xtime_v(a[1] ^ a[2])
                ns[4 * c + 2] = a[2] ^ t ^ _xtime_v(a[2] ^ a[3])
                ns[4 * c + 3] = a[3] ^ t ^ _xtime_v(a[3] ^ a[0])
            st = ns
        # expand next round key in place (fused key schedule)
        t = [sub(rk[13]), sub(rk[14]), sub(rk[15]), sub(rk[12])]
        t[0] = t[0] ^ np.uint32(rcon)
        rcon = ((rcon << 1) ^ (0x11B if rcon & 0x80 else 0)) & 0xFF
        nk = list(rk)
        for i in range(4):
            nk[i] = rk[i] ^ t[i]
        for i in range(4, 16):
            nk[i] = nk[i - 4] ^ rk[i]
        rk = nk
        # AddRoundKey
        st = [st[i] ^ rk[i] for i in range(16)]
    return _limbs_of_bytes(u128._stack_last(st))


# ---------------------------------------------------------------------------
# JAX rolled-loop variants.
#
# The unrolled round loops above are fine for NumPy, but traced under jit
# they emit the full round chain per tree level (12 rounds x ~50 ops x
# log2(N) levels), which explodes XLA compile time.  These variants put the
# round loop in lax.fori_loop so each PRF body is compiled once per level:
# identical arithmetic, ~10x smaller HLO.
#
# Runtime trade-off: a rolled fori_loop materializes its [16, B, w] carry in
# HBM every iteration (the cipher is memory-bound that way); fully unrolling
# lets XLA fuse all rounds into one elementwise kernel.  ``ROUND_UNROLL``
# picks per backend: unroll on TPU, rolled elsewhere (CPU XLA chokes on the
# big graphs).  Bitsliced AES is the exception: its rounds stay rolled
# unless forced (``aes_bitsliced.py``), since its unrolled N = 2^20
# program did not compile for a v5e in 20 min (PR 21).  Override by
# setting the module flag or EvalConfig.round_unroll.
# ---------------------------------------------------------------------------

ROUND_UNROLL = None  # None = auto (unroll on TPU), True/False = force


def _round_unroll() -> bool:
    if ROUND_UNROLL is not None:
        return bool(ROUND_UNROLL)
    import jax
    return jax.default_backend() == "tpu"

def _salsa_state(seeds, pos: int):
    import jax.numpy as jnp
    zero = seeds[..., 0] - seeds[..., 0]
    x = [zero] * 16
    x[0] = zero + np.uint32(_SIGMA[0])
    x[5] = zero + np.uint32(_SIGMA[1])
    x[10] = zero + np.uint32(_SIGMA[2])
    x[15] = zero + np.uint32(_SIGMA[3])
    x[1], x[2], x[3], x[4] = (seeds[..., 3], seeds[..., 2], seeds[..., 1],
                              seeds[..., 0])
    x[8] = _pos_word(zero, pos, 1)
    x[9] = _pos_word(zero, pos, 0)
    return jnp.stack(x)


def _salsa20_12_words_jax(seeds, ctr, unroll: bool | None = None):
    import jax
    import jax.numpy as jnp
    init = _salsa_state(seeds, ctr)

    def double_round(_, s):
        x = [s[i] for i in range(16)]
        for (a, b, c, d) in ((0, 4, 8, 12), (5, 9, 13, 1), (10, 14, 2, 6),
                             (15, 3, 7, 11), (0, 1, 2, 3), (5, 6, 7, 4),
                             (10, 11, 8, 9), (15, 12, 13, 14)):
            x[b] = x[b] ^ _rotl(x[a] + x[d], 7)
            x[c] = x[c] ^ _rotl(x[b] + x[a], 9)
            x[d] = x[d] ^ _rotl(x[c] + x[b], 13)
            x[a] = x[a] ^ _rotl(x[d] + x[c], 18)
        return jnp.stack(x)

    x = jax.lax.fori_loop(0, 6, double_round, init,
                          unroll=_round_unroll() if unroll is None
                          else unroll)
    return x + init


def prf_salsa20_12_jax(seeds, pos: int, unroll: bool | None = None):
    out = _salsa20_12_words_jax(seeds, pos, unroll)
    return u128._stack_last([out[4], out[3], out[2], out[1]])


def _chacha_state(seeds, pos: int):
    import jax.numpy as jnp
    zero = seeds[..., 0] - seeds[..., 0]
    x = [zero + np.uint32(_SIGMA[i]) for i in range(4)] + [zero] * 12
    x[4], x[5], x[6], x[7] = (seeds[..., 3], seeds[..., 2], seeds[..., 1],
                              seeds[..., 0])
    x[12] = _pos_word(zero, pos, 1)
    x[13] = _pos_word(zero, pos, 0)
    return jnp.stack(x)


def _chacha20_12_words_jax(seeds, ctr, unroll: bool | None = None):
    import jax
    import jax.numpy as jnp
    init = _chacha_state(seeds, ctr)

    def double_round(_, s):
        x = [s[i] for i in range(16)]
        for (a, b, c, d) in ((0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14),
                             (3, 7, 11, 15), (0, 5, 10, 15), (1, 6, 11, 12),
                             (2, 7, 8, 13), (3, 4, 9, 14)):
            x[a] = x[a] + x[b]
            x[d] = _rotl(x[d] ^ x[a], 16)
            x[c] = x[c] + x[d]
            x[b] = _rotl(x[b] ^ x[c], 12)
            x[a] = x[a] + x[b]
            x[d] = _rotl(x[d] ^ x[a], 8)
            x[c] = x[c] + x[d]
            x[b] = _rotl(x[b] ^ x[c], 7)
        return jnp.stack(x)

    x = jax.lax.fori_loop(0, 6, double_round, init,
                          unroll=_round_unroll() if unroll is None
                          else unroll)
    return x + init


def prf_chacha20_12_jax(seeds, pos: int, unroll: bool | None = None):
    out = _chacha20_12_words_jax(seeds, pos, unroll)
    return u128._stack_last([out[7], out[6], out[5], out[4]])


_BLK_WORDS_JAX = {PRF_SALSA20_BLK: _salsa20_12_words_jax,
                  PRF_CHACHA20_BLK: _chacha20_12_words_jax}


def prf_salsa20_12_blk_jax(seeds, pos, unroll: bool | None = None):
    return _prf_blk(lambda s, c: _salsa20_12_words_jax(s, c, unroll),
                    seeds, pos)


def prf_chacha20_12_blk_jax(seeds, pos, unroll: bool | None = None):
    return _prf_blk(lambda s, c: _chacha20_12_words_jax(s, c, unroll),
                    seeds, pos)


_RCON = np.array([0, 1, 2, 4, 8, 16, 32, 64, 128, 0x1B, 0x36],
                 dtype=np.uint32)

# ShiftRows as a static permutation of flat byte index i = 4*col + row:
# new[4c + r] = old[4*((c + r) % 4) + r]
_SHIFT_ROWS = np.array([(4 * ((i // 4 + i % 4) % 4)) + i % 4
                        for i in range(16)])



def _aes_next_round_key_jax(sbox, rcon, rk, rnd):
    """One AES-128 key-schedule step on [16, ...] byte planes (shared by
    the single-call and fused-pair variants — keep them bit-identical)."""
    import jax.numpy as jnp
    t = [sbox[rk[13]] ^ rcon[rnd], sbox[rk[14]], sbox[rk[15]], sbox[rk[12]]]
    w = [rk[i] ^ t[i] for i in range(4)]
    for i in range(4, 16):
        w.append(w[i - 4] ^ rk[i])
    return jnp.stack(w)


def _aes_mix_columns_jax(x):
    """MixColumns on [16, ...] byte planes."""
    import jax.numpy as jnp
    ns = []
    for c in range(4):
        a = [x[4 * c + r] for r in range(4)]
        t = a[0] ^ a[1] ^ a[2] ^ a[3]
        ns.append(a[0] ^ t ^ _xtime_v(a[0] ^ a[1]))
        ns.append(a[1] ^ t ^ _xtime_v(a[1] ^ a[2]))
        ns.append(a[2] ^ t ^ _xtime_v(a[2] ^ a[3]))
        ns.append(a[3] ^ t ^ _xtime_v(a[3] ^ a[0]))
    return jnp.stack(ns)


def prf_aes128_jax(seeds, pos: int, unroll: bool | None = None):
    """AES-128 with the 9 uniform middle rounds in a fori_loop."""
    import jax
    import jax.numpy as jnp
    sbox = jnp.asarray(_SBOX_NP)

    kb = _bytes_of_limbs(seeds)
    rk = jnp.stack([kb[..., i] for i in range(16)])  # [16, ...]
    zero = seeds[..., 0] - seeds[..., 0]
    st = jnp.stack(_pos_bytes(zero, pos))

    rcon = jnp.asarray(_RCON)

    def next_round_key(rk, rnd):
        return _aes_next_round_key_jax(sbox, rcon, rk, rnd)

    mix_columns = _aes_mix_columns_jax

    st = st ^ rk  # round 0

    def round_body(rnd, carry):
        st, rk = carry
        st = sbox[st]                 # SubBytes, one gather
        st = st[_SHIFT_ROWS]          # ShiftRows, static row permute
        st = mix_columns(st)
        rk = next_round_key(rk, rnd)
        return (st ^ rk, rk)

    st, rk = jax.lax.fori_loop(1, 10, round_body, (st, rk),
                              unroll=_round_unroll() if unroll is None
                              else unroll)
    # final round: no MixColumns
    st = sbox[st][_SHIFT_ROWS]
    rk = next_round_key(rk, 10)
    st = st ^ rk
    return _limbs_of_bytes(u128._stack_last([st[i] for i in range(16)]))


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

PRF_V_NUMPY = {
    PRF_DUMMY: prf_dummy_v,
    PRF_SALSA20: prf_salsa20_12_v,
    PRF_CHACHA20: prf_chacha20_12_v,
    PRF_AES128: prf_aes128_v,
    PRF_SALSA20_BLK: prf_salsa20_12_blk_v,
    PRF_CHACHA20_BLK: prf_chacha20_12_blk_v,
}

PRF_V_JAX = {
    PRF_DUMMY: prf_dummy_v,  # small graph already
    PRF_SALSA20: prf_salsa20_12_jax,
    PRF_CHACHA20: prf_chacha20_12_jax,
    PRF_AES128: prf_aes128_jax,
    PRF_SALSA20_BLK: prf_salsa20_12_blk_jax,
    PRF_CHACHA20_BLK: prf_chacha20_12_blk_jax,
}


def prf_v(method: int, seeds, pos, unroll: bool | None = None):
    """Vectorized PRF dispatch.  `method` is static; `pos` is a static
    int (the GGM branch constants) OR a traced uint32 array of positions
    broadcastable against the seed batch (the sqrt-N grid eval) — do not
    mark `pos` as a jit static argument."""
    if isinstance(seeds, np.ndarray):
        return PRF_V_NUMPY[method](seeds, pos)
    if method == PRF_DUMMY:
        return prf_dummy_v(seeds, pos)
    return PRF_V_JAX[method](seeds, pos, unroll)


def prf_aes128_pair_jax(seeds, unroll: bool | None = None):
    """AES of positions 0 AND 1 under the same per-seed key.

    The GGM level step always needs both children of a node; their AES keys
    are identical (the seed), so the key schedule — ~1/3 of the per-call
    work — is computed once and shared between the two encryptions.
    """
    return prf_aes128_multi_jax(seeds, 2, unroll)


def prf_aes128_multi_jax(seeds, arity: int, unroll: bool | None = None):
    """AES of positions 0..arity-1 under the same per-seed key (gather
    S-box variant); one shared key schedule for all children."""
    import jax
    import jax.numpy as jnp
    sbox = jnp.asarray(_SBOX_NP)

    kb = _bytes_of_limbs(seeds)
    rk = jnp.stack([kb[..., i] for i in range(16)])
    zero = seeds[..., 0] - seeds[..., 0]
    rcon = jnp.asarray(_RCON)

    def next_round_key(rk, rnd):
        return _aes_next_round_key_jax(sbox, rcon, rk, rnd)

    mix_columns = _aes_mix_columns_jax

    # plaintexts 0..arity-1 differ only in byte 0
    sts = tuple(jnp.stack([zero + np.uint32(b)] + [zero] * 15) ^ rk
                for b in range(arity))

    def round_body(rnd, carry):
        sts, rk = carry
        sts = tuple(mix_columns(sbox[st][_SHIFT_ROWS]) for st in sts)
        rk = next_round_key(rk, rnd)
        return (tuple(st ^ rk for st in sts), rk)

    sts, rk = jax.lax.fori_loop(1, 10, round_body, (sts, rk),
                                unroll=_round_unroll() if unroll is None
                                else unroll)
    rk = next_round_key(rk, 10)
    sts = tuple(sbox[st][_SHIFT_ROWS] ^ rk for st in sts)
    return tuple(
        _limbs_of_bytes(u128._stack_last([st[i] for i in range(16)]))
        for st in sts)


AES_PAIR_IMPL = "auto"  # "auto" | "gather" | "bitsliced"


def _aes_pair_impl() -> str:
    """Resolved module default ("gather"/"bitsliced") — thread this into
    jitted programs as a static argument."""
    if AES_PAIR_IMPL != "auto":
        return AES_PAIR_IMPL
    # gathers are slow on the TPU: one 512-key gather-AES eval at
    # N = 2^20 did not finish in 25 min on a v5e (PR 21)
    import jax
    return "bitsliced" if jax.default_backend() == "tpu" else "gather"


def prf_pair(method: int, seeds, aes_impl: str | None = None,
             unroll: bool | None = None):
    """Both children PRF(seed, 0), PRF(seed, 1) — fused where profitable.

    For AES the key schedule is shared between the two children;
    ``aes_impl="bitsliced"`` runs the whole cipher on boolean planes (no
    gathers) — see ``aes_bitsliced.py``.  All variants are bit-identical.  ``aes_impl``
    and ``unroll`` must be threaded from jit *static* arguments by callers
    inside jit (module defaults otherwise) so switching retraces.
    """
    return prf_multi(method, seeds, 2, aes_impl, unroll)


def prf_multi(method: int, seeds, arity: int,
              aes_impl: str | None = None, unroll: bool | None = None):
    """All `arity` children PRF(seed, 0..arity-1) — fused where profitable.

    The radix-4 GGM step (``core/radix4.py``) evaluates four children per
    node; for AES one key schedule and one fused S-box circuit pass per
    round cover all of them (16*arity + 4 byte positions), amortizing the
    schedule twice as well as the binary step.
    """
    if method in _BLK_WORDS_V:
        # One 512-bit core block serves ALL children (<=4): the whole
        # point of the block-PRG construction — a radix-4 node costs one
        # core call instead of four (prf_ref.prf_salsa20_12_blk).
        assert arity <= 4, "block PRG yields 4 children per counter"
        if isinstance(seeds, np.ndarray):
            out = _BLK_WORDS_V[method](seeds, 0)
        else:
            out = _BLK_WORDS_JAX[method](seeds, 0, unroll)
        return tuple(_blk_group(out, 4 * b) for b in range(arity))
    if not isinstance(seeds, np.ndarray) and method == PRF_AES128:
        impl = (aes_impl if aes_impl not in (None, "auto")
                else _aes_pair_impl())
        if impl.startswith("bitsliced"):
            # "bitsliced" or "bitsliced:<sbox>" with sbox in bp/tower/chain
            from .aes_bitsliced import aes128_multi_bitsliced
            sbox = impl.split(":", 1)[1] if ":" in impl else None
            return aes128_multi_bitsliced(seeds, arity, unroll, sbox)
        return prf_aes128_multi_jax(seeds, arity, unroll)
    return tuple(prf_v(method, seeds, b, unroll) for b in range(arity))

