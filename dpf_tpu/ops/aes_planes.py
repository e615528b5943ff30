"""Plane-domain bitsliced AES-128 for Pallas TPU level kernels.

``core/aes_bitsliced.py`` packs AES instances into word bits along the
*flattened element* axis, which needs minor-dim reshapes and byte-axis
gathers — fine under XLA, hostile inside a Mosaic kernel.  This module is
the Pallas-native re-expression of the same cipher (the hand-scheduled
path the reference gives its headline PRF via the templated hybrid
kernel, ``dpf_gpu/dpf/dpf_hybrid.cu:258-272`` + ``dpf_gpu/prf/prf.cu``):

* Instance layout: a GGM level step's elements are ``[32 keys, W
  columns]``; the 32 key rows are bit-packed into uint32 words (one
  ``_transpose32`` shift-swap cascade per limb) so every plane tensor is
  ``[1, W]`` with the column axis riding the 128-wide lanes.
* Every AES byte-axis manipulation (ShiftRows, RotWord, MixColumns'
  row rotation) is a static slice + concatenate — no gathers, no
  minor-dim reshapes, so the whole cipher lowers through Mosaic.
* The GGM codeword select + 128-bit add also run in plane domain: the
  select is three boolean ops per bit against per-key codeword bit words
  (SMEM scalars), the add is a ripple-carry full-adder chain — ~20
  word-equivalent ops per child, amortized 32x by the packing.
* S-box circuits are shared with the XLA path (``aes_sbox_bp`` /
  ``aes_sbox_circuit`` / chain) — they are pure plane-op circuits.

Semantics are bit-identical to ``prf_ref.prf_aes128`` /
``aes_bitsliced.aes128_multi_bitsliced`` (asserted in tests).

AES is compute-bound (~1.4K plane ops per 16-byte block vs 16 B of HBM
traffic), so unlike ChaCha there is no benefit in keeping whole subtrees
VMEM-resident; the kernel here is ONE level step (PRF children + select
+ add fused), dispatched per level by the drivers in ``core/expand.py``
and ``core/radix4.py`` — each kernel compiles in seconds.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..core.aes_bitsliced import (_RCON_VALS, _SHIFT_ROWS_BYTE,
                                  _sbox_bits, _transpose32)

TILE_KEYS = 32       # key rows bit-packed per word (fixed by uint32)
DEFAULT_TW = 256     # column tile: 32*TW instances, ~1 MB VMEM live state


def pack32(rows):
    """32 word tensors (key rows, any common shape) -> 32 bit planes.

    Same convention as ``aes_bitsliced.pack_planes`` over a 32-element
    block: plane b holds bit b of every key, key order within a word
    permuted by a fixed involution (harmless — ``unpack32`` inverts it,
    and host-side codeword packing uses the same convention).
    """
    return _transpose32(list(rows))[::-1]


def unpack32(planes):
    """Inverse of ``pack32``: 32 bit planes -> 32 key-row words."""
    return _transpose32(list(planes)[::-1])


# ---------------------------------------------------------------------------
# Plane-domain AES core (state = 8 tensors [16, W]; byte ops = static
# slices + concats; instances = 32 packed keys x W columns)
# ---------------------------------------------------------------------------

def _byte_select(x, perm):
    return jnp.concatenate([x[i:i + 1] for i in perm], axis=0)


def _shift_rows(bits, m: int = 1):
    """Byte permutation; ``m`` fused states tile the 16-byte pattern."""
    if m == 1:
        perm = _SHIFT_ROWS_BYTE
    else:
        perm = np.concatenate([_SHIFT_ROWS_BYTE + 16 * k
                               for k in range(m)])
    return [_byte_select(b, perm) for b in bits]


def _xtime_bits(bits):
    out = [bits[7]]
    for i in range(1, 8):
        v = bits[i - 1]
        if (0x1B >> i) & 1:
            v = v ^ bits[7]
        out.append(v)
    return out


def _mix_columns(bits):
    """Works on any multiple of 16 bytes (M fused states = 4M columns);
    major-axis reshapes only (Mosaic-safe)."""
    a4 = [b.reshape(-1, 4, b.shape[-1]) for b in bits]  # [col, row, W]
    nxt = [jnp.concatenate([a[:, 1:], a[:, :1]], axis=1) for a in a4]
    x = [a4[i] ^ nxt[i] for i in range(8)]
    xt = _xtime_bits(x)
    out = []
    for i in range(8):
        t = (a4[i][:, 0:1] ^ a4[i][:, 1:2] ^ a4[i][:, 2:3]
             ^ a4[i][:, 3:4])
        out.append((a4[i] ^ t ^ xt[i]).reshape(bits[i].shape))
    return out


def _ark_tiled(st, rk, m_cnt):
    """AddRoundKey on a fused state via leading-axis rk tiling (concat,
    not broadcast-reshape: leading-axis concat is the Mosaic-safest)."""
    if m_cnt == 1:
        return [st[i] ^ rk[i] for i in range(8)]
    return [st[i] ^ jnp.concatenate([rk[i]] * m_cnt, axis=0)
            for i in range(8)]


def _round_fused(st, rk, m_cnt, rcon, ones_row, sbox):
    """One fused round on M fused states (planes [16*M, W]) + schedule
    step.  ``rcon`` is either a static int (unrolled rounds: the byte-0
    flip folds to a constant) or a traced uint32 scalar (fori_loop
    rounds: flip via a computed mask).  ShiftRows/MixColumns/ARK
    downstream also run once on the fused tensor — the per-round op
    count no longer scales with M.
    """
    rot = [jnp.concatenate([rk[i][13:14], rk[i][14:15], rk[i][15:16],
                            rk[i][12:13]], axis=0) for i in range(8)]
    fused_in = [jnp.concatenate([st[i], rot[i]], axis=0)
                for i in range(8)]
    fused_out = _sbox_bits(fused_in, ones_row, sbox)
    sub = [f[:16 * m_cnt] for f in fused_out]
    t = [f[16 * m_cnt:16 * m_cnt + 4] for f in fused_out]
    if isinstance(rcon, (int, np.integer)):
        t = [jnp.concatenate(
            [t[i][0:1] ^ np.uint32(0xFFFFFFFF), t[i][1:]], axis=0)
            if (int(rcon) >> i) & 1 else t[i] for i in range(8)]
    else:
        masks = [(np.uint32(0) - ((rcon >> np.uint32(i))
                                  & np.uint32(1))).astype(jnp.uint32)
                 for i in range(8)]
        t = [jnp.concatenate([t[i][0:1] ^ masks[i], t[i][1:]], axis=0)
             for i in range(8)]
    new_rk = []
    for i in range(8):
        w0 = rk[i][0:4] ^ t[i]
        w1 = w0 ^ rk[i][4:8]
        w2 = w1 ^ rk[i][8:12]
        w3 = w2 ^ rk[i][12:16]
        new_rk.append(jnp.concatenate([w0, w1, w2, w3], axis=0))
    return sub, new_rk


def aes128_multi_planes(key_planes, n_pts: int, sbox: str | None = None,
                        unroll: bool = True):
    """AES of positions 0..n_pts-1 under per-instance keys, plane domain.

    key_planes: 128 tensors [1, W] — bit t (= limb t//32, bit t%32) of
    every instance's seed.  Returns ``n_pts`` lists of 128 output planes
    with the same bit indexing, matching ``prf_ref.prf_aes128(seed, b)``.

    ``unroll=True`` (the Pallas kernel) unrolls the 9 uniform middle
    rounds; ``unroll=False`` (the non-Pallas reference path) runs them in
    a ``fori_loop`` so the traced graph stays one round body deep — the
    fully-unrolled cipher times out XLA-CPU compilation when several
    levels stack in one program.
    """
    rk = [jnp.concatenate([key_planes[8 * byte + i] for byte in range(16)],
                          axis=0) for i in range(8)]  # 8 x [16, W]
    ones_row = jnp.full_like(key_planes[0], np.uint32(0xFFFFFFFF))

    # plaintext b: only byte 0 nonzero; fold into the initial ARK.
    # States live FUSED back to back on the byte axis ([16*M, W] planes)
    # for the whole cipher.
    st = []
    for i in range(8):
        blocks = []
        for b in range(n_pts):
            if (b >> i) & 1:
                blocks.append(jnp.concatenate(
                    [rk[i][0:1] ^ np.uint32(0xFFFFFFFF), rk[i][1:]],
                    axis=0))
            else:
                blocks.append(rk[i])
        st.append(blocks[0] if n_pts == 1
                  else jnp.concatenate(blocks, axis=0))

    def middle(st, rk, rcon):
        sub, rk = _round_fused(st, rk, n_pts, rcon, ones_row, sbox)
        return _ark_tiled(_mix_columns(_shift_rows(sub, n_pts)), rk,
                          n_pts), rk

    if unroll:
        for rnd in range(1, 10):
            st, rk = middle(st, rk, _RCON_VALS[rnd])
    else:
        # rcon is carried as a scalar and stepped by xtime in GF(256)
        # (rcon_{r+1} = xtime(rcon_r)) instead of indexing a u32[10]
        # constant: a captured constant array is rejected inside Pallas
        # kernel bodies, and the recurrence is two scalar ops.
        def body(r, carry):
            s, c, rcon = carry
            sl, rkl = middle([s[i] for i in range(8)],
                             [c[i] for i in range(8)], rcon)
            rcon = ((rcon << np.uint32(1))
                    ^ ((rcon >> np.uint32(7)) * np.uint32(0x11B))
                    ) & np.uint32(0xFF)
            return (jnp.stack(sl), jnp.stack(rkl), rcon)

        carry = (jnp.stack(st), jnp.stack(rk), jnp.uint32(1))
        carry = jax.lax.fori_loop(0, 9, body, carry)
        st = [carry[0][i] for i in range(8)]
        rk = [carry[1][i] for i in range(8)]

    sub, rk = _round_fused(st, rk, n_pts, _RCON_VALS[10], ones_row, sbox)
    fin = _ark_tiled(_shift_rows(sub, n_pts), rk, n_pts)
    outs = []
    for b in range(n_pts):
        outs.append([fin[p % 8][16 * b + p // 8:16 * b + p // 8 + 1]
                     for p in range(128)])
    return outs


# ---------------------------------------------------------------------------
# GGM plumbing in plane domain
# ---------------------------------------------------------------------------

def _add128_planes(a, b):
    """128-bit add mod 2^128 as a ripple-carry full-adder chain."""
    out = []
    carry = None
    for t in range(128):
        axb = a[t] ^ b[t]
        if carry is None:
            out.append(axb)
            carry = a[t] & b[t]
        else:
            out.append(axb ^ carry)
            carry = (a[t] & b[t]) | (carry & axb)
    return out


def pack_cw_planes(cw_lvl):
    """Host-side codeword bit packing for the level kernel.

    cw_lvl: [B, A, 4] uint32 (B % 32 == 0) — this level's codewords.
    Returns [B//32, A*128] uint32: word (tile, a*128 + t) holds bit t of
    the A-th codeword of the tile's 32 keys, packed with the ``pack32``
    key order (so it composes with the in-kernel seed packing).
    """
    bsz, a_cnt, _ = cw_lvl.shape
    assert bsz % TILE_KEYS == 0
    v = cw_lvl.reshape(bsz // TILE_KEYS, TILE_KEYS, a_cnt * 4)
    rows = [v[:, k, :] for k in range(TILE_KEYS)]     # [tiles, A*4] each
    planes = _transpose32(rows)[::-1]                 # 32 x [tiles, A*4]
    # bit index t = 32*limb + plane  ->  stack planes minor, limbs next
    stacked = jnp.stack(planes, axis=-1)              # [tiles, A*4, 32]
    return stacked.reshape(bsz // TILE_KEYS, a_cnt, 4 * 32).reshape(
        bsz // TILE_KEYS, a_cnt * 128)


def _level_planes_core(seed_limbs, cw1_at, cw2_at, arity: int,
                       sbox: str | None, unroll: bool = True):
    """Shared level-step body (kernel and non-Pallas reference).

    seed_limbs: 4 tensors [32, W] (key rows x columns, limb l).
    cw*_at(i): scalar accessor for codeword bit word i (i = b*128 + t).
    Returns ``arity`` lists of 4 limb tensors [32, W] (child b).
    """
    planes = []
    for l in range(4):
        rows = [seed_limbs[l][k:k + 1, :] for k in range(TILE_KEYS)]
        planes.extend(pack32(rows))                   # 128 x [1, W]
    sel = planes[0]                                   # LSB plane
    outs = aes128_multi_planes(planes, arity, sbox, unroll)
    res = []
    for b in range(arity):
        cw = []
        for t in range(128):
            c1 = cw1_at(b * 128 + t)
            c2 = cw2_at(b * 128 + t)
            cw.append(c1 ^ (sel & (c1 ^ c2)))
        child = _add128_planes(outs[b], cw)
        res.append([jnp.concatenate(unpack32(child[32 * l:32 * l + 32]),
                                    axis=0) for l in range(4)])
    return res


def _make_aes_level_kernel(arity: int, sbox: str | None,
                           unroll: bool = True):
    def kernel(cw1p_ref, cw2p_ref, seeds_ref, *out_refs):
        # seeds_ref [4, 32, TW]; cw*p_ref [1, arity*128] (SMEM);
        # out_refs: arity x [4, 32, TW]
        res = _level_planes_core(
            [seeds_ref[l] for l in range(4)],
            lambda i: cw1p_ref[0, i], lambda i: cw2p_ref[0, i],
            arity, sbox, unroll=unroll)
        for b in range(arity):
            for l in range(4):
                out_refs[b][l] = res[b][l]

    return kernel


def aes_level_step_ref(seeds, cw1_lvl, cw2_lvl, *, arity: int = 2,
                       sbox: str | None = None):
    """Non-Pallas reference of ``aes_level_step_pallas``: identical math
    (same packing, same plane circuits, same accessors) as plain traced
    jnp.  Exists so the full driver glue (cw slicing, grouping, scan,
    contraction) is testable without interpret-mode Pallas cost; the
    kernel itself is asserted against this in the small interpret tests.
    """
    bsz, w, _ = seeds.shape
    pb = (-bsz) % TILE_KEYS
    if pb:
        seeds = jnp.pad(seeds, ((0, pb), (0, 0), (0, 0)))
        cw1_lvl = jnp.pad(cw1_lvl, ((0, pb), (0, 0), (0, 0)))
        cw2_lvl = jnp.pad(cw2_lvl, ((0, pb), (0, 0), (0, 0)))
    bp = bsz + pb
    cw1p = pack_cw_planes(cw1_lvl)
    cw2p = pack_cw_planes(cw2_lvl)
    tiles = []
    for ti in range(bp // TILE_KEYS):
        sl = slice(ti * TILE_KEYS, (ti + 1) * TILE_KEYS)
        res = _level_planes_core(
            [seeds[sl, :, l] for l in range(4)],
            lambda i, ti=ti: cw1p[ti, i], lambda i, ti=ti: cw2p[ti, i],
            arity, sbox, unroll=False)
        # res[b][l]: [32, w] -> node-major children [32, A*w, 4]
        kids = jnp.stack([jnp.stack(res[b], axis=-1)
                          for b in range(arity)], axis=2)
        tiles.append(kids.reshape(TILE_KEYS, arity * w, 4))
    return jnp.concatenate(tiles, axis=0)[:bsz]


def _aes_level_step_impl(seeds, cw1_lvl, cw2_lvl, *, arity: int = 2,
                         sbox: str | None = None, interpret: bool = False,
                         tw: int = DEFAULT_TW, unroll: bool = True):
    """One AES GGM level via the plane-domain Pallas kernel.

    seeds: [B, w, 4] u32; cw*_lvl: [B, arity, 4] u32 (this level's
    codewords, branch-major).  Returns [B, arity*w, 4] children in
    node-major order (child b of node j at arity*j + b) — the same
    convention as ``expand._level_step_pair`` / ``radix4._level_step_mixed``,
    so the standard permuted tables apply unchanged.

    ``unroll=False`` runs the 9 middle rounds in a ``fori_loop`` — a
    ~10x smaller traced graph, used by the interpret-mode tests (the
    unrolled cipher leg is pinned directly by the cipher-vs-reference
    tests); the production TPU path keeps the unrolled body.
    """
    from jax.experimental import pallas as pl

    bsz, w, _ = seeds.shape
    tw = min(tw, w)
    pb = (-bsz) % TILE_KEYS
    pw = (-w) % tw
    if pb or pw:
        seeds = jnp.pad(seeds, ((0, pb), (0, pw), (0, 0)))
        cw1_lvl = jnp.pad(cw1_lvl, ((0, pb), (0, 0), (0, 0)))
        cw2_lvl = jnp.pad(cw2_lvl, ((0, pb), (0, 0), (0, 0)))
    bp, wp = bsz + pb, w + pw

    sm = jnp.transpose(seeds, (2, 0, 1))              # [4, B, w]
    cw1p = pack_cw_planes(cw1_lvl)                    # [tiles, A*128]
    cw2p = pack_cw_planes(cw2_lvl)

    from jax.experimental.pallas import tpu as pltpu
    # [tiles, 1, A*128]: the block's last two dims equal the array's, as
    # Mosaic requires (a (1, A*128) block of a 2-D array is refused)
    cw1p = cw1p[:, None, :]
    cw2p = cw2p[:, None, :]
    cw_spec = pl.BlockSpec((pl.squeezed, 1, arity * 128),
                           lambda i, j: (i, 0, 0),
                           memory_space=pltpu.SMEM)

    from .pallas_level import _compiler_params

    grid = (bp // TILE_KEYS, wp // tw)
    kernel = _make_aes_level_kernel(arity, sbox, unroll)
    outs = pl.pallas_call(
        kernel,
        name="dpf_aes_level",
        grid=grid,
        # key tiles and column tiles are fully independent
        compiler_params=_compiler_params(("parallel", "parallel")),
        in_specs=[
            cw_spec,
            cw_spec,
            pl.BlockSpec((4, TILE_KEYS, tw), lambda i, j: (0, i, j)),
        ],
        out_specs=[pl.BlockSpec((4, TILE_KEYS, tw), lambda i, j: (0, i, j))
                   ] * arity,
        out_shape=[jax.ShapeDtypeStruct((4, bp, wp), jnp.uint32)] * arity,
        interpret=interpret,
    )(cw1p, cw2p, sm)

    children = jnp.stack([jnp.transpose(o, (1, 2, 0)) for o in outs],
                         axis=2)                      # [B, w, A, 4]
    return children.reshape(bp, arity * wp, 4)[:bsz, :arity * w]


_aes_level_step_jit = functools.partial(
    jax.jit, static_argnames=("arity", "sbox", "interpret", "tw",
                              "unroll"))(_aes_level_step_impl)


def aes_level_step_pallas(seeds, cw1_lvl, cw2_lvl, *, arity: int = 2,
                          sbox: str | None = None, interpret: bool = False,
                          tw: int = DEFAULT_TW, unroll: bool = True):
    """Jit-wrapped plane-AES level kernel; ``interpret=True`` runs
    EAGERLY — interpret-mode pallas_call under jit makes XLA-CPU compile
    blow up super-linearly with grid size (see
    ``pallas_level.chacha_level_step_pallas``)."""
    fn = _aes_level_step_impl if interpret else _aes_level_step_jit
    return fn(seeds, cw1_lvl, cw2_lvl, arity=arity, sbox=sbox,
              interpret=interpret, tw=tw, unroll=unroll)
