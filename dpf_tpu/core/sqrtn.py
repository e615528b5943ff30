"""Sqrt-N DPF construction: O(sqrt N) keys, single-level PRF evaluation.

Re-derivation of the reference's non-recursive construction
(``dpf_base/dpf.h:290-360``, the ``GenerateSeedsAndCodewords`` base case)
as a standalone TPU-friendly scheme.  The table of N entries is viewed as
an ``R x K`` grid (rows ``R = n_codewords``, columns ``K = n_keys``,
index ``x = r * K + j``):

* Each server holds K 128-bit column seeds, identical across servers
  except at the target column ``j* = alpha % K``, where the two seeds are
  random with *opposite* LSBs.  Which server gets the even seed is itself
  a coin flip: each server's marginal view is K uniform seeds, so a
  single server learns nothing about ``j*`` (forcing a fixed parity per
  server would let it rule out every column whose seed has the other
  parity — half the columns).
* Both servers hold the same two codeword arrays ``cw1[R]``, ``cw2[R]``;
  an evaluator adds ``cw1[r]`` or ``cw2[r]`` by the LSB of its column
  seed.  With ``s_e``/``s_o`` the even/odd target seeds,
  ``cw2 - cw1 = PRF(s_e, r) - PRF(s_o, r) - (-1)^[server2 is even] *
  beta * [r == r*]`` makes the shares differ by ``beta`` exactly at
  ``alpha`` regardless of which server drew the even seed.

Compared with log-N keys (O(log N) size, O(N) PRFs tree-walked), sqrt-N
keys are O(sqrt N) big but evaluation is a *flat* PRF grid — one
vectorized PRF call over ``[R, K]`` (positions vary along rows: the PRF
variants accept traced position arrays) plus one select/add.  On TPU that
is one fused elementwise program with no level loop at all, so it's the
latency-friendly construction for mid-sized tables, and the natural-order
output needs no bit-reversal permutation.

Keys use their own wire format (the reference never serializes sqrt keys;
its wrapper ships log-N only): ``[K | R | n | alpha_pad | keys[K] |
cw1[R] | cw2[R]]`` as uint128 little-endian slots viewed as int32.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from . import u128
from .expand import CHUNK_SEED_BYTES_BOUND
from .keygen import Shake256Drbg
from .prf import prf_v
from .prf_ref import MASK128, PRF_FUNCS


@dataclass
class SqrtKey:
    """One server's sqrt-N DPF key (host representation)."""
    n_keys: int          # K — column seeds
    n_codewords: int     # R — rows (N = R * K)
    n: int
    keys: np.ndarray     # [K, 4] uint32 limbs
    cw1: np.ndarray      # [R, 4] uint32
    cw2: np.ndarray      # [R, 4] uint32

    def serialize(self) -> np.ndarray:
        k, r = self.n_keys, self.n_codewords
        slots = np.zeros((4 + k + 2 * r, 4), dtype=np.uint32)
        slots[0] = u128.int_to_limbs(k)
        slots[1] = u128.int_to_limbs(r)
        slots[2] = u128.int_to_limbs(self.n)
        slots[4:4 + k] = self.keys
        slots[4 + k:4 + k + r] = self.cw1
        slots[4 + k + r:] = self.cw2
        return slots.reshape(-1).view(np.int32).copy()


def deserialize_sqrt_key(arr) -> SqrtKey:
    flat = np.asarray(arr, dtype=np.int32).reshape(-1)
    if flat.size % 4 or flat.size < 8:
        raise ValueError("malformed sqrt-N key: %d int32 words" % flat.size)
    slots = flat.view(np.uint32).reshape(-1, 4)
    k = int(slots[0, 0])
    r = int(slots[1, 0])
    if slots.shape[0] != 4 + k + 2 * r:
        raise ValueError("malformed sqrt-N key: %d slots for K=%d R=%d"
                         % (slots.shape[0], k, r))
    n = u128.limbs_to_int(slots[2])
    if k * r != n:
        raise ValueError("malformed sqrt-N key: n=%d != K*R=%d" % (n, k * r))
    return SqrtKey(n_keys=k, n_codewords=r, n=n,
                   keys=slots[4:4 + k].copy(),
                   cw1=slots[4 + k:4 + k + r].copy(),
                   cw2=slots[4 + k + r:].copy())


def default_split(n: int) -> tuple[int, int]:
    """Balanced power-of-two grid: K = 2^ceil(d/2), R = N / K."""
    d = n.bit_length() - 1
    k = 1 << ((d + 1) // 2)
    return k, n // k


def generate_sqrt_keys(alpha: int, n: int, seed: bytes, prf_method: int,
                       beta: int = 1, n_keys: int | None = None):
    """-> (SqrtKey server1, SqrtKey server2) with share difference
    ``v1[x] - v2[x] = beta * [x == alpha]`` mod 2^128."""
    if n & (n - 1):
        raise ValueError("n must be a power of two")
    if not 0 <= alpha < n:
        raise ValueError("alpha out of range")
    k = n_keys or default_split(n)[0]
    if n % k:
        raise ValueError("n_keys must divide n")
    r = n // k
    j_t, r_t = alpha % k, alpha // k

    rng = Shake256Drbg(seed)
    keys1 = np.zeros((k, 4), dtype=np.uint32)
    keys2 = np.zeros((k, 4), dtype=np.uint32)
    for j in range(k):
        if j == j_t:
            # uniform seed for server 1; server 2 uniform with the
            # opposite LSB — marginally both are uniform, so neither
            # server can distinguish the target column from its key
            s1_val = rng.u128()
            keys1[j] = u128.int_to_limbs(s1_val)
            keys2[j] = u128.int_to_limbs(
                (rng.u128() & ~1) | (1 ^ (s1_val & 1)))
        else:
            keys1[j] = keys2[j] = u128.int_to_limbs(rng.u128())

    prf = PRF_FUNCS[prf_method]
    s1 = u128.limbs_to_int(keys1[j_t])
    s2 = u128.limbs_to_int(keys2[j_t])
    # evaluator picks cw_{lsb(seed)}; with server 1 holding the even seed
    # the required difference is cw2-cw1 = PRF(s1)-PRF(s2)-beta*[r==r*],
    # and with roles swapped it is the negation (both servers still index
    # opposite codeword arrays, so v1-v2 flips sign along with it)
    s1_even = (s1 & 1) == 0
    cw1 = np.zeros((r, 4), dtype=np.uint32)
    cw2 = np.zeros((r, 4), dtype=np.uint32)
    for row in range(r):
        diff = (prf(s1, row) - prf(s2, row)) & MASK128
        if row == r_t:
            diff = (diff - beta) & MASK128
        if not s1_even:
            diff = (-diff) & MASK128
        c1 = rng.u128()
        cw1[row] = u128.int_to_limbs(c1)
        cw2[row] = u128.int_to_limbs((c1 + diff) & MASK128)

    args = dict(n_keys=k, n_codewords=r, n=n)
    return (SqrtKey(keys=keys1, cw1=cw1, cw2=cw2, **args),
            SqrtKey(keys=keys2, cw1=cw1, cw2=cw2, **args))


def gen_sqrt_batched(alphas, n: int, seeds=None, *, prf_method: int,
                     beta: int = 1, n_keys: int | None = None,
                     knobs=None):
    """Vectorized two-server sqrt-N keygen over B independent indices.

    The sqrt-N counterpart of ``keygen.gen_batched``: one DRBG squeeze
    per key, then ONE vectorized PRF call over the [B, R] target-column
    grid instead of ``O(B * R)`` Python-int PRF calls.  Bit-identical to
    ``generate_sqrt_keys(alphas[i], n, seeds[i])`` per key (the scalar
    generator stays the fuzz oracle).  Returns two
    [B, (4 + K + 2R) * 4] int32 wire-key arrays.

    ``knobs`` (searched, ``tune.kernel_search.keygen_search``):
    ``prf_group="stacked"`` fuses the two target-column grid calls over
    s1‖s2 into one; ``squeeze_draws`` chunks the DRBG squeeze.  Both
    bit-identical reformulations (PRF row-wise purity / byte-stream
    identity); the single-call grid has no target-path recomputation,
    so ``path_reuse`` is vacuous here.
    """
    from .keygen import _check_batch_args, drbg_u128_batch
    alphas, seeds = _check_batch_args(alphas, n, seeds)
    kn = dict(knobs or {})
    k = n_keys or default_split(n)[0]
    if n % k:
        raise ValueError("n_keys must divide n")
    r = n // k
    bsz = alphas.size
    j_t = (alphas % k).astype(np.int64)
    r_t = (alphas // k).astype(np.int64)
    # draw layout per key: k+1 column draws (the target column consumes
    # two — its server-1 seed, then server-2's opposite-LSB seed), then
    # one codeword draw per row — the exact scalar draw order
    draws = drbg_u128_batch(seeds, k + 1 + r,
                            squeeze_draws=kn.get("squeeze_draws"))
    rows_b = np.arange(bsz)
    col_idx = np.arange(k)[None, :] + (np.arange(k)[None, :] > j_t[:, None])
    keys1 = draws[rows_b[:, None], col_idx]           # [B, K, 4]
    keys2 = keys1.copy()
    s1v = keys1[rows_b, j_t]                          # [B, 4]
    d2 = draws[rows_b, j_t + 1].copy()
    d2[:, 0] = ((d2[:, 0] & np.uint32(0xFFFFFFFE))
                | (np.uint32(1) ^ (s1v[:, 0] & np.uint32(1))))
    keys2[rows_b, j_t] = d2
    s2v = d2

    from .prf import prf_v
    rows = np.arange(r, dtype=np.uint32)
    if kn.get("prf_group") == "stacked":
        both = prf_v(prf_method, np.ascontiguousarray(np.broadcast_to(
            np.stack([s1v, s2v])[:, :, None, :],
            (2, bsz, r, 4))).reshape(2 * bsz, r, 4), rows)
        p1, p2 = both[:bsz], both[bsz:]
    else:
        p1 = prf_v(prf_method,
                   np.ascontiguousarray(np.broadcast_to(
                       s1v[:, None, :], (bsz, r, 4))), rows)
        p2 = prf_v(prf_method,
                   np.ascontiguousarray(np.broadcast_to(
                       s2v[:, None, :], (bsz, r, 4))), rows)
    diff = u128.sub128(p1, p2)                        # [B, R, 4]
    beta_c = np.broadcast_to(u128.int_to_limbs(beta), (bsz, 4))
    tmask = (rows[None, :] == r_t[:, None])[..., None]
    diff = np.where(tmask, u128.sub128(diff, beta_c[:, None, :]), diff)
    s1_even = ((s1v[:, 0] & np.uint32(1)) == 0)[:, None, None]
    diff = np.where(s1_even, diff, u128.neg128(diff))
    c1 = draws[:, k + 1:]                             # [B, R, 4]
    cw1 = c1
    cw2 = u128.add128(c1, diff)

    def wire(key_seeds, cw1, cw2):
        slots = np.zeros((bsz, 4 + k + 2 * r, 4), dtype=np.uint32)
        slots[:, 0, 0] = np.uint32(k)
        slots[:, 1, 0] = np.uint32(r)
        slots[:, 2, 0] = np.uint32(n & 0xFFFFFFFF)
        slots[:, 2, 1] = np.uint32(n >> 32)
        slots[:, 4:4 + k] = key_seeds
        slots[:, 4 + k:4 + k + r] = cw1
        slots[:, 4 + k + r:] = cw2
        return slots.reshape(bsz, -1).view(np.int32)

    return wire(keys1, cw1, cw2), wire(keys2, cw1, cw2)


def _grid_vals(prf_method: int, seeds_row, r: int, xp,
               row0=np.uint32(0)):
    """PRF values over rows row0..row0+r-1 for a seed tensor broadcast
    along a leading row axis (``seeds_row``: [..., 1, K, 4]-shaped
    broadcastable maker, called with the row count to use).  ``row0``
    may be a traced uint32 scalar (the chunked scan's row offset); it
    must be a multiple of 4 whenever the caller chunks a larger grid
    (``eval_contract_batched`` enforces this via the row_chunk rules).

    Block-PRG ids (4/5): rows 4c..4c+3 are the four word groups of ONE
    core block at counter c — evaluate ceil(r/4) blocks and interleave,
    a 4x core-call saving on the sqrt-N latency path.  Other ids: one
    core per row (the generic path).
    """
    from .prf import _BLK_WORDS_JAX, _BLK_WORDS_V, _blk_group
    if prf_method not in _BLK_WORDS_V:
        rows = (xp.arange(r, dtype=xp.uint32) + row0)[:, None]
        return prf_v(prf_method, seeds_row(r), rows)
    nctr = -(-r // 4)
    ctr = (xp.arange(nctr, dtype=xp.uint32)
           + (row0 >> np.uint32(2)))[:, None]
    seeds = seeds_row(nctr)
    if isinstance(seeds, np.ndarray):
        out16 = _BLK_WORDS_V[prf_method](seeds, ctr)
    else:
        out16 = _BLK_WORDS_JAX[prf_method](seeds, ctr)
    groups = xp.stack([_blk_group(out16, 4 * g) for g in range(4)],
                      axis=-3)                        # [.., C, 4, K, 4]
    flat = groups.reshape(groups.shape[:-4] + (4 * nctr,)
                          + groups.shape[-2:])
    return flat[..., :r, :, :]


def eval_grid(key: SqrtKey, prf_method: int, xp=np):
    """Full one-hot share, natural order: [N] int32 (low 32 bits).

    One vectorized PRF call over the [R, K] grid — seeds broadcast along
    rows, positions along columns — then LSB-select of the codeword row.
    """
    k, r = key.n_keys, key.n_codewords
    keys = xp.asarray(key.keys)                       # [K, 4]
    vals = _grid_vals(
        prf_method,
        lambda nr: xp.broadcast_to(keys[None, :, :], (nr, k, 4)),
        r, xp)                                        # [R, K, 4]
    sel = (keys[None, :, 0] & np.uint32(1))[..., None]
    cw = xp.where(sel.astype(bool), xp.asarray(key.cw2)[:, None, :],
                  xp.asarray(key.cw1)[:, None, :])    # [R, K, 4]
    out = u128.add128(vals, cw)
    return out[..., 0].astype(xp.int32).reshape(-1)   # x = r*K + j


def eval_contract(keys: list, prf_method: int, table: np.ndarray):
    """Batched fused evaluation on device: [B, E] int32 shares.

    table is the *natural-order* [N, E] int32 table (no bit-reversal —
    the grid emits natural order).  Exact mod-2^32 contraction.
    """
    import jax.numpy as jnp

    shares = jnp.stack([eval_grid(kk, prf_method, jnp) for kk in keys])
    from ..ops import matmul128
    return matmul128.dot(shares, jnp.asarray(table))


def pack_sqrt_keys(keys: list) -> tuple:
    """List of SqrtKey (uniform K, R) -> (seeds [B,K,4], cw1 [B,R,4],
    cw2 [B,R,4]) uint32 arrays for the batched device path."""
    k, r = keys[0].n_keys, keys[0].n_codewords
    bsz = len(keys)
    seeds = np.zeros((bsz, k, 4), dtype=np.uint32)
    cw1 = np.zeros((bsz, r, 4), dtype=np.uint32)
    cw2 = np.zeros((bsz, r, 4), dtype=np.uint32)
    for i, kk in enumerate(keys):
        if (kk.n_keys, kk.n_codewords) != (k, r):
            raise ValueError("keys for mixed sqrt-N splits")
        seeds[i] = kk.keys
        cw1[i] = kk.cw1
        cw2[i] = kk.cw2
    return seeds, cw1, cw2


# ------------------------------------------------------ packed-batch codec

@dataclass
class PackedSqrtKeys:
    """A sqrt-N key batch decoded straight into device-layout arrays —
    the scheme's counterpart of ``keygen.PackedKeys``, with the same
    ``batch``/``slice``/``pad_to`` surface so the serving engine's
    bucket logic stays scheme-agnostic."""
    seeds: np.ndarray    # [B, K, 4] uint32 column seeds
    cw1: np.ndarray      # [B, R, 4] uint32
    cw2: np.ndarray      # [B, R, 4] uint32
    n: int               # shared table size (N = K * R)

    @property
    def n_keys(self) -> int:
        return self.seeds.shape[1]

    @property
    def n_codewords(self) -> int:
        return self.cw1.shape[1]

    @property
    def batch(self) -> int:
        return self.seeds.shape[0]

    def slice(self, lo: int, hi: int) -> "PackedSqrtKeys":
        return PackedSqrtKeys(self.seeds[lo:hi], self.cw1[lo:hi],
                              self.cw2[lo:hi], self.n)

    def pad_to(self, size: int) -> "PackedSqrtKeys":
        """Pad the batch axis to ``size`` by repeating the last key (the
        same padding rule the logn paths use; pad rows are computed and
        discarded).  No-op when already at least ``size``."""
        reps = size - self.batch
        if reps <= 0:
            return self
        return PackedSqrtKeys(
            np.concatenate([self.seeds,
                            np.repeat(self.seeds[-1:], reps, 0)]),
            np.concatenate([self.cw1, np.repeat(self.cw1[-1:], reps, 0)]),
            np.concatenate([self.cw2, np.repeat(self.cw2[-1:], reps, 0)]),
            self.n)


def stack_sqrt_wire_keys(keys) -> np.ndarray:
    """Key batch (list of flat int32 array-likes, torch tensors
    included, or one [B, W] array) -> one contiguous [B, W] int32
    buffer (``keygen.stack_wire_keys`` with the width check lifted —
    sqrt keys are O(sqrt N)-sized).  Ragged wire lengths can only come
    from mixed splits and are rejected as such."""
    from .keygen import stack_wire_keys
    if len(keys) == 0:
        raise ValueError("empty key batch")
    try:
        return stack_wire_keys(keys, words=None)
    except ValueError:
        raise ValueError("keys for mixed sqrt-N splits") from None


def sqrt_wire_ns(arr: np.ndarray) -> np.ndarray:
    """Per-key table size n from a stacked [B, W] sqrt-N wire buffer
    (header slot 2, limbs 0/1), with the width sanity check a header
    read needs.  The one wire-header reader outside the decoder —
    exported so batch callers can attribute a wrong-domain key to its
    batch position before the full decode."""
    if arr.shape[1] % 4 or arr.shape[1] < 16:
        raise ValueError("malformed sqrt-N key: %d int32 words"
                         % arr.shape[1])
    slots = arr.view(np.uint32).reshape(arr.shape[0], -1, 4)
    return (slots[:, 2, 0].astype(np.int64)
            | (slots[:, 2, 1].astype(np.int64) << 32))


def decode_sqrt_keys_batched(keys) -> PackedSqrtKeys:
    """Vectorized wire -> packed-arrays codec for a uniform sqrt-N key
    batch.

    Replaces the per-key ``deserialize_sqrt_key`` + ``pack_sqrt_keys``
    host loop on the hot path: the wire words are stacked once and every
    seed/codeword limb is decoded with views and reshapes — O(1) Python
    ops after the stack.  Bit-identical to the scalar codec (asserted in
    tests/test_key_codec.py), which stays the tested oracle.
    """
    arr = stack_sqrt_wire_keys(keys)
    if arr.shape[1] % 4 or arr.shape[1] < 8:
        raise ValueError("malformed sqrt-N key: %d int32 words"
                         % arr.shape[1])
    slots = arr.view(np.uint32).reshape(arr.shape[0], -1, 4)
    k = int(slots[0, 0, 0])
    r = int(slots[0, 1, 0])
    if ((slots[:, 0, 0] != np.uint32(k)).any()
            or (slots[:, 1, 0] != np.uint32(r)).any()):
        raise ValueError("keys for mixed sqrt-N splits")
    if slots.shape[1] != 4 + k + 2 * r:
        raise ValueError("malformed sqrt-N key: %d slots for K=%d R=%d"
                         % (slots.shape[1], k, r))
    # n <= 2^32 spills into limb 1; limbs 2/3 are zero on every writer
    n = (slots[:, 2, 0].astype(np.uint64)
         | (slots[:, 2, 1].astype(np.uint64) << np.uint64(32)))
    if (n != n[0]).any():
        raise ValueError("keys for mixed table sizes")
    if slots[:, 2, 2:].any() or k * r != int(n[0]):
        raise ValueError("malformed sqrt-N key: n=%d != K*R=%d"
                         % (int(n[0]), k * r))
    # seeds/cw1/cw2 are VIEWS into the one stacked buffer: sqrt keys are
    # O(sqrt N)-big, so a host-side compaction copy would rival the
    # decode itself — and the device transfer re-lays the bytes anyway
    return PackedSqrtKeys(
        seeds=slots[:, 4:4 + k],
        cw1=slots[:, 4 + k:4 + k + r],
        cw2=slots[:, 4 + k + r:],
        n=int(n[0]))


# -------------------------------------------------- chunked fused eval

ROW_CHUNK_FLOOR = 4  # the block-PRG 4-row interleave quantum


def row_chunk_within_bound(rc: int, k: int, batch: int) -> bool:
    """True when a [B, rc, K, 4] PRF slab fits the 64 MiB live-seed
    budget shared with the logn paths (``expand.CHUNK_SEED_BYTES_BOUND``;
    the 4-row floor is always allowed)."""
    return rc <= ROW_CHUNK_FLOOR or rc * k * 16 * max(1, batch) <= \
        CHUNK_SEED_BYTES_BOUND


def choose_row_chunk(r: int, k: int, batch: int) -> int:
    """Grid rows PRF-expanded per scan step: bound the live
    [B, rc, K, 4] slab at 64 MiB (at N=2^20, B=512 the full grid would
    be ~8 GiB).  Always a power-of-two multiple of 4 dividing R — the
    block-PRG ids interleave 4 rows per core block — or R itself when R
    is too small (or odd-shaped) to chunk."""
    if r <= ROW_CHUNK_FLOOR or r % ROW_CHUNK_FLOOR:
        return r
    target = max(ROW_CHUNK_FLOOR,
                 CHUNK_SEED_BYTES_BOUND // (16 * k * max(1, batch)))
    rc = ROW_CHUNK_FLOOR
    while rc * 2 <= target and r % (rc * 2) == 0 and rc * 2 <= r:
        rc *= 2
    return min(rc, r)


def clamp_row_chunk(rc, r: int, k: int, batch: int) -> int:
    """Harden a possibly-tuned ``row_chunk`` against the actual key
    split and the live-slab budget: tuned entries key on the table
    shape, not the split, and a nearest-batch fallback can pair a
    small-batch chunk with a bigger batch.  Falsy or invalid values fall
    back to the heuristic."""
    if (not rc or r % int(rc)
            or (int(rc) < r and int(rc) % ROW_CHUNK_FLOOR)
            or not row_chunk_within_bound(int(rc), k, batch)):
        return choose_row_chunk(r, k, batch)
    return int(rc)


def sqrt_chunk_candidates(r: int, k: int, batch: int, span: int = 2) -> list:
    """``row_chunk`` candidates for the autotuner: powers-of-two
    multiples of 4 within ``span`` octaves of the ``choose_row_chunk``
    heuristic, each dividing R and honoring the live-slab bound
    (candidates above it are dropped, not clipped).  The heuristic
    itself is always a member, so a tuned config can never regress the
    static default's memory envelope.  Sorted ascending."""
    base = choose_row_chunk(r, k, batch)
    out = {base}
    for s in range(-span, span + 1):
        c = base << s if s >= 0 else base >> (-s)
        if (ROW_CHUNK_FLOOR <= c <= r and r % c == 0
                and row_chunk_within_bound(c, k, batch)):
            out.add(c)
    return sorted(out)


@functools.partial(jax.jit, static_argnames=("prf_method", "dot_impl",
                                             "row_chunk"))
def _eval_contract_batched_jit(seeds, cw1, cw2, table, *, prf_method,
                               dot_impl, row_chunk):
    from ..ops import matmul128

    bsz, k, _ = seeds.shape
    r = cw1.shape[1]
    e = table.shape[1]
    rc = row_chunk
    steps = r // rc
    sel = (seeds[:, None, :, 0] & np.uint32(1)).astype(bool)[..., None]

    def slab(row0, c1, c2):
        """One [B, rc, K] grid chunk -> [B, rc*K] int32 leaf shares."""
        vals = _grid_vals(
            prf_method,
            lambda nr: jnp.broadcast_to(seeds[:, None, :, :],
                                        (bsz, nr, k, 4)),
            rc, jnp, row0=row0)                       # [B, rc, K, 4]
        cw = jnp.where(sel, c2[:, :, None, :], c1[:, :, None, :])
        out = u128.add128(vals, cw)
        return out[..., 0].astype(jnp.int32).reshape(bsz, rc * k)

    if steps == 1:  # grid fits the budget — no scan machinery at all
        return matmul128.dot(slab(np.uint32(0), cw1, cw2), table, dot_impl)

    def body(acc, inp):
        row0, c1, c2, tbl = inp
        # int32 adds wrap, so accumulating per-chunk partial dots stays
        # exact mod 2^32
        return acc + matmul128.dot(slab(row0, c1, c2), tbl, dot_impl), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((bsz, e), jnp.int32),
        (jnp.arange(steps, dtype=jnp.uint32) * jnp.uint32(rc),
         jnp.moveaxis(cw1.reshape(bsz, steps, rc, 4), 1, 0),
         jnp.moveaxis(cw2.reshape(bsz, steps, rc, 4), 1, 0),
         table.reshape(steps, rc * k, e)))
    return acc


def _resolve_row_chunk(r: int, k: int, bsz: int,
                       row_chunk: int | None) -> int:
    """The one row_chunk policy for the fused sqrt-N entry points:
    None -> the ``choose_row_chunk`` heuristic; explicit values must
    divide R and — when actually chunking — be a multiple of
    ``ROW_CHUNK_FLOOR`` so the block-PRG 4-row interleave in
    ``_grid_vals`` stays intact."""
    if row_chunk is None:
        row_chunk = choose_row_chunk(r, k, bsz)
    row_chunk = int(row_chunk)
    if row_chunk < 1 or r % row_chunk:
        raise ValueError("row_chunk (%d) must divide R=%d"
                         % (row_chunk, r))
    if row_chunk < r and row_chunk % ROW_CHUNK_FLOOR:
        raise ValueError(
            "row_chunk (%d) must be a multiple of 4 when chunking (the "
            "block-PRG ids interleave 4 rows per core block)" % row_chunk)
    return row_chunk


def eval_contract_batched(seeds, cw1, cw2, table, *, prf_method: int,
                          dot_impl: str = "i32",
                          row_chunk: int | None = None,
                          kernel_impl: str | None = "xla",
                          kernel_variant=None):
    """Fused batched sqrt-N evaluation: one device program for the whole
    batch — row-chunked [B, rc, K] PRF grid slabs scanned over the R
    rows, LSB codeword select, 128-bit add, exact mod-2^32 contraction
    against the matching natural-order table rows, accumulated [B, E].

    ``row_chunk`` rows are PRF-expanded per scan step (None = the
    ``choose_row_chunk`` heuristic), bounding live grid memory at
    ``expand.CHUNK_SEED_BYTES_BOUND`` instead of the full
    ``B x N x 16`` bytes; it must divide R and — when actually chunking
    — be a multiple of 4, so the block-PRG 4-row interleave in
    ``_grid_vals`` stays intact.

    ``kernel_impl`` picks the program: ``"xla"`` (default) is the scan
    path below — kept verbatim as the bit-exactness oracle — and
    ``"pallas"`` routes to the fused VMEM-resident grid kernel
    (``ops/pallas_sqrt.py``; ``row_chunk`` then obeys the kernel's
    VMEM cell cap and ``dot_impl`` is moot — the in-kernel contraction
    is the exact int32 dot).  This layer does NOT probe availability:
    ``api.resolved_eval_knobs`` gates and degrades with provenance,
    mirroring the logn ``expand_and_contract`` split.

    This is the production sqrt-N path (``eval_contract`` keeps the
    per-key stacking for reference use): no level loop, no permutation —
    the latency-friendly construction for mid-sized tables (the role the
    reference's coop kernel plays for single queries,
    ``dpf_gpu/dpf_coop.cu:3-9``).

    ``kernel_variant`` (pallas only) is a searched structural variant —
    a dict of ``ops.pallas_sqrt`` launcher keywords (``tb``,
    ``max_cells``, ``grid_order``, ``dim_semantics``, ``limbs``,
    ``cw_add``) as produced by ``tune/kernel_search.py``; every variant
    is bit-identical to the scan oracle, so this only changes the
    schedule, never the answer.  Ignored on the xla path (its searched
    fields, ``row_chunk``/``dot_impl``, are native arguments here).
    """
    if (kernel_impl or "xla") == "pallas":
        from ..ops import pallas_sqrt
        kv = {k: v for k, v in dict(kernel_variant or {}).items()
              if k in pallas_sqrt._VARIANT_FIELDS and v is not None}
        return pallas_sqrt.sqrt_grid_contract_pallas(
            seeds, cw1, cw2, table, prf_method=prf_method,
            row_chunk=row_chunk, **kv)
    bsz, k = seeds.shape[0], seeds.shape[1]
    r = cw1.shape[1]
    row_chunk = _resolve_row_chunk(r, k, bsz, row_chunk)
    return _eval_contract_batched_jit(
        jnp.asarray(seeds), jnp.asarray(cw1), jnp.asarray(cw2), table,
        prf_method=prf_method, dot_impl=dot_impl, row_chunk=row_chunk)


@functools.partial(jax.jit, static_argnames=("prf_method", "dot_impl",
                                             "row_chunk"))
def _eval_contract_pkt_jit(seeds, cw1, cw2, tables, *, prf_method,
                           dot_impl, row_chunk):
    from ..ops import matmul128

    bsz, k, _ = seeds.shape
    r = cw1.shape[1]
    e = tables.shape[-1]
    rc = row_chunk
    steps = r // rc
    sel = (seeds[:, None, :, 0] & np.uint32(1)).astype(bool)[..., None]

    def slab(row0, c1, c2):
        """One [B, rc, K] grid chunk -> [B, rc*K] int32 leaf shares."""
        vals = _grid_vals(
            prf_method,
            lambda nr: jnp.broadcast_to(seeds[:, None, :, :],
                                        (bsz, nr, k, 4)),
            rc, jnp, row0=row0)                       # [B, rc, K, 4]
        cw = jnp.where(sel, c2[:, :, None, :], c1[:, :, None, :])
        out = u128.add128(vals, cw)
        return out[..., 0].astype(jnp.int32).reshape(bsz, rc * k)

    def bdot(leaves, chunk):
        # [B, C] x [B, C, E] -> [B, E], batched over keys, mod 2^32
        if (dot_impl or "i32") == "i32":
            return jax.lax.dot_general(
                leaves, chunk, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32)
        return jax.vmap(lambda a, t: matmul128.dot(a[None, :], t,
                                                   dot_impl)[0])(leaves,
                                                                 chunk)

    if steps == 1:  # grid fits the budget — no scan machinery at all
        return bdot(slab(np.uint32(0), cw1, cw2), tables)

    def body(acc, inp):
        row0, c1, c2, tbl = inp
        return acc + bdot(slab(row0, c1, c2), tbl), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((bsz, e), jnp.int32),
        (jnp.arange(steps, dtype=jnp.uint32) * jnp.uint32(rc),
         jnp.moveaxis(cw1.reshape(bsz, steps, rc, 4), 1, 0),
         jnp.moveaxis(cw2.reshape(bsz, steps, rc, 4), 1, 0),
         jnp.moveaxis(tables.reshape(bsz, steps, rc * k, e), 1, 0)))
    return acc


def eval_contract_per_key_tables(seeds, cw1, cw2, tables, *,
                                 prf_method: int, dot_impl: str = "i32",
                                 row_chunk: int | None = None):
    """Fused batched sqrt-N evaluation where every key has its OWN table.

    tables: [B, N, E] int32 in NATURAL order (the grid emits natural
    order — no permutation, unlike the logn per-key-tables paths).
    Returns [B, E] int32: ``out[b] = sum_x leaf32[b, x] * tables[b, x]``
    mod 2^32.  This is the sqrt-N construction's batch-PIR surface (one
    device dispatch answers one query round across all equal-sized
    bins), mirroring ``expand.expand_and_contract_per_key_tables``;
    ``row_chunk`` follows the same rules as ``eval_contract_batched``.
    """
    bsz, k = seeds.shape[0], seeds.shape[1]
    r = cw1.shape[1]
    row_chunk = _resolve_row_chunk(r, k, bsz, row_chunk)
    return _eval_contract_pkt_jit(
        jnp.asarray(seeds), jnp.asarray(cw1), jnp.asarray(cw2),
        jnp.asarray(tables), prf_method=prf_method, dot_impl=dot_impl,
        row_chunk=row_chunk)


# ----------------------------------------------------- mesh-sharded eval

@functools.partial(jax.jit, static_argnames=("prf_method", "dot_impl",
                                             "row_chunk", "psum_group",
                                             "mesh", "kernel_impl"))
def _eval_sharded_sqrt_jit(seeds, cw1, cw2, table, *, prf_method,
                           dot_impl, row_chunk, psum_group, mesh,
                           kernel_impl="xla"):
    from jax.sharding import PartitionSpec as P

    from ..ops import matmul128
    from .expand import _pvary, _scan_psum_groups, _valid_psum_group

    n_shards = mesh.shape["table"]
    k = seeds.shape[1]
    r = cw1.shape[1]
    e = table.shape[-1]
    r_local = r // n_shards
    rc = row_chunk
    steps = r_local // rc

    def per_shard(seeds_l, cw1_l, cw2_l, tbl):
        # seeds_l/cw*_l: this batch-shard's keys (codewords replicated
        # over "table"); tbl: [r_local * K, E] — this chip's grid rows
        bsz = seeds_l.shape[0]
        shard_ix = jax.lax.axis_index("table")
        row0_base = shard_ix.astype(jnp.uint32) * jnp.uint32(r_local)
        c1 = jax.lax.dynamic_slice_in_dim(cw1_l, shard_ix * r_local,
                                          r_local, axis=1)
        c2 = jax.lax.dynamic_slice_in_dim(cw2_l, shard_ix * r_local,
                                          r_local, axis=1)
        if (kernel_impl or "xla") == "pallas":
            # the fused grid kernel accumulates its own row tiles in
            # VMEM with the TRACED per-shard row base, so the local
            # scan (and psum_group pipelining) collapses to one kernel
            # dispatch + one terminal psum
            from ..ops import pallas_sqrt
            return jax.lax.psum(
                pallas_sqrt._sqrt_grid_contract_impl(
                    seeds_l, c1, c2, tbl, row0_base,
                    prf_method=prf_method, row_chunk=rc), "table")
        sel = (seeds_l[:, None, :, 0] & np.uint32(1)).astype(bool)[..., None]

        def contract(row0, c1_c, c2_c, tc):
            """One [B, rc, K] grid chunk against its table rows."""
            vals = _grid_vals(
                prf_method,
                lambda nr: jnp.broadcast_to(seeds_l[:, None, :, :],
                                            (bsz, nr, k, 4)),
                rc, jnp, row0=row0)                   # [B, rc, K, 4]
            cw = jnp.where(sel, c2_c[:, :, None, :], c1_c[:, :, None, :])
            leaves = u128.add128(vals, cw)[..., 0].astype(
                jnp.int32).reshape(bsz, rc * k)
            return matmul128.dot(leaves, tc, dot_impl)

        tbl_chunks = tbl.reshape(steps, rc * k, e)
        if steps == 1:
            return jax.lax.psum(contract(row0_base, c1, c2,
                                         tbl_chunks[0]), "table")
        row0s = row0_base + jnp.arange(steps, dtype=jnp.uint32) \
            * jnp.uint32(rc)
        c1s = jnp.moveaxis(c1.reshape(bsz, steps, rc, 4), 1, 0)
        c2s = jnp.moveaxis(c2.reshape(bsz, steps, rc, 4), 1, 0)

        def body(acc, inp):
            return acc + contract(*inp), None

        zeros = jnp.zeros((bsz, e), jnp.int32)
        g = _valid_psum_group(psum_group, steps)
        if not g:  # one terminal psum after the local accumulation
            acc, _ = jax.lax.scan(body, _pvary(zeros, ("batch", "table")),
                                  (row0s, c1s, c2s, tbl_chunks))
            return jax.lax.psum(acc, "table")
        n_groups = steps // g
        return _scan_psum_groups(body, zeros, (
            row0s.reshape(n_groups, g),
            c1s.reshape(n_groups, g, bsz, rc, 4),
            c2s.reshape(n_groups, g, bsz, rc, 4),
            tbl_chunks.reshape(n_groups, g, rc * k, e)), "table")

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P("batch"), P("batch"), P("batch"),
                  # pallas may get the table's [4, N, E] digit planes
                  P(*(None,) * (table.ndim - 2), "table", None)),
        out_specs=P("batch", None),
        # a pallas_call's out_shape carries no mesh-axis typing
        check_vma=(kernel_impl or "xla") != "pallas")
    return fn(seeds, cw1, cw2, table)


def eval_sharded_sqrt(seeds, cw1, cw2, table, *, prf_method: int,
                      mesh, dot_impl: str = "i32",
                      row_chunk: int | None = None,
                      psum_group: int | None = None,
                      kernel_impl: str | None = "xla"):
    """Mesh-parallel fused sqrt-N evaluation: the [R, K] grid row-sharded
    over the "table" mesh axis, keys over "batch".

    ``table`` is the NATURAL-order [N, E] int32 table sharded
    ``P("table", None)`` (``parallel.sharded.shard_table_sqrt``) — grid
    row ``r`` is table rows ``[r*K, (r+1)*K)``, so a contiguous
    N/shards row block is exactly R/shards whole grid rows and the
    sharding is key-split agnostic.  Each chip PRF-expands ONLY its own
    grid rows in ``row_chunk``-row slabs (the per-shard counterpart of
    ``eval_contract_batched``'s scan, same 64 MiB live-slab bound),
    contracts locally, and partial [B, E] contractions are summed with
    ``psum`` — int32 adds wrap, so the result is bit-identical to the
    single-device oracle.

    ``row_chunk`` rows are expanded per scan step PER SHARD (None = the
    ``choose_row_chunk`` heuristic over R/shards); it must divide
    R/shards and — when actually chunking — be a multiple of 4.
    ``psum_group`` = scan steps accumulated locally between psums
    (0/None = one terminal psum): smaller groups start collectives
    earlier so ICI latency overlaps the next chunk's PRF expansion.
    ``kernel_impl="pallas"`` swaps each shard's local scan for the
    fused VMEM-resident grid kernel (``ops/pallas_sqrt.py``) with this
    shard's traced ``row0`` base; the kernel accumulates its own row
    tiles, so ``psum_group`` is moot (one terminal psum) and
    ``row_chunk`` additionally obeys the kernel's VMEM cell cap.
    Availability is the CALLER's job (``api.resolved_eval_knobs`` /
    ``ShardedDPFServer.resolved_eval_knobs`` degrade with provenance);
    an unsupported shape here raises.
    Returns [B, E] int32, sharded over "batch", replicated over "table".
    """
    bsz, k = seeds.shape[0], seeds.shape[1]
    r = cw1.shape[1]
    n_shards = mesh.shape["table"]
    if r % n_shards:
        raise ValueError(
            "sqrt-N grid rows R=%d must divide over %d table shards"
            % (r, n_shards))
    r_local = r // n_shards
    from .prf import _BLK_WORDS_V
    if n_shards > 1 and prf_method in _BLK_WORDS_V \
            and r_local % ROW_CHUNK_FLOOR:
        raise ValueError(
            "block-PRG sqrt-N sharding needs R/shards (%d) to be a "
            "multiple of 4 (the 4-row core-block interleave must not "
            "straddle a shard boundary) — use fewer table shards or a "
            "wider n_keys split" % r_local)
    row_chunk = _resolve_row_chunk(r_local, k, bsz, row_chunk)
    if (kernel_impl or "xla") == "pallas":
        from ..ops.pallas_sqrt import pallas_sqrt_unsupported
        reason = pallas_sqrt_unsupported(prf_method, r_local)
        if reason:
            raise ValueError(reason)
    return _eval_sharded_sqrt_jit(
        jnp.asarray(seeds), jnp.asarray(cw1), jnp.asarray(cw2), table,
        prf_method=prf_method, dot_impl=dot_impl, row_chunk=row_chunk,
        psum_group=int(psum_group or 0), mesh=mesh,
        kernel_impl=(kernel_impl or "xla"))


# ------------------------------------------------------ point evaluation

def eval_points_sqrt_scalar(keys: list, indices, prf_method: int):
    """Scalar per-(key, index) loop — the tests' parity oracle for
    ``eval_points_sqrt`` (kept off the hot path on purpose)."""
    idx = np.asarray(indices, dtype=np.int64)
    out = np.zeros((len(keys), idx.size), dtype=np.int32)
    prf = PRF_FUNCS[prf_method]
    for i, kk in enumerate(keys):
        for q, x in enumerate(idx):
            r_i, j = divmod(int(x), kk.n_keys)
            s = u128.limbs_to_int(kk.keys[j])
            cw = kk.cw2[r_i] if (s & 1) else kk.cw1[r_i]
            v = (prf(s, r_i) + u128.limbs_to_int(cw)) & MASK128
            out[i, q] = np.int64(v & 0xFFFFFFFF).astype(np.int32)
    return out


def eval_points_sqrt(keys: list, indices, prf_method: int):
    """Sparse evaluation at the given indices: [B, Q] int32 shares.

    Index x = r*K + j costs ONE PRF call (seed j at row r) — the sqrt-N
    scheme's native strength; no tree walk at all.  The whole [B, Q]
    query block runs as a single vectorized PRF call over the gathered
    (seed, row) pairs (``eval_points_sqrt_scalar`` is the scalar
    oracle)."""
    idx = np.asarray(indices, dtype=np.int64).reshape(-1)
    seeds, cw1, cw2 = pack_sqrt_keys(keys)
    k = keys[0].n_keys
    rows = (idx // k).astype(np.uint32)               # [Q]
    sel_seeds = seeds[:, idx % k]                     # [B, Q, 4]
    vals = prf_v(prf_method, sel_seeds, rows)         # rows broadcast
    lsb = (sel_seeds[..., 0] & np.uint32(1)).astype(bool)[..., None]
    cw = np.where(lsb, cw2[:, rows], cw1[:, rows])    # [B, Q, 4]
    return u128.add128(vals, cw)[..., 0].astype(np.int32)
