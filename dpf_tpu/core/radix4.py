"""Radix-4 (mixed-radix) GGM DPF — a TPU-native construction.

The wire-compatible binary construction (``core/keygen.py``, matching the
reference's ``dpf_base/dpf.h:403-464``) expands one bit of the index per
level: ``2N`` child PRF evaluations and ``log2 N`` level round trips.
Nothing about the seed-LSB control-bit scheme requires arity 2, and on TPU
a wider fan-out is strictly better:

* **Total PRF children drop from 2N to 4N/3** (nodes ``(N-1)/3`` instead
  of ``N-1``; 4 children each).
* **AES amortizes twice as well**: the bitsliced step fuses all children
  of a node with the key schedule into ONE S-box circuit pass —
  ``16*4 + 4 = 68`` byte positions per radix-4 node vs ``36`` per binary
  node, i.e. ~0.63x the S-box work per leaf
  (``aes_bitsliced.aes128_multi_bitsliced``).
* **Half the levels**: half the codeword adds, half the inter-level HBM
  carries in the scan path, half the per-level programs in dispatch mode.

Construction (generalizing ``keygen.generate_keys`` branch-for-branch):
each level consumes one radix-``a`` digit of alpha (LSB-first); a level
owns ``a`` codeword slots per server view; an evaluator picks the cw1 vs
cw2 array by the LSB of its current seed.  On-path seeds differ by an odd
beta so LSBs differ; off-path seeds are equal and contributions cancel —
the same invariant as the binary scheme, with the per-branch codeword
algebra repeated over 4 branches.  Odd depths take one binary base level
followed by radix-4 levels (``arities(n)``).

Keys are NOT wire-compatible with the reference (which has no such
construction); they reuse the same 524-int32 container with a radix
marker in slot 0 limb 1 (binary keys keep 0 there), and the codeword
footprint is identical: ``sum(arities) = 2 log2 N <= 64`` slots.

Leaves emerge in digit-reversed BFS order; ``mixed_reverse_indices``
gives the table permutation (the binary case reduces to bit reversal,
``dpf_wrapper.cu:104-109``).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import u128
from .keygen import KEY_WORDS, Shake256Drbg
from .prf_ref import MASK128, PRF_FUNCS

MAX_CW = 64


def arities(n: int) -> tuple[int, ...]:
    """Eval-order level arities for table size n: a binary base level iff
    depth is odd, then radix-4 all the way up."""
    depth = n.bit_length() - 1
    out = (2,) if depth % 2 else ()
    return out + (4,) * (depth // 2)


def cw_offsets(ars) -> list:
    """Slot offset of each level's codeword block (eval order)."""
    offs, o = [], 0
    for a in ars:
        offs.append(o)
        o += a
    return offs


def mixed_reverse_indices(ars) -> np.ndarray:
    """perm[bfs_pos] = alpha landing there under breadth-first expansion.

    Eval consumes digits LSB-first, so BFS position has alpha's digits
    most-significant-first: reversing mixed-radix digits.  All-2 arities
    reduce to classic bit reversal.
    """
    n = int(np.prod(ars))
    rem = np.arange(n, dtype=np.int64)
    alpha = np.zeros(n, dtype=np.int64)
    block = n
    mult = 1
    for a in ars:
        block //= a
        d, rem = np.divmod(rem, block)
        alpha += d * mult
        mult *= a
    return alpha


@dataclass
class MixedKey:
    """One server's mixed-radix DPF key (host representation)."""
    arities: tuple       # eval order; level j consumes digit j (LSB-first)
    cw1: np.ndarray      # [64, 4] uint32 (slots beyond sum(arities) zero)
    cw2: np.ndarray      # [64, 4] uint32
    last_key: int        # 128-bit start seed
    n: int

    def serialize(self) -> np.ndarray:
        """-> [524] int32: binary-key container + radix marker.

        Slot 0 = (depth, radix marker 4, n_binary_levels, 0); the rest of
        the layout mirrors ``keygen.FlatKey.serialize`` with codeword
        blocks at ``cw_offsets`` (eval order) instead of the binary
        ``2i + b`` scheme.
        """
        depth = self.n.bit_length() - 1
        slots = np.zeros((131, 4), dtype=np.uint32)
        slots[0, 0] = depth
        slots[0, 1] = 4
        slots[0, 2] = sum(1 for a in self.arities if a == 2)
        slots[1:65] = self.cw1
        slots[65:129] = self.cw2
        slots[129] = u128.int_to_limbs(self.last_key)
        slots[130] = u128.int_to_limbs(self.n)
        return slots.reshape(-1).view(np.int32).copy()


def is_mixed_key(arr) -> bool:
    """True if a 524-word key carries the radix marker."""
    a = np.asarray(arr, dtype=np.int32).reshape(-1)
    return a.shape[0] == KEY_WORDS and a.view(np.uint32)[1] == 4


def deserialize_mixed_key(arr) -> MixedKey:
    a = np.asarray(arr, dtype=np.int32).reshape(-1)
    if a.shape[0] != KEY_WORDS:
        raise ValueError("mixed-radix key must be %d int32 words, got %d"
                         % (KEY_WORDS, a.shape[0]))
    slots = a.view(np.uint32).reshape(131, 4)
    if slots[0, 1] != 4:
        raise ValueError("not a mixed-radix key (marker %d)"
                         % int(slots[0, 1]))
    n = u128.limbs_to_int(slots[130])
    ars = arities(n)
    if (int(slots[0, 0]) != n.bit_length() - 1
            or int(slots[0, 2]) != sum(1 for x in ars if x == 2)):
        raise ValueError("mixed-radix key header inconsistent with n=%d" % n)
    return MixedKey(arities=ars, cw1=slots[1:65].copy(),
                    cw2=slots[65:129].copy(),
                    last_key=u128.limbs_to_int(slots[129]), n=n)


def decode_mixed_keys_batched(keys):
    """Vectorized wire -> packed-arrays codec for a radix-4 key batch.

    The mixed-radix counterpart of ``keygen.decode_keys_batched``:
    replaces the per-key ``deserialize_mixed_key`` + ``pack_mixed_keys``
    host loop with one stacked buffer and view/reshape decoding.
    Returns a ``keygen.PackedKeys`` (cw slots are eval-order blocks at
    ``cw_offsets`` rather than the binary ``2i + b`` scheme — the packed
    array layout is identical either way).
    """
    from .keygen import PackedKeys, stack_wire_keys
    slots = stack_wire_keys(keys).view(np.uint32).reshape(-1, 131, 4)
    if (slots[:, 0, 1] != 4).any():
        bad = int(np.argmax(slots[:, 0, 1] != 4))
        raise ValueError("not a mixed-radix key (marker %d)"
                         % int(slots[bad, 0, 1]))
    n = (slots[:, 130, 0].astype(np.uint64)
         | (slots[:, 130, 1].astype(np.uint64) << np.uint64(32)))
    if (n != n[0]).any():
        raise ValueError("keys for mixed table sizes")
    n0 = int(n[0])
    ars = arities(n0)
    depth = n0.bit_length() - 1
    n_bin = sum(1 for x in ars if x == 2)
    if ((slots[:, 0, 0] != depth) | (slots[:, 0, 2] != n_bin)).any():
        raise ValueError("mixed-radix key header inconsistent with n=%d"
                         % n0)
    return PackedKeys(
        cw1=np.ascontiguousarray(slots[:, 1:65]),
        cw2=np.ascontiguousarray(slots[:, 65:129]),
        last=np.ascontiguousarray(slots[:, 129]),
        depth=depth, n=n0)


def generate_keys_r4(alpha: int, n: int, seed: bytes, prf_method: int,
                     beta: int = 1):
    """Two servers' mixed-radix keys for f(alpha) = beta (mod 2^128).

    Same bottom-up derivation as ``keygen.generate_keys`` with the branch
    loop widened per level arity.  O(log N) PRF calls, host side.
    """
    if n & (n - 1) != 0 or n < 2:
        raise ValueError("table size (%d) must be a power of two >= 2" % n)
    if not 0 <= alpha < n:
        raise ValueError("alpha (%d) must be in [0, %d)" % (alpha, n))
    if n.bit_length() - 1 > 32:  # sum(arities) = 2*depth must fit MAX_CW
        raise ValueError("table size 2^%d exceeds max 2^32"
                         % (n.bit_length() - 1))
    ars = arities(n)
    offs = cw_offsets(ars)
    levels = len(ars)
    prf = PRF_FUNCS[prf_method]
    rng = Shake256Drbg(seed)

    cw1 = np.zeros((MAX_CW, 4), dtype=np.uint32)
    cw2 = np.zeros((MAX_CW, 4), dtype=np.uint32)

    digits = []
    rem = alpha
    for a in ars:
        digits.append(rem % a)
        rem //= a

    # --- base level (eval step 0) ---------------------------------------
    a0 = ars[0]
    k1 = rng.u128() & ~1          # server 0 start seed: LSB 0
    k2 = rng.u128() | 1           # server 1 start seed: LSB 1
    beta_l = beta if levels == 1 else rng.u128_odd()
    tb = digits[0]
    c1 = [rng.u128() for _ in range(a0)]
    for b in range(a0):
        d = (prf(k1, b) - prf(k2, b)) & MASK128
        if b == tb:
            d = (d - beta_l) & MASK128
        cw1[offs[0] + b] = u128.int_to_limbs(c1[b])
        cw2[offs[0] + b] = u128.int_to_limbs((c1[b] + d) & MASK128)
    s1 = (prf(k1, tb) + c1[tb]) & MASK128
    s2 = (prf(k2, tb)
          + u128.limbs_to_int(cw2[offs[0] + tb])) & MASK128

    # --- upper levels, bottom to top -------------------------------------
    for j in range(1, levels):
        if not ((s1 - s2) & MASK128 == beta_l and (s1 ^ s2) & 1):
            raise AssertionError(
                "radix keygen invariant broken at level %d: seed shares "
                "must differ by the odd beta' (and so in LSB)" % j)
        a = ars[j]
        beta_l = beta if j == levels - 1 else rng.u128_odd()
        tb = digits[j]
        s1_even = (s1 & 1) == 0
        c1 = [rng.u128() for _ in range(a)]
        for b in range(a):
            d = (prf(s2, b) - prf(s1, b)) & MASK128
            if s1_even:
                d = (-d) & MASK128
            cw2[offs[j] + b] = u128.int_to_limbs((c1[b] + d) & MASK128)
        c1[tb] = (c1[tb] + (beta_l if s1_even else -beta_l)) & MASK128
        for b in range(a):
            cw1[offs[j] + b] = u128.int_to_limbs(c1[b])
        n1 = (prf(s1, tb) + (c1[tb] if s1_even else
                             u128.limbs_to_int(cw2[offs[j] + tb]))) & MASK128
        n2 = (prf(s2, tb) + (u128.limbs_to_int(cw2[offs[j] + tb])
                             if s1_even else c1[tb])) & MASK128
        s1, s2 = n1, n2

    ka = MixedKey(arities=ars, cw1=cw1, cw2=cw2, last_key=k1, n=n)
    kb = MixedKey(arities=ars, cw1=cw1.copy(), cw2=cw2.copy(),
                  last_key=k2, n=n)
    return ka, kb


def gen_batched_r4(alphas, n: int, seeds=None, *, prf_method: int,
                   beta: int = 1, knobs=None):
    """Vectorized two-server mixed-radix keygen over B indices.

    The radix-4 counterpart of ``keygen.gen_batched``: one DRBG squeeze
    per key, then O(log4 N) vectorized PRF calls over [B, 4] limb
    tensors.  Bit-identical to ``generate_keys_r4(alphas[i], n,
    seeds[i])`` per key (the scalar generator stays the fuzz oracle).
    Returns two [B, 524] int32 wire-key arrays.

    ``knobs`` (searched, ``tune.kernel_search.keygen_search``) selects
    among bit-identical reformulations: ``prf_group="stacked"`` fuses
    the two per-branch PRF calls over s1‖s2 into one, ``path_reuse=
    "reuse"`` selects the target-path PRF outputs from the saved branch
    outputs instead of recomputing, ``squeeze_draws`` chunks the DRBG
    squeeze (``keygen.drbg_u128_batch``).
    """
    from .keygen import (_check_batch_args, _keygen_knob_fns, _wire_batch,
                         drbg_u128_batch)
    alphas, seeds = _check_batch_args(alphas, n, seeds)
    depth = n.bit_length() - 1
    if depth > 32:  # sum(arities) = 2*depth must fit MAX_CW
        raise ValueError("table size 2^%d exceeds max 2^32" % depth)
    ars = arities(n)
    offs = cw_offsets(ars)
    levels = len(ars)
    bsz = alphas.size
    prf_pair_v, path_pick, squeeze_draws = _keygen_knob_fns(
        prf_method, knobs)
    n_draws = 2 + (0 if levels == 1 else 1) + ars[0] + sum(
        (0 if j == levels - 1 else 1) + ars[j] for j in range(1, levels))
    draws = drbg_u128_batch(seeds, n_draws, squeeze_draws=squeeze_draws)
    cur = 0

    def draw():
        nonlocal cur
        v = draws[:, cur, :]
        cur += 1
        return v

    def odd(v):
        v = v.copy()
        v[:, 0] |= np.uint32(1)
        return v

    digits = np.empty((bsz, levels), dtype=np.uint32)
    rem = alphas.copy()
    for j, a in enumerate(ars):
        digits[:, j] = rem % a
        rem //= a

    beta_c = np.broadcast_to(u128.int_to_limbs(beta), (bsz, 4))
    cw1 = np.zeros((bsz, MAX_CW, 4), dtype=np.uint32)
    cw2 = np.zeros((bsz, MAX_CW, 4), dtype=np.uint32)
    rows = np.arange(bsz)

    # --- base level (eval step 0) ---------------------------------------
    a0 = ars[0]
    k1 = draw().copy()
    k1[:, 0] &= np.uint32(0xFFFFFFFE)                 # server 0: LSB 0
    k2 = odd(draw())                                  # server 1: LSB 1
    beta_l = beta_c if levels == 1 else odd(draw())
    tb = digits[:, 0]
    c1 = [draw() for _ in range(a0)]
    p1, p2 = [], []
    for b in range(a0):
        v1, v2 = prf_pair_v(k1, k2, b)
        p1.append(v1)
        p2.append(v2)
        d = u128.sub128(v1, v2)
        d = np.where((tb == b)[:, None], u128.sub128(d, beta_l), d)
        cw1[:, offs[0] + b] = c1[b]
        cw2[:, offs[0] + b] = u128.add128(c1[b], d)
    c1_t = np.stack(c1, axis=1)[rows, tb]
    s1 = u128.add128(path_pick(p1, k1, tb, rows), c1_t)
    s2 = u128.add128(path_pick(p2, k2, tb, rows), cw2[rows, offs[0] + tb])

    # --- upper levels, bottom to top -------------------------------------
    for j in range(1, levels):
        if not ((u128.sub128(s1, s2) == beta_l).all()
                and (((s1[:, 0] ^ s2[:, 0]) & 1) == 1).all()):
            raise AssertionError(
                "radix keygen invariant broken at level %d: seed shares "
                "must differ by the odd beta' (and so in LSB)" % j)
        a = ars[j]
        beta_l = beta_c if j == levels - 1 else odd(draw())
        tb = digits[:, j]
        s1_even = ((s1[:, 0] & np.uint32(1)) == 0)[:, None]
        c1 = [draw() for _ in range(a)]
        p1, p2 = [], []
        for b in range(a):
            v1, v2 = prf_pair_v(s1, s2, b)
            p1.append(v1)
            p2.append(v2)
            d = u128.sub128(v2, v1)
            d = np.where(s1_even, u128.neg128(d), d)
            cw2[:, offs[j] + b] = u128.add128(c1[b], d)
        adj = np.where(s1_even, beta_l, u128.neg128(beta_l))
        c1 = [np.where((tb == b)[:, None], u128.add128(c1[b], adj), c1[b])
              for b in range(a)]
        for b in range(a):
            cw1[:, offs[j] + b] = c1[b]
        c1_t = np.stack(c1, axis=1)[rows, tb]
        cw2_t = cw2[rows, offs[j] + tb]
        n1 = u128.add128(path_pick(p1, s1, tb, rows),
                         np.where(s1_even, c1_t, cw2_t))
        n2 = u128.add128(path_pick(p2, s2, tb, rows),
                         np.where(s1_even, cw2_t, c1_t))
        s1, s2 = n1, n2

    n_bin = sum(1 for a in ars if a == 2)
    marker = (np.uint32(4), np.uint32(n_bin))
    return (_wire_batch(cw1, cw2, k1, depth, n, radix_slot0=marker),
            _wire_batch(cw1, cw2, k2, depth, n, radix_slot0=marker))


def evaluate_mixed(key: MixedKey, indx: int, prf_method: int) -> int:
    """Scalar reference evaluation at one index (O(log N) PRF calls)."""
    prf = PRF_FUNCS[prf_method]
    offs = cw_offsets(key.arities)
    cur = key.last_key
    rem = indx
    for j, a in enumerate(key.arities):
        b = rem % a
        val = prf(cur, b)
        cw = key.cw1 if (cur & 1) == 0 else key.cw2
        cur = (val + u128.limbs_to_int(cw[offs[j] + b])) & MASK128
        rem //= a
    return cur


# ---------------------------------------------------------------------------
# Batched evaluation (host NumPy and device JAX share the level step)
# ---------------------------------------------------------------------------

def pack_mixed_keys(keys) -> tuple:
    """List of MixedKey -> (cw1 [B,64,4], cw2, last [B,4]) uint32."""
    bsz = len(keys)
    cw1 = np.zeros((bsz, MAX_CW, 4), dtype=np.uint32)
    cw2 = np.zeros((bsz, MAX_CW, 4), dtype=np.uint32)
    last = np.zeros((bsz, 4), dtype=np.uint32)
    for i, k in enumerate(keys):
        cw1[i] = k.cw1
        cw2[i] = k.cw2
        last[i] = u128.int_to_limbs(k.last_key)
    return cw1, cw2, last


def _level_step_mixed(seeds, cw1_lvl, cw2_lvl, prf_method: int, arity: int,
                      aes_impl=None, round_unroll=None):
    """One mixed-radix level: seeds [B, w, 4], cw*_lvl [B, a, 4]
    -> [B, a*w, 4] children (node-major: child b of node j at a*j + b)."""
    from .prf import prf_multi
    xp = np if isinstance(seeds, np.ndarray) else _jnp()
    sel = (seeds[..., 0] & np.uint32(1)).astype(bool)[..., None]
    outs = prf_multi(prf_method, seeds, arity, aes_impl, round_unroll)
    children = []
    for b in range(arity):
        cw = xp.where(sel, cw2_lvl[:, None, b, :], cw1_lvl[:, None, b, :])
        children.append(u128.add128(outs[b], cw))
    stacked = xp.stack(children, axis=2)              # [B, w, a, 4]
    bsz, w = seeds.shape[0], seeds.shape[1]
    return stacked.reshape(bsz, arity * w, 4)


def _jnp():
    import jax.numpy as jnp
    return jnp


def expand_leaves_mixed(cw1, cw2, last, *, n: int, prf_method: int,
                        natural_order: bool = True):
    """Full expansion to [B, N] low-32 leaf shares (NumPy or JAX arrays in
    -> same kind out).  Debug / one-hot path."""
    ars = arities(n)
    offs = cw_offsets(ars)
    xp = np if isinstance(last, np.ndarray) else _jnp()
    seeds = last[:, None, :]
    for j, a in enumerate(ars):
        c1 = cw1[:, offs[j]:offs[j] + a, :]
        c2 = cw2[:, offs[j]:offs[j] + a, :]
        seeds = _level_step_mixed(seeds, c1, c2, prf_method, a)
    lo = seeds[..., 0].astype(xp.int32)               # [B, N] BFS order
    if not natural_order:
        return lo
    # natural[perm[p]] = bfs[p]
    perm = mixed_reverse_indices(ars)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size)
    return lo[:, inv]


def eval_points_mixed(cw1, cw2, last, indices, *, n: int, prf_method: int,
                      aes_impl: str = "gather"):
    """Per-index root-to-leaf walks on device: [B,...] keys x [Q] indices.

    Mixed-radix counterpart of ``expand.eval_points`` (the naive-strategy
    surface): O(Q log4 N) PRF calls per key, natural-order output,
    [B, Q] int32.  Levels are a static Python loop (arities vary per
    level); gather S-box for AES (single-seed walks — bitslicing would
    pad each call to 32 lanes).
    """
    import jax
    import jax.numpy as jnp

    from .prf import prf_multi

    ars = arities(n)
    offs = cw_offsets(ars)
    indices = jnp.asarray(indices, dtype=jnp.uint32)

    def walk(cw1_k, cw2_k, last_k, idx):
        seed, rem = last_k, idx
        for j, a in enumerate(ars):
            b = (rem % np.uint32(a)).astype(jnp.int32)
            outs = prf_multi(prf_method, seed[None, :], a, aes_impl)
            val = jnp.stack([o[0] for o in outs])[b]      # [4]
            sel = (seed[0] & np.uint32(1)).astype(bool)
            cw_pair = jnp.where(sel, cw2_k[offs[j] + b],
                                cw1_k[offs[j] + b])
            seed = u128.add128(val, cw_pair)
            rem = rem // np.uint32(a)
        return seed[0].astype(jnp.int32)

    per_key = jax.vmap(jax.vmap(walk, in_axes=(None, None, None, 0)),
                       in_axes=(0, 0, 0, None))
    return per_key(jnp.asarray(cw1), jnp.asarray(cw2), jnp.asarray(last),
                   indices)


def _suffix_chunk(ars, target: int) -> tuple:
    """Split levels so phase 2 covers a trailing suffix with product <=
    target (at least the last level): returns (f_levels, chunk)."""
    prod = 1
    j = len(ars)
    while j > 0 and prod * ars[j - 1] <= max(target, ars[-1]):
        j -= 1
        prod *= ars[j]
    return j, prod


def _expand_contract_mixed_core(cw1, cw2, last, per_chunk_tables, dot_fn, *,
                                ars, offs, f_lv, prf_method, aes_impl,
                                round_unroll, out_width):
    import jax.numpy as jnp
    from jax import lax

    bsz = last.shape[0]

    def level(seeds, j):
        a = ars[j]
        return _level_step_mixed(
            seeds, cw1[:, offs[j]:offs[j] + a, :],
            cw2[:, offs[j]:offs[j] + a, :], prf_method, a,
            aes_impl, round_unroll)

    seeds = last[:, None, :]
    for j in range(f_lv):
        seeds = level(seeds, j)                       # [B, F, 4]

    def expand_subtree(node_seeds):
        s = node_seeds[:, None, :]
        for j in range(f_lv, len(ars)):
            s = level(s, j)
        return s[..., 0].astype(jnp.int32)            # [B, C]

    if f_lv == 0:
        return dot_fn(expand_subtree(seeds[:, 0, :]), per_chunk_tables[0])

    frontier = jnp.moveaxis(seeds, 1, 0)              # [F, B, 4]

    def body(acc, xs):
        node_seeds, chunk = xs
        return acc + dot_fn(expand_subtree(node_seeds), chunk), None

    acc0 = jnp.zeros((bsz, out_width), dtype=jnp.int32)
    acc, _ = lax.scan(body, acc0, (frontier, per_chunk_tables))
    return acc


def _expand_and_contract_mixed_jit(cw1, cw2, last, table_perm, *, n,
                                   prf_method, chunk_leaves, dot_impl,
                                   aes_impl, round_unroll, f_levels=None):
    from .expand import _dot_i32
    ars = arities(n)
    offs = cw_offsets(ars)
    e = table_perm.shape[1]
    if f_levels is None:
        f_lv, c = _suffix_chunk(ars, chunk_leaves or n)
    else:
        # searched override: phase 1 covers the first f_levels MIXED
        # levels (not binary levels — the cache key carries the radix,
        # so the two unit systems never mix)
        f_lv = int(f_levels)
        if not 0 <= f_lv < len(ars):
            raise ValueError("f_levels (%d) out of range for arities %r"
                             % (f_lv, ars))
        c = int(np.prod(ars[f_lv:]))
    f = n // c
    return _expand_contract_mixed_core(
        cw1, cw2, last, table_perm.reshape(f, c, e),
        lambda leaves, chunk: _dot_i32(leaves, chunk, dot_impl),
        ars=ars, offs=offs, f_lv=f_lv, prf_method=prf_method,
        aes_impl=aes_impl, round_unroll=round_unroll, out_width=e)


_RUN_JIT = None  # module-level jit wrapper: one trace cache per process


def expand_and_contract_mixed(cw1, cw2, last, table_perm, *, n: int,
                              prf_method: int, chunk_leaves: int | None,
                              dot_impl: str = "i32", aes_impl=None,
                              round_unroll=None,
                              f_levels: int | None = None):
    """Batched fused mixed-radix evaluation against one shared table.

    table_perm: [N, E] int32, pre-permuted with ``mixed_reverse_indices``.
    Returns [B, E] int32 shares.  The fused/monolithic counterpart of
    ``expand.expand_and_contract`` for radix-4 keys.  ``f_levels``
    overrides the ``_suffix_chunk`` split (mixed-level units); leaf
    order and results are invariant, only the phase-1/phase-2 balance
    moves.
    """
    import functools
    global _RUN_JIT
    if _RUN_JIT is None:
        import jax
        _RUN_JIT = functools.partial(
            jax.jit, static_argnames=("n", "prf_method", "chunk_leaves",
                                      "dot_impl", "aes_impl",
                                      "round_unroll", "f_levels")
        )(_expand_and_contract_mixed_jit)

    import jax.numpy as jnp
    return _RUN_JIT(jnp.asarray(cw1), jnp.asarray(cw2), jnp.asarray(last),
                    table_perm, n=n, prf_method=prf_method,
                    chunk_leaves=chunk_leaves, dot_impl=dot_impl,
                    aes_impl=aes_impl, round_unroll=round_unroll,
                    f_levels=f_levels)


def _per_key_tables_mixed_jit(cw1, cw2, last, tables_perm, *, n,
                              prf_method, chunk_leaves, dot_impl,
                              aes_impl, round_unroll):
    import jax
    import jax.numpy as jnp
    from jax import lax

    ars = arities(n)
    offs = cw_offsets(ars)
    bsz, _, e = tables_perm.shape
    f_lv, c = _suffix_chunk(ars, chunk_leaves or n)
    f = n // c

    def bdot(leaves, chunk):
        # [B, C] x [B, C, E] -> [B, E], batched over keys, mod 2^32
        from ..ops import matmul128
        if (dot_impl or "i32") == "i32":
            return lax.dot_general(
                leaves, chunk, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32)
        return jax.vmap(lambda a, t: matmul128.dot(a[None, :], t,
                                                   dot_impl)[0])(leaves,
                                                                 chunk)

    chunks = jnp.moveaxis(tables_perm.reshape(bsz, f, c, e), 1, 0)
    return _expand_contract_mixed_core(
        cw1, cw2, last, chunks, bdot, ars=ars, offs=offs, f_lv=f_lv,
        prf_method=prf_method, aes_impl=aes_impl,
        round_unroll=round_unroll, out_width=e)


_PKT_JIT = None


def expand_and_contract_per_key_tables_mixed(
        cw1, cw2, last, tables_perm, *, n: int, prf_method: int,
        chunk_leaves: int | None, dot_impl: str = "i32", aes_impl=None,
        round_unroll=None):
    """Radix-4 fused evaluation where every key has its OWN table.

    tables_perm: [B, N, E] int32, each digit-reverse-permuted.  The
    mixed-radix counterpart of
    ``expand.expand_and_contract_per_key_tables`` (the batch-PIR bin
    protocol's one-dispatch-per-round path).
    """
    import functools
    global _PKT_JIT
    if _PKT_JIT is None:
        import jax
        _PKT_JIT = functools.partial(
            jax.jit, static_argnames=("n", "prf_method", "chunk_leaves",
                                      "dot_impl", "aes_impl",
                                      "round_unroll")
        )(_per_key_tables_mixed_jit)
    import jax.numpy as jnp
    return _PKT_JIT(jnp.asarray(cw1), jnp.asarray(cw2), jnp.asarray(last),
                    tables_perm, n=n, prf_method=prf_method,
                    chunk_leaves=chunk_leaves, dot_impl=dot_impl,
                    aes_impl=aes_impl, round_unroll=round_unroll)


def _mixed_pallas_aes(cw1, cw2, last, table_perm, *, n, sbox, interpret,
                      dot_impl="i32"):
    """Radix-4 AES via the plane-domain Pallas level kernel: grouped
    breadth-first expansion under ``lax.scan`` (the mixed counterpart of
    ``expand._expand_contract_pallas_aes``)."""
    import jax.numpy as jnp

    from ..ops.aes_planes import aes_level_step_pallas
    from .expand import choose_chunk, grouped_scan_contract

    ars = arities(n)
    offs = cw_offsets(ars)
    bsz = last.shape[0]
    f_lv, c = _suffix_chunk(ars, choose_chunk(n, bsz))
    f = n // c

    def level(s, j):
        a = ars[j]
        return aes_level_step_pallas(
            s, cw1[:, offs[j]:offs[j] + a, :],
            cw2[:, offs[j]:offs[j] + a, :], arity=a, sbox=sbox,
            interpret=interpret)

    seeds = last[:, None, :]
    for j in range(f_lv):
        seeds = level(seeds, j)                       # [B, F, 4]

    def expand_fn(node_seeds):
        s = node_seeds
        for j in range(f_lv, len(ars)):
            s = level(s, j)
        return s[..., 0].astype(jnp.int32)            # [B, g*c]

    return grouped_scan_contract(seeds, table_perm, expand_fn, f=f, c=c,
                                 dot_impl=dot_impl)


def mixed_digits(table_perm, n: int):
    """The mixed subtree kernel's table (``pallas_level.subtree_digits``)
    for an N-row radix-4 table, built once by ``DPF``."""
    from ..ops.pallas_level import pallas_chunk_leaves, subtree_digits
    ars = arities(n)
    f_lv, _ = _suffix_chunk(ars, pallas_chunk_leaves(n))
    return subtree_digits(table_perm, int(np.prod(ars[:f_lv])),
                          tuple(ars[f_lv:]))


def _expand_contract_mixed_pallas_jit(cw1, cw2, last, table_perm, *, n,
                                      prf_method, interpret, sbox=None,
                                      dot_impl="i32"):
    from ..ops.pallas_level import (pallas_chunk_leaves,
                                    subtree_contract_pallas_mixed)
    from .prf import PRF_AES128
    if prf_method == PRF_AES128:
        return _mixed_pallas_aes(cw1, cw2, last, table_perm, n=n,
                                 sbox=sbox, interpret=interpret,
                                 dot_impl=dot_impl)
    ars = arities(n)
    offs = cw_offsets(ars)
    f_lv, _ = _suffix_chunk(ars, pallas_chunk_leaves(n))
    seeds = last[:, None, :]
    for j in range(f_lv):
        seeds = _level_step_mixed(
            seeds, cw1[:, offs[j]:offs[j] + ars[j], :],
            cw2[:, offs[j]:offs[j] + ars[j], :], prf_method, ars[j])
    return subtree_contract_pallas_mixed(
        seeds, cw1, cw2, table_perm, ars=ars, f_lv=f_lv,
        prf_method=prf_method, interpret=interpret)


_PALLAS_JIT = None


def expand_and_contract_mixed_pallas(cw1, cw2, last, table_perm, *, n: int,
                                     prf_method: int, interpret=False,
                                     aes_impl: str | None = None,
                                     dot_impl: str = "i32"):
    """Radix-4 fused evaluation on the Pallas kernels: ChaCha/Salsa ride
    the phase-2 subtree kernel
    (``ops/pallas_level.subtree_contract_pallas_mixed``), AES the
    plane-domain level kernel (``ops/aes_planes``)."""
    import functools
    global _PALLAS_JIT
    if _PALLAS_JIT is None:
        import jax
        _PALLAS_JIT = functools.partial(
            jax.jit, static_argnames=("n", "prf_method", "interpret",
                                      "sbox", "dot_impl")
        )(_expand_contract_mixed_pallas_jit)
    import jax.numpy as jnp
    sbox = (aes_impl.split(":", 1)[1]
            if aes_impl and ":" in aes_impl else None)
    return _PALLAS_JIT(jnp.asarray(cw1), jnp.asarray(cw2),
                       jnp.asarray(last), table_perm, n=n,
                       prf_method=prf_method, interpret=interpret,
                       sbox=sbox, dot_impl=dot_impl)


_STEP_JIT = None  # module-level per-level jit (cached across batches)


def eval_dispatch_mixed(cw1, cw2, last, table_perm, *, n: int,
                        prf_method: int, chunk_leaves: int | None,
                        group: int | None = None,
                        dot_impl: str = "i32", aes_impl=None,
                        round_unroll=None, deadline=None):
    """Per-level-program mixed-radix evaluation (the fast-compiling mode
    for bitsliced AES — compile time linear in level count, which radix-4
    halves).  Same math as ``expand_and_contract_mixed``.

    group: frontier subtrees expanded per pass (None = auto, live leaf
    tensor bounded at ~2^18 per key)."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from .expand import DeadlineExceeded, _group_contract

    def check_deadline():
        # monotonic like expand.eval_dispatch: NTP-step immune
        if deadline is not None and _time.monotonic() > deadline:
            raise DeadlineExceeded(
                "eval_dispatch soft deadline passed between dispatches")

    global _STEP_JIT
    if _STEP_JIT is None:
        _STEP_JIT = jax.jit(_level_step_mixed,
                            static_argnames=("prf_method", "arity",
                                             "aes_impl", "round_unroll"))
    step = _STEP_JIT

    ars = arities(n)
    offs = cw_offsets(ars)
    e = table_perm.shape[1]
    f_lv, c = _suffix_chunk(ars, chunk_leaves or n)
    f = n // c
    bsz = last.shape[0]
    if group is not None and group < 1:
        raise ValueError("dispatch group must be >= 1 (got %r)" % (group,))
    from .expand import choose_group
    g = min(group or choose_group(f, c), f)
    while f % g:  # explicit `group` may not divide f
        g -= 1

    cw1 = jnp.asarray(cw1)
    cw2 = jnp.asarray(cw2)

    def level(seeds, j):
        check_deadline()
        a = ars[j]
        return step(seeds, cw1[:, offs[j]:offs[j] + a, :],
                    cw2[:, offs[j]:offs[j] + a, :], prf_method, a,
                    aes_impl, round_unroll)

    seeds = jnp.asarray(last)[:, None, :]
    for j in range(f_lv):
        seeds = level(seeds, j)                       # [B, f, 4]

    tables = jnp.asarray(table_perm).reshape(f, c, e)
    acc = jnp.zeros((bsz, e), dtype=jnp.int32)
    for start in range(0, f, g):
        s = seeds[:, start:start + g, :]
        for j in range(f_lv, len(ars)):
            s = level(s, j)
        leaves = s[..., 0].astype(jnp.int32).reshape(bsz, g, c)
        acc = _group_contract(acc, leaves, tables[start:start + g],
                              dot_impl)
    return acc
