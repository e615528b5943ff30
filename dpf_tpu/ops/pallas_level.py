"""Hand-scheduled Pallas TPU kernels for the GGM expansion hot path.

The XLA path (``core/expand.py``) relies on fusion for the cipher rounds
but pays HBM round-trips for the ``[B, w, 4]`` seed tensors between tree
levels (the ``lax.scan`` carry).  At ChaCha's ~25 int-ops/byte that
traffic is comparable to the compute, so a fused kernel has up to ~2x of
headroom.  This module supplies the hand-scheduled alternative — the role
the reference's tuned hybrid kernel plays on GPU
(``dpf_gpu/dpf/dpf_hybrid.cu:123-231``, DFS subtrees resident in shared
memory) — redesigned for the TPU memory hierarchy:

* ``subtree_contract_pallas`` — the production kernel.  Grid
  ``(B/TB, F)``: for each key tile, every frontier subtree is expanded
  root-to-leaves **entirely in VMEM** (no inter-level HBM traffic), the
  low-32 leaf shares are contracted against the matching table chunk, and
  the ``[TB, E]`` accumulator stays resident in VMEM across the chunk
  axis (the documented reduction-dim pattern: the innermost grid
  dimension does not appear in the output index map).
* ``chacha_level_step_pallas`` — a single tiled level step (kept for
  layer-by-layer A/B measurement), grid over ``(B, w)`` tiles so VMEM
  stays bounded at any width.

Layout: limb-major ``[4, B, w]`` — the wide node axis rides the 128-wide
lanes; the ``[B, w, 4]`` boundary transposes sit inside jit where they are
negligible next to the cipher.

Contraction: the v5e MXU has no int32 matmul, so the kernels contract
on its int8 path (``_dot_digits``: 4 balanced int8 digits per operand,
10 digit products, exact mod 2^32).

Correctness: asserted against the portable XLA path in tests (interpret
mode on CPU, compiled on TPU).  ChaCha20-12 and Salsa20-12 cores; AES
has its own plane-domain level kernel (``ops/aes_planes.py``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.prf import _SIGMA


def _compiler_params(dimension_semantics):
    """Mosaic grid-dimension semantics ("parallel" dims may be pipelined
    /parallelized; "arbitrary" = sequential, for accumulation dims)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def _rotl(x, b):
    return (x << np.uint32(b)) | (x >> np.uint32(32 - b))


def _pos_plane(zero, pos_word):
    """The cipher state's position word as a plane.  Scalar positions
    (the GGM child index) stay a hard-coded u32 constant; array
    positions (the sqrt-N grid kernel's per-cell row counters,
    ``ops/pallas_sqrt.py``) broadcast against the zero plane."""
    if isinstance(pos_word, (int, np.integer)):
        return zero + np.uint32(pos_word)
    return zero + pos_word


def _chacha_block_planes(s, pos_word):
    """ChaCha20-12 full block on 4 seed planes -> 16 output words.

    Key/position placement matches ``core/prf._chacha_state`` (seed limbs
    LSW-first occupy state words 7..4) so results are bit-identical to
    the portable path.  The 6 double rounds run in a ``lax.fori_loop``:
    a fully unrolled body, chained across subtree levels through the
    block's constant-initialized output words, sends the XLA CPU
    simplifier into a pathological slow compile (hours at depth 6); the
    loop form compiles in seconds on every backend and Mosaic handles
    static-trip-count loops natively.
    """
    zero = s[0] - s[0]
    x = [zero + np.uint32(_SIGMA[i]) for i in range(4)]
    x += [s[3], s[2], s[1], s[0]]
    x += [zero] * 4
    x += [zero, _pos_plane(zero, pos_word), zero, zero]
    init = jnp.stack(x)

    def double_round(_, st):
        x = [st[i] for i in range(16)]
        for (a, b, c, d) in ((0, 4, 8, 12), (1, 5, 9, 13),
                             (2, 6, 10, 14), (3, 7, 11, 15),
                             (0, 5, 10, 15), (1, 6, 11, 12),
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

    out = lax.fori_loop(0, 6, double_round, init) + init
    return [out[i] for i in range(16)]


def _chacha_core_planes(s, pos_word):
    """ChaCha20-12 core -> 4 output planes (words 7..4, limbs LSW-first)."""
    o = _chacha_block_planes(s, pos_word)
    return [o[7], o[6], o[5], o[4]]


def _salsa_block_planes(s, pos_word):
    """Salsa20-12 full block — layout matches ``core/prf._salsa_state``
    (key at words 4..1 LSW-last, pos at word 9).  fori_loop rounds for
    the same compile-pathology reason as ``_chacha_block_planes``."""
    zero = s[0] - s[0]
    x = [zero] * 16
    x[0] = zero + np.uint32(_SIGMA[0])
    x[5] = zero + np.uint32(_SIGMA[1])
    x[10] = zero + np.uint32(_SIGMA[2])
    x[15] = zero + np.uint32(_SIGMA[3])
    x[1], x[2], x[3], x[4] = s[3], s[2], s[1], s[0]
    x[9] = _pos_plane(zero, pos_word)
    init = jnp.stack(x)

    def double_round(_, st):
        x = [st[i] for i in range(16)]
        for (a, b, c, d) in ((0, 4, 8, 12), (5, 9, 13, 1), (10, 14, 2, 6),
                             (15, 3, 7, 11), (0, 1, 2, 3), (5, 6, 7, 4),
                             (10, 11, 8, 9), (15, 12, 13, 14)):
            x[b] = x[b] ^ _rotl(x[a] + x[d], 7)
            x[c] = x[c] ^ _rotl(x[b] + x[a], 9)
            x[d] = x[d] ^ _rotl(x[c] + x[b], 13)
            x[a] = x[a] ^ _rotl(x[d] + x[c], 18)
        return jnp.stack(x)

    out = lax.fori_loop(0, 6, double_round, init) + init
    return [out[i] for i in range(16)]


def _salsa_core_planes(s, pos_word):
    """Salsa20-12 core -> 4 output planes (words 4..1, limbs LSW-first)."""
    o = _salsa_block_planes(s, pos_word)
    return [o[4], o[3], o[2], o[1]]


_CORES = {2: _chacha_core_planes, 1: _salsa_core_planes}  # prf id -> core
# block-PRG ids (core/prf_ref.py): ONE core call per node feeds all
# children — child b = block words [4b..4b+3] MSW-first, i.e. planes
# (limbs LSW-first) [4b+3, 4b+2, 4b+1, 4b]
_BLK_CORES = {4: _salsa_block_planes, 5: _chacha_block_planes}


def has_subtree_core(prf_method: int) -> bool:
    """True when the subtree kernel has a plane core for this PRF id
    (Salsa20/ChaCha20 and their block-PRG forms; not AES or DUMMY)."""
    return prf_method in _CORES or prf_method in _BLK_CORES


def heuristic_kernel(prf_method: int, radix: int = 2) -> str:
    """The binary-GGM kernel when no config, tuned or searched entry
    names one, on one chip (``api.DPF``) and on a mesh
    (``parallel.sharded.ShardedDPFServer``) alike: binary GGM over a PRF
    with a subtree core runs the VMEM-resident subtree kernel where it
    compiles (a TPU), which is bit-identical to the xla scan and 2.5-6x
    faster on a v5e (PERF.md); AES, DUMMY, radix 4 and every other
    backend keep the scan."""
    from ..utils.compat import has_pallas_sqrt_kernel
    if (radix == 2 and has_subtree_core(prf_method)
            and has_pallas_sqrt_kernel()):
        return "pallas"
    return "xla"


def _add128_planes(val, cw):
    """val + cw mod 2^128 on two 4-plane lists (explicit carry chain)."""
    out = []
    carry = None
    for i in range(4):
        t = val[i] + cw[i]
        c1 = (t < val[i]).astype(jnp.uint32)
        if carry is None:
            out.append(t)
            carry = c1
        else:
            t2 = t + carry
            c2 = (t2 < t).astype(jnp.uint32)
            out.append(t2)
            carry = c1 | c2
    return out


def _digits_i8(x):
    """int32 -> 4 signed int8 digits d_k with x == sum_k d_k 2^(8k) mod
    2^32 (balanced: each d_k in [-128, 127]).  Traceable in and out of
    a kernel: arithmetic shifts only."""
    out = []
    for _ in range(4):
        d = (x << 24) >> 24
        out.append(d.astype(jnp.int8))
        x = (x - d) >> 8
    return out


@jax.jit
def table_digits(table):
    """[N, E] int32 table -> [4, N, E] int8 digit planes, the form the
    in-kernel contraction reads (the v5e MXU has no int32 matmul)."""
    return jnp.stack(_digits_i8(jnp.asarray(table, jnp.int32)))


def _dot_digits(lhs, rhs_ref, rhs_k: int = 0):
    """Exact wrapping int32 ``lhs [M, K] @ rhs [K, E]`` on the MXU's
    int8 path: rhs comes as its 4 digit planes (``table_digits``), lhs
    is split here, and the 10 digit products with shift < 32 are
    accumulated.  Each product sums K terms of magnitude <= 2^14, so it
    is exact in int32 for K < 2^17.  ``rhs_k=1``: each plane is held
    [E, K], the K axis minor."""
    a = _digits_i8(lhs)
    acc = None
    for s in range(4):
        t = None
        for i in range(s + 1):
            p = lax.dot_general(a[i], rhs_ref[s - i],
                                (((1,), (rhs_k,)), ((), ())),
                                preferred_element_type=jnp.int32)
            t = p if t is None else t + p
        t = t << (8 * s) if s else t
        acc = t if acc is None else acc + t
    return acc


# ---------------------------------------------------------------------------
# Tiled single level step
# ---------------------------------------------------------------------------

def _level_kernel(seeds_ref, cw1_ref, cw2_ref, out0_ref, out1_ref):
    """seeds [4, TB, TW] u32; cw* [4, TB, 2] (limb, key, branch);
    out* [4, TB, TW] — children for branches 0 and 1."""
    s = [seeds_ref[i] for i in range(4)]
    sel = (s[0] & np.uint32(1)).astype(jnp.bool_)
    for branch, out_ref in ((0, out0_ref), (1, out1_ref)):
        val = _chacha_core_planes(s, np.uint32(branch))
        cw = [jnp.where(sel, cw2_ref[i, :, branch][:, None],
                        cw1_ref[i, :, branch][:, None]) for i in range(4)]
        res = _add128_planes(val, cw)
        for i in range(4):
            out_ref[i] = res[i]


def _chacha_level_step_impl(seeds, cw1_lvl, cw2_lvl, interpret=False,
                            tb: int = 8, tw: int = 512):
    """One ChaCha GGM level via Pallas, tiled over (batch, width).

    seeds: [B, w, 4] u32; cw*_lvl: [B, 2, 4] u32 (this level's codeword
    pair per key).  Returns [B, 2w, 4] children (new[2j+b] layout).
    VMEM per step is bounded by the (tb, tw) tile regardless of B, w.
    """
    from jax.experimental import pallas as pl

    bsz, w, _ = seeds.shape
    tb = min(tb, bsz)
    tw = min(tw, w)
    if bsz % tb or w % tw:  # pad to tile multiples, slice after
        pb = (-bsz) % tb
        pw = (-w) % tw
        seeds = jnp.pad(seeds, ((0, pb), (0, pw), (0, 0)))
        cw1_lvl = jnp.pad(cw1_lvl, ((0, pb), (0, 0), (0, 0)))
        cw2_lvl = jnp.pad(cw2_lvl, ((0, pb), (0, 0), (0, 0)))
    bp, wp = seeds.shape[0], seeds.shape[1]

    sm = jnp.transpose(seeds, (2, 0, 1))     # [4, B, w]
    cw1 = jnp.transpose(cw1_lvl, (2, 0, 1))  # [4, B, 2]
    cw2 = jnp.transpose(cw2_lvl, (2, 0, 1))

    grid = (bp // tb, wp // tw)
    out_shape = [jax.ShapeDtypeStruct((4, bp, wp), jnp.uint32)] * 2
    spec_seeds = pl.BlockSpec((4, tb, tw), lambda i, j: (0, i, j))
    spec_cw = pl.BlockSpec((4, tb, 2), lambda i, j: (0, i, 0))
    spec_out = pl.BlockSpec((4, tb, tw), lambda i, j: (0, i, j))
    out0, out1 = pl.pallas_call(
        _level_kernel,
        name="dpf_chacha_level",
        grid=grid,
        compiler_params=_compiler_params(("parallel", "parallel")),
        in_specs=[spec_seeds, spec_cw, spec_cw],
        out_specs=[spec_out, spec_out],
        out_shape=out_shape,
        interpret=interpret,
    )(sm, cw1, cw2)

    children = jnp.stack([jnp.transpose(out0, (1, 2, 0)),
                          jnp.transpose(out1, (1, 2, 0))], axis=2)
    return children.reshape(bp, 2 * wp, 4)[:bsz, :2 * w]


_chacha_level_step_jit = functools.partial(
    jax.jit, static_argnames=("interpret", "tb", "tw"))(
        _chacha_level_step_impl)


def chacha_level_step_pallas(seeds, cw1_lvl, cw2_lvl, interpret=False,
                             tb: int = 8, tw: int = 512):
    """Jit-wrapped level step; ``interpret=True`` runs EAGERLY.

    XLA-CPU compile of an interpret-mode pallas_call grows super-linearly
    with grid size (a 2x2 grid was observed past 30 GB / 20 min of
    compile); eager interpret executes the kernel body op-by-op in
    seconds.  Only the compiled (TPU) path needs the jit.
    """
    if interpret:
        return _chacha_level_step_impl(seeds, cw1_lvl, cw2_lvl,
                                       interpret=True, tb=tb, tw=tw)
    return _chacha_level_step_jit(seeds, cw1_lvl, cw2_lvl,
                                  interpret=False, tb=tb, tw=tw)


# ---------------------------------------------------------------------------
# Fused subtree expand + contract (the production kernel)
# ---------------------------------------------------------------------------

# default tile knobs: the levels' limbs live in two [4, TB, C] u32
# scratch buffers (``_make_subtree_kernel``).
# On a v5e at N = 2^16, B = 64 and 512 (PERF.md): the binary kernel
# (ChaCha20) ran 11-29 % faster per key at TB = 8 than at 16, 32 or 64,
# and slower at C = 2048; the radix-4 kernel (ChaCha20-BLK) ran 3 %
# faster at TB = 32 than at 8.
PALLAS_TB = 8         # binary key tile (one sublane group)
PALLAS_TB_MIXED = 32  # radix-4 key tile (a smaller batch: its size, >= 8)
PALLAS_MAX_C = 4096   # leaves per subtree -> 1 MiB of scratch at TB = 8

# One register tile of the cipher state: the 16 words of [8, 256] u32
# planes are 32 of the core's 64 vregs, so a tile's double rounds run
# in registers.  A whole wide level's [TB, w] planes did not fit: at
# w >= 1024 the state spilled to VMEM and the stores bound the loop
# (PERF.md section 5).
TILE_ROWS, TILE_LANES = 8, 256


def tiled_levels(sched: tuple) -> int:
    """How many of the subtree kernel's levels run tile by tile: those
    whose parent planes are at least one register tile wide (4 of 12
    at 4,096 leaves a subtree, binary)."""
    w, n = 1, 0
    for a in sched:
        n += w >= TILE_LANES
        w *= a
    return n


def _make_subtree_kernel(sched: tuple, prf_method: int = 2,
                         leaves_minor: bool = False, tb: int = PALLAS_TB):
    """Kernel over a per-level arity schedule.  ``sched[k]`` is the
    fan-out of kernel level k; the sliced codeword arrays hold the levels'
    slots back to back in the same order (see the wrapper's ``idx``).
    Block-PRG methods evaluate ONE core per node per level and split the
    512-bit block into the children (4x fewer cores at arity 4).

    Children are concatenated along the lanes (``new[b*w + j]``), not
    interleaved: Mosaic refuses the lane-splitting reshape an
    interleave needs.  The leaves thus come out digit-reversed against
    the XLA path's order, and the launcher hands the kernel its table in
    that order (``_kernel_leaf_order``).

    Levels narrower than a register tile run on the whole [TB, w]
    planes as values.  The wider ones (``tiled_levels``) loop over
    tiles of (8 rows, 256 lanes), reading their parents from one of two
    [4, TB, C] VMEM scratch buffers and writing the children to the
    other at lane offset ``b*w + j``; the leaf level writes limb 0
    alone, the one the contraction reads."""
    from jax.experimental import pallas as pl

    blk = _BLK_CORES.get(prf_method)
    core = None if blk is not None else _CORES[prf_method]
    n_tiled = tiled_levels(sched)
    n_narrow = len(sched) - n_tiled
    tr = TILE_ROWS if tb % TILE_ROWS == 0 else tb
    n_row_tiles = tb // tr

    def level(planes, cw1_ref, cw2_ref, rows, off, a):
        """Yield each child ``b`` of the parents ``planes`` (4 limbs of
        [R, L]) with its 4 limbs, keys ``rows`` of the codeword refs."""
        sel = (planes[0] & np.uint32(1)).astype(jnp.bool_)
        if blk is not None:
            out16 = blk(planes, np.uint32(0))
        for b in range(a):
            if blk is not None:
                val = [out16[4 * b + 3], out16[4 * b + 2],
                       out16[4 * b + 1], out16[4 * b]]
            else:
                val = core(planes, np.uint32(b))
            cw = [jnp.where(sel, cw2_ref[i, rows, off + b][:, None],
                            cw1_ref[i, rows, off + b][:, None])
                  for i in range(4)]
            yield b, _add128_planes(val, cw)

    def kernel(seeds_ref, cw1_ref, cw2_ref, table_ref, out_ref, *bufs):
        f = pl.program_id(1)
        x = seeds_ref[...]                            # [TB, 4]
        planes = [x[:, i:i + 1] for i in range(4)]    # [TB, 1]
        off, w = 0, 1
        for k, a in enumerate(sched[:n_narrow]):
            kids = level(planes, cw1_ref, cw2_ref, slice(None), off, a)
            if n_tiled and k == n_narrow - 1:     # into the first buffer
                for b, res in kids:
                    for i in range(4):
                        bufs[0][i, :, b * w:(b + 1) * w] = res[i]
            else:
                kids = [res for _, res in kids]
                planes = [jnp.concatenate([c[i] for c in kids], axis=1)
                          for i in range(4)]
            off, w = off + a, w * a
        for k, a in enumerate(sched[n_narrow:]):
            src, dst = bufs[k % 2], bufs[1 - k % 2]
            limbs = 1 if k == n_tiled - 1 else 4
            n_lane_tiles = w // TILE_LANES        # a power of two

            def tile(t, carry):                   # traced right here
                rows = slice(None)
                if n_row_tiles > 1:
                    r = t >> (n_lane_tiles.bit_length() - 1)
                    rows = pl.ds(pl.multiple_of(r * tr, tr), tr)
                    t = t & (n_lane_tiles - 1)
                j = pl.multiple_of(t * TILE_LANES, TILE_LANES)
                p = [src[i, rows, pl.ds(j, TILE_LANES)] for i in range(4)]
                for b, res in level(p, cw1_ref, cw2_ref, rows, off, a):
                    for i in range(limbs):
                        dst[i, rows, pl.ds(b * w + j, TILE_LANES)] = res[i]
                return carry

            lax.fori_loop(0, n_row_tiles * n_lane_tiles, tile, 0)
            off, w = off + a, w * a
        leaves = bufs[n_tiled % 2][0] if n_tiled else planes[0]
        contrib = _dot_digits(leaves.astype(jnp.int32), table_ref,
                              int(leaves_minor))      # [TB, E]

        @pl.when(f == 0)
        def _():
            out_ref[:] = contrib

        @pl.when(f > 0)
        def _():
            out_ref[:] = out_ref[:] + contrib

    return kernel


def _kernel_leaf_order(table_perm, f_cnt: int, sched: tuple):
    """Table rows in the subtree kernel's leaf order.  Within a subtree
    chunk the XLA order makes the first level's branch the most
    significant digit; the kernel's concatenated children make it the
    least significant, so each chunk's digit axes are reversed (a
    static row gather: a transpose over the tiny digit axes would pad
    every [2, E] tile to the chip's (8, 128) layout)."""
    n = table_perm.shape[0]
    depth = len(sched)
    idx = np.arange(n).reshape((f_cnt,) + tuple(sched))
    idx = np.transpose(idx, (0,) + tuple(range(depth, 0, -1))).reshape(n)
    return table_perm[idx]


@functools.partial(jax.jit, static_argnames=("f_cnt", "sched"))
def subtree_digits(table_perm, f_cnt: int, sched: tuple):
    """The subtree kernel's table: [4, N, E] int8 digit planes in its
    leaf order.  ``DPF`` builds it once per layout and passes it in place
    of the int32 table, so a call holds no table-sized temporaries."""
    return table_digits(_kernel_leaf_order(table_perm, f_cnt, sched))


@jax.jit
def table_digits_t(table):
    """[N, E] int32 rows -> [4, E, N] int8 digit planes, the leaves on
    the minor axis.  The kernel reads this form with ``leaves_minor``:
    the chip tiles an int8 array's minor axis by 128, so [4, N, 16]
    planes held for the row-major block occupy 8x their bytes, which a
    table of 2^26 rows a chip cannot afford."""
    return jnp.swapaxes(table_digits(table), 1, 2)


def _subtree_contract_run(frontier, cw1, cw2, table_perm, *, idx, sched,
                          prf_method, interpret, tb,
                          leaves_minor: bool = False):
    """Shared launcher: slice codeword slots (``idx``, level-major), pad
    the batch to the key-tile multiple, run the schedule kernel.
    ``table_perm`` is the [N, E] int32 table or its ``subtree_digits``;
    with ``leaves_minor`` its digits held [4, E, N] (``table_digits_t``
    of the rows in the kernel's leaf order)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, f_cnt, _ = frontier.shape
    n, e = table_perm.shape[-2:]
    if leaves_minor:
        e, n = n, e
    c = n // f_cnt
    assert c == int(np.prod(sched)), (c, sched)

    pb = (-bsz) % tb
    if pb:
        frontier = jnp.pad(frontier, ((0, pb), (0, 0), (0, 0)))
        cw1 = jnp.pad(cw1, ((0, pb), (0, 0), (0, 0)))
        cw2 = jnp.pad(cw2, ((0, pb), (0, 0), (0, 0)))
    bp = bsz + pb

    n_slots = len(idx)
    idx = np.asarray(idx)
    cw1_sl = jnp.transpose(cw1[:, idx, :], (2, 0, 1))
    cw2_sl = jnp.transpose(cw2[:, idx, :], (2, 0, 1))
    # [tiles, F, TB, 4]: one (TB, 4) block per grid step, whose dims
    # equal the array's last two, as Mosaic requires
    seeds = jnp.transpose(frontier.reshape(bp // tb, tb, f_cnt, 4),
                          (0, 2, 1, 3))
    digits = (table_perm if table_perm.ndim == 3
              else subtree_digits(table_perm, f_cnt, tuple(sched)))

    grid = (bp // tb, f_cnt)
    kernel = _make_subtree_kernel(tuple(sched), prf_method, leaves_minor,
                                  tb)
    scratch = ([pltpu.VMEM((4, tb, c), jnp.uint32)] * 2
               if tiled_levels(tuple(sched)) else [])
    table_spec = (pl.BlockSpec((4, e, c), lambda i, f: (0, 0, f))
                  if leaves_minor
                  else pl.BlockSpec((4, c, e), lambda i, f: (0, f, 0)))
    out = pl.pallas_call(
        kernel,
        name="dpf_subtree_contract",
        grid=grid,
        in_specs=[
            pl.BlockSpec((pl.squeezed, pl.squeezed, tb, 4),
                         lambda i, f: (i, f, 0, 0)),
            pl.BlockSpec((4, tb, n_slots), lambda i, f: (0, i, 0)),
            pl.BlockSpec((4, tb, n_slots), lambda i, f: (0, i, 0)),
            table_spec,
        ],
        out_specs=pl.BlockSpec((tb, e), lambda i, f: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, e), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
        # key tiles are independent; the subtree axis accumulates into
        # the same [tb, E] output block (reduction dim -> "arbitrary")
        compiler_params=_compiler_params(("parallel", "arbitrary")),
    )(seeds, cw1_sl, cw2_sl, digits)
    return out[:bsz]


def _subtree_contract_pallas_impl(frontier, cw1, cw2, table_perm, *,
                                  depth: int, f_levels: int,
                                  interpret=False, tb: int | None = None,
                                  prf_method: int = 2,
                                  leaves_minor: bool = False):
    """Fused phase-2: expand every frontier subtree in VMEM and contract.

    frontier:   [B, F, 4] u32 — phase-1 output seeds (subtree f of key b).
    cw1, cw2:   [B, 64, 4] u32 — full codeword arrays (wire layout).
    table_perm: [N, E] int32 — bit-reverse-permuted table, N = F * C —
    or its ``subtree_digits``; with ``leaves_minor``, the digits held
    [4, E, N] (``table_digits_t`` of the rows in the kernel's order).
    prf_method: 2 = ChaCha20-12, 1 = Salsa20-12 (for AES see
    ``subtree_contract_pallas_aes``).
    Returns [B, E] int32 shares: sum_f leaves(f) . chunk(f).
    """
    levels = depth - f_levels
    # phase-2 codeword slots, kernel level k = global flat level
    # depth-1-(f_levels+k), branches adjacent (binary wire layout 2i+b)
    idx = [2 * (depth - 1 - (f_levels + k)) + b
           for k in range(levels) for b in (0, 1)]
    return _subtree_contract_run(
        frontier, cw1, cw2, table_perm, idx=idx, sched=(2,) * levels,
        prf_method=prf_method, interpret=interpret, tb=tb or PALLAS_TB,
        leaves_minor=leaves_minor)


_subtree_contract_pallas_jit = functools.partial(jax.jit, static_argnames=(
    "depth", "f_levels", "interpret", "tb", "prf_method", "leaves_minor"))(
        _subtree_contract_pallas_impl)


def subtree_contract_pallas(frontier, cw1, cw2, table_perm, *,
                            depth: int, f_levels: int,
                            interpret=False, tb: int | None = None,
                            prf_method: int = 2, leaves_minor: bool = False):
    """Jit-wrapped fused subtree kernel; ``interpret=True`` runs EAGERLY
    (see ``chacha_level_step_pallas`` — interpret-under-jit compile
    blows up super-linearly on XLA-CPU)."""
    fn = (_subtree_contract_pallas_impl if interpret
          else _subtree_contract_pallas_jit)
    return fn(frontier, cw1, cw2, table_perm, depth=depth,
              f_levels=f_levels, interpret=interpret, tb=tb,
              prf_method=prf_method, leaves_minor=leaves_minor)


def _subtree_contract_pallas_mixed_impl(frontier, cw1, cw2, table_perm, *,
                                        ars: tuple, f_lv: int,
                                        interpret=False,
                                        tb: int | None = None,
                                        prf_method: int = 2):
    """Mixed-radix (radix-4) variant: phase-2 covers eval levels
    ``ars[f_lv:]`` with the mixed codeword layout (``radix4.cw_offsets``,
    level-major slots).  Same VMEM-resident expand+contract as the binary
    kernel; the wider fan-out means half the levels per subtree."""
    from ..core.radix4 import cw_offsets

    offs = cw_offsets(ars)
    sched = tuple(ars[f_lv:])
    idx = [offs[j] + b for j in range(f_lv, len(ars))
           for b in range(ars[j])]
    tb = tb or min(PALLAS_TB_MIXED, max(8, frontier.shape[0]))
    return _subtree_contract_run(
        frontier, cw1, cw2, table_perm, idx=idx, sched=sched,
        prf_method=prf_method, interpret=interpret, tb=tb)


_subtree_contract_pallas_mixed_jit = functools.partial(
    jax.jit, static_argnames=("ars", "f_lv", "interpret", "tb",
                              "prf_method"))(
        _subtree_contract_pallas_mixed_impl)


def subtree_contract_pallas_mixed(frontier, cw1, cw2, table_perm, *,
                                  ars: tuple, f_lv: int,
                                  interpret=False, tb: int | None = None,
                                  prf_method: int = 2):
    """Jit-wrapped mixed-radix subtree kernel; ``interpret=True`` runs
    EAGERLY (see ``chacha_level_step_pallas``)."""
    fn = (_subtree_contract_pallas_mixed_impl if interpret
          else _subtree_contract_pallas_mixed_jit)
    return fn(frontier, cw1, cw2, table_perm, ars=ars, f_lv=f_lv,
              interpret=interpret, tb=tb, prf_method=prf_method)


def pallas_chunk_leaves(n: int) -> int:
    """Leaves per subtree for the Pallas path.  Unlike the XLA path's
    ``choose_chunk`` (which scales with batch), the bound here is the
    per-key-tile VMEM cipher state, fixed by (PALLAS_TB, PALLAS_MAX_C)."""
    c = 1
    while c * 2 <= min(n, PALLAS_MAX_C):
        c *= 2
    return c
