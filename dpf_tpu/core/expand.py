"""TPU-side batched DPF expansion with fused table contraction.

Design (SURVEY.md §7): on TPU the natural formulation is breadth-first
everywhere.  The GGM level recurrence

    new[2j+b] = PRF(old[j], b) + cw[old[j] & 1][2i + b]        (mod 2^128)

runs as elementwise uint32-limb ops over a ``[B, width, 4]`` seed tensor.
To bound memory at large N (the role of the reference's DFS "hybrid" kernel,
``dpf_gpu/dpf/dpf_hybrid.cu``), expansion is split in two phases:

* **Phase 1**: expand all B keys from the root to a frontier of F nodes
  (full materialization, F small).
* **Phase 2**: ``lax.scan`` over the F frontier nodes; each step expands one
  node's subtree to its C = N/F leaves and immediately contracts against the
  matching table rows, accumulating into the output — O(B * C) live memory.

The contraction exploits that the protocol truncates shares to int32
(``dpf_wrapper.cu:178-185``): mod 2^32, the 128-bit leaf x entry product
reduces to ``lo32(leaf) * entry``, so the fused dot is an exact wrapping
int32 matmul — no 128-bit GEMM needed on the server at all.  (The reference
burns a custom split-K uint128 GEMM on this, ``dpf_gpu/matmul/matmul.cu``.)

Leaves emerge in bit-reversed order; the table is pre-permuted once at init
(`permute_table`), exactly as the reference does (``dpf_wrapper.cu:104-109``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import u128
from .prf import prf_pair

MAX_CW = 64  # codeword slots in the wire format (2 per level, depth <= 32)

# Live-seed budget for phase 2: the [B, C] x 16-byte seed tensor of one
# scanned subtree group.  choose_chunk and the autotuner's candidate
# generator (chunk_candidates) both honor it; 64 MiB keeps phase 2 well
# under VMEM-spill territory on TPU and cache-resident on CPU.
CHUNK_SEED_BYTES_BOUND = 1 << 26  # 64 MiB

_CHUNK_FLOOR = 256  # below this, scan overhead dominates any memory win


def chunk_within_bound(c: int, batch: int) -> bool:
    """True when a [B, C] seed tensor fits the 64 MiB budget (the floor
    chunk is always allowed: for batch <= 16384 it still fits exactly)."""
    return c <= _CHUNK_FLOOR or c * 16 * max(1, batch) <= \
        CHUNK_SEED_BYTES_BOUND


def choose_chunk(n: int, batch: int) -> int:
    """Leaves per phase-2 step: bound the live seed tensor at 64 MiB
    (B x C x 16 B with C = max(256, 2^22 / B); at B=512, C=8192)."""
    target = max(_CHUNK_FLOOR, (CHUNK_SEED_BYTES_BOUND // 16)
                 // max(1, batch))
    c = 1
    while c * 2 <= min(n, target):
        c *= 2
    return c


def clamp_chunk(chunk, n: int, batch: int) -> int:
    """Harden a possibly-tuned ``chunk_leaves`` against the live-seed
    budget: a falsy or over-budget value (e.g. a nearest-batch tuning
    cache fallback pairing a small-batch chunk with a bigger batch)
    falls back to the heuristic.  Shared by the single-chip and mesh
    resolution paths."""
    if not chunk or not chunk_within_bound(chunk, batch):
        chunk = choose_chunk(n, batch)
    return min(int(chunk), n)


def chunk_candidates(n: int, batch: int, span: int = 2) -> list:
    """``chunk_leaves`` candidates for the autotuner: powers of two within
    ``span`` octaves of the ``choose_chunk`` heuristic, each a divisor of
    the power-of-two ``n`` and each honoring the same 64 MiB live-seed
    bound (candidates above it are dropped, not clipped).  The heuristic
    itself is always a member, so a tuned config can never regress the
    static default's memory envelope.  Sorted ascending."""
    base = choose_chunk(n, batch)
    out = set()
    for s in range(-span, span + 1):
        c = base << s if s >= 0 else base >> (-s)
        if 1 <= c <= n and chunk_within_bound(c, batch):
            out.add(c)
    return sorted(out)


def f_level_candidates(n: int, chunk: int, batch: int,
                       span: int = 3) -> list:
    """Legal ``f_levels`` overrides for one (n, chunk_leaves) pair: the
    phase-1/phase-2 split may sit anywhere from the chunk-implied
    frontier (``log2(n/chunk)`` — the pre-search behavior, always a
    member) down the tree, as long as the fully-materialized frontier
    seed tensor [B, 2^f_levels, 4] honors the same 64 MiB live-seed
    bound that phase 2 does.  At most ``span`` extra levels are offered
    (each one doubles the frontier).  Sorted ascending."""
    depth = int(np.log2(n))
    base = depth - int(np.log2(max(1, int(chunk))))
    out = []
    for fl in range(base, min(depth, base + span) + 1):
        if (1 << fl) * 16 * max(1, batch) <= CHUNK_SEED_BYTES_BOUND:
            out.append(fl)
    return out or [base]


def _level_step_pair(seeds, cw1_pair, cw2_pair, prf_method: int,
                     aes_impl: str | None = None,
                     round_unroll: bool | None = None):
    """One GGM level with this level's codeword pairs passed directly.

    seeds [B, w, 4]; cw*_pair [B, 2, 4] (branch, limb) -> [B, 2w, 4]."""
    with jax.named_scope("dpf.prf"):
        prf_out = prf_pair(prf_method, seeds, aes_impl, round_unroll)
    with jax.named_scope("dpf.cw_add"):
        sel = (seeds[..., 0] & np.uint32(1)).astype(bool)[..., None]
        children = []
        for b in (0, 1):
            cw = jnp.where(sel, cw2_pair[:, None, b, :],
                           cw1_pair[:, None, b, :])       # [B, w, 4]
            children.append(u128.add128(prf_out[b], cw))
        stacked = jnp.stack(children, axis=2)             # [B, w, 2, 4]
        bsz, w = seeds.shape[0], seeds.shape[1]
        return stacked.reshape(bsz, 2 * w, 4)


def _scoped(scope: str, fn):
    """``fn`` traced under ``jax.named_scope(scope)``.  A program that
    is dispatched on its own is traced afresh, outside any scope open
    at its call site, so its phase's name has to go inside it."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)
    return run


# one level program per phase of eval_dispatch: (seeds, cw1 pair, cw2
# pair, prf_method, aes_impl, round_unroll), the last three static
_frontier_step_jit = jax.jit(_scoped("dpf.frontier", _level_step_pair),
                             static_argnums=(3, 4, 5))
_subtree_step_jit = jax.jit(_scoped("dpf.subtree", _level_step_pair),
                            static_argnums=(3, 4, 5))


def _level_step(seeds, cw1, cw2, i: int, prf_method: int,
                aes_impl: str | None = None,
                round_unroll: bool | None = None):
    """One GGM level: [B, w, 4] -> [B, 2w, 4].  `i` is the flat level index."""
    return _level_step_pair(seeds, cw1[:, 2 * i:2 * i + 2, :],
                            cw2[:, 2 * i:2 * i + 2, :], prf_method,
                            aes_impl, round_unroll)


# Bitsliced AES is one large boolean circuit (~1K ops a round).  Unrolled
# per tree level it appears once per level, and the N = 2^20 program took
# 435 s to compile on the chip host (PR 21).  Its levels therefore run
# through ONE level program in a loop (``_expand_tiled``): every level is
# cut into tiles of a fixed width, so XLA compiles the circuit once per
# loop instead of once per level.
TILE_SEEDS = 1 << 17  # seeds per tiled level step (batch x tile)


def _tiled(prf_method: int, aes_impl) -> bool:
    from .prf import PRF_AES128, _aes_pair_impl
    impl = aes_impl if aes_impl not in (None, "auto") else _aes_pair_impl()
    return prf_method == PRF_AES128 and impl.startswith("bitsliced")


def _tile(batch: int, out_w: int) -> int:
    """Nodes per key in one tiled level step: TILE_SEEDS / batch, a power
    of two no wider than half the expansion's output."""
    t = 1
    while t * 2 * max(1, batch) <= TILE_SEEDS and t * 4 <= out_w:
        t *= 2
    return t


def _expand_tiled(seeds, cw1, cw2, top: int, levels: int, prf_method: int,
                  aes_impl, round_unroll):
    """``levels`` GGM levels from flat level ``top`` down through one level
    program: [B, w0, 4] -> [B, w0 << levels, 4], bit-identical to the
    per-level ``_level_step`` chain.  Each level runs as tiles of
    ``_tile`` nodes, last tile first, in one buffer: tile t's children
    land at 2t*tile, past every parent still unread.  A level narrower
    than a tile pads it; the padding's children are never read."""
    bsz, w0, _ = seeds.shape
    out_w = w0 << levels
    tile = _tile(bsz, out_w)
    buf = jnp.zeros((bsz, max(out_w, 2 * tile), 4), jnp.uint32)
    buf = lax.dynamic_update_slice_in_dim(buf, seeds, 0, axis=1)

    def level(k, buf):
        i = top - k
        p1 = lax.dynamic_slice_in_dim(cw1, 2 * i, 2, axis=1)
        p2 = lax.dynamic_slice_in_dim(cw2, 2 * i, 2, axis=1)
        n_tiles = jnp.maximum(jnp.left_shift(w0, k) // tile, 1)

        def step(j, buf):
            t = n_tiles - 1 - j
            s = lax.dynamic_slice_in_dim(buf, t * tile, tile, axis=1)
            kids = _level_step_pair(s, p1, p2, prf_method, aes_impl,
                                    round_unroll)
            return lax.dynamic_update_slice_in_dim(buf, kids, 2 * t * tile,
                                                   axis=1)
        return lax.fori_loop(0, n_tiles, step, buf)

    return lax.fori_loop(0, levels, level, buf)[:, :out_w]


def expand_levels(seeds, cw1, cw2, top: int, levels: int, prf_method: int,
                  aes_impl=None, round_unroll=None):
    """``levels`` GGM levels from flat level ``top`` down: [B, w, 4] ->
    [B, w << levels, 4].  Bitsliced AES runs them through one tiled level
    program, every other PRF as one ``_level_step`` per level."""
    if _tiled(prf_method, aes_impl):
        return _expand_tiled(seeds, cw1, cw2, top, levels, prf_method,
                             aes_impl, round_unroll)
    for i in range(top, top - levels, -1):
        seeds = _level_step(seeds, cw1, cw2, i, prf_method, aes_impl,
                            round_unroll)
    return seeds


def permute_table(table_i32: np.ndarray) -> np.ndarray:
    """Bit-reverse-permute table rows once at init (host side)."""
    n = table_i32.shape[0]
    return np.ascontiguousarray(table_i32[u128.bit_reverse_indices(n)])


def _expand_contract_core(cw1, cw2, last, per_chunk_tables, dot_fn, *,
                          depth, prf_method, f, aes_impl, round_unroll,
                          out_width, f_levels=None):
    """Shared engine for the fused kernels: phase-1 frontier expansion, then
    a scan over frontier subtrees applying `dot_fn(leaves, chunk)` against
    `per_chunk_tables` ([F, ...] with chunk on the leading axis).

    ``f_levels`` decouples the phase-1/phase-2 split from the contraction
    chunk (the kernel-search "level-fusion frontier" axis): phase 1 may
    expand PAST the ``log2(f)`` frontier the chunk implies, in which case
    each scan step takes ``2^f_levels / f`` consecutive frontier nodes and
    expands them together through the remaining levels — the leaves still
    land in the same BFS order, so the contraction (and the answer) is
    bit-identical; only the materialization/scan balance moves.  ``None``
    keeps the pre-search behavior (``f_levels == log2(f)``)."""
    bsz = last.shape[0]
    seeds = last[:, None, :]  # [B, 1, 4]
    base_levels = int(np.log2(f))
    tiled = _tiled(prf_method, aes_impl)
    if f_levels is None and tiled:
        # start each subtree a tile wide (no padded levels), as far as
        # the live-seed budget lets the phase-1 frontier grow
        tile = _tile(bsz, (1 << depth) // f)
        f_levels = base_levels
        while (f_levels < depth and (2 << f_levels) // f <= tile
               and (2 << f_levels) * 16 * bsz <= CHUNK_SEED_BYTES_BOUND):
            f_levels += 1
    f_levels = base_levels if f_levels is None else int(f_levels)
    assert base_levels <= f_levels <= depth, (
        "f_levels %d outside [log2(f)=%d, depth=%d]"
        % (f_levels, base_levels, depth))

    def expand(s, first, last):
        return expand_levels(s, cw1, cw2, depth - 1 - first, last - first,
                             prf_method, aes_impl, round_unroll)

    # Phase 1: root -> frontier (levels depth-1 .. depth-f_levels)
    with jax.named_scope("dpf.frontier"):
        seeds = expand(seeds, 0, f_levels)
    g = (1 << f_levels) // f  # frontier nodes per contraction chunk

    def expand_subtree(node_seeds):
        """[B, g, 4] frontier seeds -> [B, C] low-32 leaf shares."""
        with jax.named_scope("dpf.subtree"):
            s = expand(node_seeds, f_levels, depth)
            return s[..., 0].astype(jnp.int32)  # low limb, [B, C]

    if f == 1:
        return dot_fn(expand_subtree(seeds), per_chunk_tables[0])

    frontier = jnp.moveaxis(seeds.reshape(bsz, f, g, 4), 1, 0)  # [F,B,g,4]

    def body(acc, xs):
        node_seeds, chunk = xs
        return acc + dot_fn(expand_subtree(node_seeds), chunk), None

    acc0 = jnp.zeros((bsz, out_width), dtype=jnp.int32)
    acc, _ = lax.scan(body, acc0, (frontier, per_chunk_tables))
    return acc


@functools.partial(jax.jit, static_argnames=("depth", "prf_method",
                                             "chunk_leaves", "dot_impl",
                                             "aes_impl", "round_unroll",
                                             "kernel_impl", "f_levels",
                                             "pallas_tb"))
def expand_and_contract(cw1, cw2, last, table_perm, *, depth: int,
                        prf_method: int, chunk_leaves: int,
                        dot_impl: str = "i32", aes_impl: str | None = None,
                        round_unroll: bool | None = None,
                        kernel_impl: str = "xla",
                        f_levels: int | None = None,
                        pallas_tb: int | None = None):
    """Batched fused DPF evaluation against one shared table.

    Args:
      cw1, cw2: [B, 64, 4] uint32 — per-key codeword limb arrays.
      last:     [B, 4] uint32 — per-key start seeds.
      table_perm: [N, E] int32 — bit-reverse-permuted table.
      depth: log2(N); prf_method: static PRF id; chunk_leaves: C.
      kernel_impl: "xla" (scan + fused dot) or "pallas" (hand-scheduled
        subtree kernel, ChaCha/Salsa — see ops/pallas_level.py).
      f_levels: optional phase-1/phase-2 split override (the kernel
        search's level-fusion frontier axis; None = log2(N/C), the
        pre-search behavior).  Bit-identical for any legal value.
      pallas_tb: optional key-tile override for the Pallas subtree
        kernel (searched GGM variants; None = the hand-tuned default).

    Returns [B, E] int32 server output shares.
    """
    n, e = table_perm.shape[-2:]  # pallas: [4, N, E] digits may come
    c = chunk_leaves
    f = n // c  # frontier width
    assert c * f == n and depth == int(np.log2(n))
    if kernel_impl == "pallas":
        from ..core.prf import PRF_AES128
        from ..ops.pallas_level import has_subtree_core
        if prf_method == PRF_AES128:
            sbox = (aes_impl.split(":", 1)[1]
                    if aes_impl and ":" in aes_impl else None)
            return _expand_contract_pallas_aes(
                cw1, cw2, last, table_perm, depth=depth,
                chunk_leaves=c, dot_impl=dot_impl, sbox=sbox)
        assert has_subtree_core(prf_method), (
            "kernel_impl='pallas' supports ChaCha20/Salsa20(+_BLK)/AES128")
        return _expand_contract_pallas(cw1, cw2, last, table_perm,
                                       depth=depth, f=f,
                                       prf_method=prf_method,
                                       f_levels=f_levels, tb=pallas_tb)
    return _expand_contract_core(
        cw1, cw2, last, table_perm.reshape(f, c, e),
        lambda leaves, chunk: _dot_i32(leaves, chunk, dot_impl),
        depth=depth, prf_method=prf_method, f=f, aes_impl=aes_impl,
        round_unroll=round_unroll, out_width=e, f_levels=f_levels)


@functools.partial(jax.jit, static_argnames=("dot_impl",))
def _group_contract(acc, leaves, chunks, dot_impl: str = "i32"):
    """acc [B,E] += einsum('bgc,gce->be') of group leaves x table chunks,
    exact mod 2^32 (int32 wraparound).  The sum over (g, c) is a plain
    [B, G*C] x [G*C, E] matmul, so both contraction impls apply."""
    bsz = leaves.shape[0]
    e = chunks.shape[-1]
    return acc + _dot_i32(leaves.reshape(bsz, -1), chunks.reshape(-1, e),
                          dot_impl)


class DeadlineExceeded(RuntimeError):
    """Raised by eval_dispatch between device programs when its soft
    deadline passes — never mid-compile."""


def eval_dispatch(cw1, cw2, last, table_perm, *, depth: int,
                  prf_method: int, chunk_leaves: int, group: int | None = None,
                  dot_impl: str = "i32", aes_impl: str | None = None,
                  round_unroll: bool | None = None,
                  deadline: float | None = None):
    """Multi-dispatch evaluation: Python-driven per-level jitted steps.

    Same math as ``expand_and_contract`` but split into one small XLA
    program per GGM level (cached per width) plus a contraction step —
    compile time grows linearly with depth instead of with the whole
    unrolled program.  This matters for bitsliced AES, whose monolithic
    graph (~16 level blocks x ~1.4K-op S-box circuits) can take tens of
    minutes to compile; per-level graphs compile in seconds.  Dispatch
    overhead is ~(levels + 1) x (F/G) host round-trips per batch.

    group: frontier nodes expanded together per pass (default: as many as
    keep the live leaf tensor under ~2^18 x batch x 16 B).
    deadline: optional time.monotonic() value; checked between dispatches
    (cooperative — raises DeadlineExceeded without interrupting a
    compile).  Monotonic, not wall-clock: an NTP step must neither fire
    the deadline spuriously nor starve it.
    """
    import time as _time

    def check_deadline():
        if deadline is not None and _time.monotonic() > deadline:
            raise DeadlineExceeded(
                "eval_dispatch soft deadline passed between dispatches")
    n, e = table_perm.shape
    c = chunk_leaves
    f = n // c
    assert c * f == n and depth == int(np.log2(n))
    bsz = last.shape[0]
    if group is not None and group < 1:
        raise ValueError("dispatch group must be >= 1 (got %r)" % (group,))
    g = min(group or choose_group(f, c), f)
    while f % g:  # explicit `group` may not divide f
        g -= 1
    f_levels = int(np.log2(f))

    cw1 = jnp.asarray(cw1)
    cw2 = jnp.asarray(cw2)

    def pairs(i):
        return cw1[:, 2 * i:2 * i + 2, :], cw2[:, 2 * i:2 * i + 2, :]

    seeds = jnp.asarray(last)[:, None, :]
    for l in range(f_levels):
        check_deadline()
        p1, p2 = pairs(depth - 1 - l)
        seeds = _frontier_step_jit(seeds, p1, p2, prf_method, aes_impl,
                                   round_unroll)              # [B, f, 4]

    tables = jnp.asarray(table_perm).reshape(f, c, e)
    acc = jnp.zeros((bsz, e), dtype=jnp.int32)
    for start in range(0, f, g):
        s = seeds[:, start:start + g, :]                      # [B, g, 4]
        for l in range(f_levels, depth):
            check_deadline()
            p1, p2 = pairs(depth - 1 - l)
            s = _subtree_step_jit(s, p1, p2, prf_method, aes_impl,
                                  round_unroll)
        leaves = s[..., 0].astype(jnp.int32).reshape(bsz, g, c)
        acc = _group_contract(acc, leaves, tables[start:start + g],
                              dot_impl)
    return acc


def _expand_contract_pallas(cw1, cw2, last, table_perm, *, depth: int,
                            f: int, interpret: bool = False,
                            prf_method: int = 2,
                            f_levels: int | None = None,
                            tb: int | None = None):
    """Phase-1 frontier via XLA (tiny), phase-2 via the fused Pallas
    subtree kernel.  ``f_levels``/``tb`` are the searched GGM variant's
    structure overrides (None = the chunk-implied split and the
    hand-tuned key tile)."""
    from ..ops.pallas_level import subtree_contract_pallas
    seeds = last[:, None, :]
    f_levels = int(np.log2(f)) if f_levels is None else int(f_levels)
    with jax.named_scope("dpf.frontier"):
        for l in range(f_levels):
            seeds = _level_step(seeds, cw1, cw2, depth - 1 - l, prf_method)
    with jax.named_scope("dpf.subtree"):   # the contraction is inside
        return subtree_contract_pallas(
            seeds, cw1, cw2, table_perm, depth=depth, f_levels=f_levels,
            interpret=interpret, tb=tb, prf_method=prf_method)


def _pvary(x, axes):
    """Type a shard_map scan carry as varying over the mesh axes.  Empty
    ``axes`` (a caller outside any shard_map, e.g. the cluster tier's
    host-local leaf-range eval) is identity: a cast over axis names
    that don't exist would raise."""
    return lax.pcast(x, tuple(axes), to="varying") if axes else x


def _valid_psum_group(psum_group, n_chunks: int) -> int:
    """The effective chunk-group size for grouped psums: 0 (one terminal
    psum) unless ``psum_group`` divides the chunk count with at least
    two groups — a tuned value from another shape degrades to the
    terminal psum rather than failing the program."""
    g = int(psum_group or 0)
    return g if 0 < g < n_chunks and n_chunks % g == 0 else 0


def _scan_psum_groups(body, zeros, xs, axis_name: str,
                      outer_axes=("batch",)):
    """Grouped-psum driver shared by the three sharded constructions.

    Scans ``xs`` (every leaf already reshaped to ``[n_groups, g, ...]``)
    one chunk-group at a time: each group accumulates locally through
    ``body`` (a standard per-chunk scan body), the group partial is
    psummed over ``axis_name``, and the psum result adds onto the outer
    carry — int32 wrap keeps any grouping exact, and the collective has
    no data dependency on the NEXT group's PRF expansion, so an async
    backend overlaps ICI latency with compute.

    Carry typing: the INNER partial is varying over ``outer_axes`` plus
    ``axis_name`` (its body adds shard-local dot products), but the
    OUTER carry holds only psum outputs — invariant along ``axis_name``
    — so it is typed varying over ``outer_axes`` alone.  Typing it over
    the reduced axis too would trip shard_map's out_specs invariance
    check.  The 2D row x
    entry-byte path passes ``outer_axes=("batch", "byte")``: its psum
    runs over "table" only, so the carry still varies over the byte
    axis (each byte shard holds a different entry block)."""
    def gbody(acc, xs_g):
        part0 = _pvary(zeros, tuple(outer_axes) + (axis_name,))
        part, _ = lax.scan(body, part0, xs_g)
        with jax.named_scope("dpf.psum"):
            return acc + lax.psum(part, axis_name), None

    acc, _ = lax.scan(gbody, _pvary(zeros, tuple(outer_axes)), xs)
    return acc


def eval_leaf_range(cw1, cw2, last, tbl, row0, *, depth: int,
                    prf_method: int, chunk_leaves: int, n_total: int,
                    kernel_impl: str = "xla", aes_impl: str | None = None,
                    psum_group: int = 0, axis_name: str | None = None,
                    carry_axes=("batch", "table")):
    """The partial share of one contiguous range of BFS leaves, [row0,
    row0 + rows): the per-shard step of every binary mesh and cluster
    evaluation.

    The range is whole frontier subtrees of ``chunk_leaves`` leaves.
    Phase 1 expands the frontier (cheap: width ``n_total / chunk``) and
    takes the range's nodes with a dynamic slice, so ``row0`` may be
    traced (the mesh's axis index, or a cluster granule's offset).
    Phase 2 is the subtree kernel (``kernel_impl="pallas"``, ``tbl`` the
    range's ``[4, E, rows]`` int8 digit planes in the kernel's leaf
    order, see ``parallel.sharded.place_table``) or the xla scan (``tbl``
    the range's ``[rows, E]`` int32 rows in BFS order).

    Returns ``(out, psummed)``: with a valid ``psum_group`` (and an
    ``axis_name`` to reduce over) the xla scan psums every chunk group
    and ``out`` is already the mesh-wide sum (``psummed=True``);
    otherwise ``out`` is the range's partial and the caller reduces it.
    ``carry_axes`` types the scan carry for shard_map callers; pass
    ``()`` outside a mesh program.
    """
    pallas = kernel_impl == "pallas"
    rows, e = (tbl.shape[2], tbl.shape[1]) if pallas else tbl.shape
    bsz = last.shape[0]
    c = chunk_leaves
    f_local = rows // c                      # frontier nodes in the range
    f_levels = int(np.log2(n_total // c))    # levels down to the frontier

    with jax.named_scope("dpf.frontier"):
        seeds = expand_levels(last[:, None, :], cw1, cw2, depth - 1,
                              f_levels, prf_method, aes_impl)
        seeds = lax.dynamic_slice_in_dim(seeds, row0 // c, f_local, axis=1)

    if pallas:
        from ..ops.pallas_level import subtree_contract_pallas
        with jax.named_scope("dpf.subtree"):   # the contraction is inside
            return subtree_contract_pallas(
                seeds, cw1, cw2, tbl, depth=depth, f_levels=f_levels,
                prf_method=prf_method, leaves_minor=True), False

    def expand_subtree(node_seeds):
        with jax.named_scope("dpf.subtree"):
            s = expand_levels(node_seeds[:, None, :], cw1, cw2,
                              depth - 1 - f_levels, depth - f_levels,
                              prf_method, aes_impl)
            return s[..., 0].astype(jnp.int32)

    tbl_chunks = tbl.reshape(f_local, c, e)
    if f_local == 1:
        return _dot_i32(expand_subtree(seeds[:, 0, :]), tbl_chunks[0]), False

    frontier = jnp.moveaxis(seeds, 1, 0)  # [f_local, B, 4]

    def body(acc, xs):
        node_seeds, chunk = xs
        return acc + _dot_i32(expand_subtree(node_seeds), chunk), None

    zeros = jnp.zeros((bsz, e), dtype=jnp.int32)
    g = _valid_psum_group(psum_group, f_local) if axis_name else 0
    if not g:
        # inside shard_map the scan carry must be typed as varying over
        # the mesh axes (the body's output is), or the carry mismatches
        acc, _ = lax.scan(body, _pvary(zeros, carry_axes),
                          (frontier, tbl_chunks))
        return acc, False
    return _scan_psum_groups(body, zeros, (
        frontier.reshape(f_local // g, g, bsz, 4),
        tbl_chunks.reshape(f_local // g, g, c, e)), axis_name,
        outer_axes=tuple(a for a in carry_axes if a != axis_name)), True


def choose_group(f: int, c: int) -> int:
    """Frontier nodes expanded together: the largest divisor of ``f``
    keeping the live leaf tensor under ~2^18 x batch x 16 B (shared by
    the dispatch and Pallas-AES drivers)."""
    g = max(1, min(f, (1 << 18) // c))
    while f % g:
        g -= 1
    return g


def grouped_scan_contract(seeds, table_perm, expand_fn, *, f: int, c: int,
                          dot_impl: str = "i32"):
    """Phase-2 grouping under ``lax.scan``: split the ``f`` frontier
    nodes ([B, F, 4] ``seeds``) into equal groups of g, expand each group
    with ``expand_fn([B, g, 4]) -> [B, g*c]`` leaves, contract against
    the matching table rows, accumulate [B, E].  Equal shapes per group
    make the whole loop one scanned program; live memory is bounded at
    ``B x g x c x 16 B``."""
    e = table_perm.shape[1]
    bsz = seeds.shape[0]
    g = choose_group(f, c)

    def body(acc, xs):
        node_seeds, chunk = xs                        # [B, g, 4], [g*c, E]
        leaves = expand_fn(node_seeds)                # [B, g*c]
        return acc + _dot_i32(leaves, chunk, dot_impl), None

    acc0 = jnp.zeros((bsz, e), dtype=jnp.int32)
    tables = table_perm.reshape(f // g, g * c, e)
    grouped = jnp.moveaxis(seeds.reshape(bsz, f // g, g, 4), 1, 0)
    if f // g == 1:
        acc, _ = body(acc0, (grouped[0], tables[0]))
        return acc
    acc, _ = lax.scan(body, acc0, (grouped, tables))
    return acc


def _expand_contract_pallas_aes(cw1, cw2, last, table_perm, *, depth: int,
                                chunk_leaves: int, dot_impl: str = "i32",
                                sbox: str | None = None,
                                interpret: bool = False):
    """AES via the plane-domain Pallas level kernel (ops/aes_planes.py).

    AES is compute-bound, so unlike the ChaCha subtree kernel there is no
    inter-level VMEM-residency win; each level is one fast-compiling
    Pallas program, and frontier groups ride ``grouped_scan_contract``.
    """
    from ..ops.aes_planes import aes_level_step_pallas
    n, e = table_perm.shape
    c = chunk_leaves
    f = n // c
    f_levels = int(np.log2(f))

    def level(s, l):
        i = depth - 1 - l
        return aes_level_step_pallas(
            s, cw1[:, 2 * i:2 * i + 2, :], cw2[:, 2 * i:2 * i + 2, :],
            arity=2, sbox=sbox, interpret=interpret)

    seeds = last[:, None, :]
    with jax.named_scope("dpf.frontier"):
        for l in range(f_levels):
            seeds = level(seeds, l)                   # [B, F, 4]

    def expand_fn(node_seeds):
        s = node_seeds
        with jax.named_scope("dpf.subtree"):
            for l in range(f_levels, depth):
                s = level(s, l)
            return s[..., 0].astype(jnp.int32)        # [B, g*c]

    return grouped_scan_contract(seeds, table_perm, expand_fn, f=f, c=c,
                                 dot_impl=dot_impl)


def _dot_i32(a, b, impl: str | None = None):
    """Exact wrapping int32 matmul: [B, C] x [C, E] -> [B, E] mod 2^32.

    Delegates to ops.matmul128 (switchable VPU int32 vs MXU int8-limb)."""
    from ..ops import matmul128
    with jax.named_scope("dpf.contract"):
        return matmul128.dot(a, b, impl)


@functools.partial(jax.jit, static_argnames=("depth", "prf_method",
                                             "chunk_leaves", "dot_impl",
                                             "aes_impl", "round_unroll"))
def expand_and_contract_per_key_tables(
        cw1, cw2, last, tables_perm, *, depth: int, prf_method: int,
        chunk_leaves: int, dot_impl: str = "i32",
        aes_impl: str | None = None, round_unroll: bool | None = None):
    """Fused evaluation where every key has its OWN table.

    tables_perm: [B, N, E] int32 (each bit-reverse-permuted).  Returns
    [B, E] int32 shares: out[b] = sum_j leaf32[b, j] * tables_perm[b, j].

    This serves the batch-PIR bin protocol natively: one dispatch answers
    one query round across all equal-sized bins (the reference's layer
    loops bins on the host).
    """
    bsz, n, e = tables_perm.shape
    c = chunk_leaves
    f = n // c
    assert c * f == n and depth == int(np.log2(n))

    @jax.named_scope("dpf.contract")
    def bdot(leaves, chunk):
        # [B, C] x [B, C, E] -> [B, E], batched over keys, mod 2^32
        from ..ops import matmul128
        if (dot_impl or "i32") == "i32":
            return lax.dot_general(
                leaves, chunk, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32)
        # mxu decomposition per key via vmap over the batch axis
        return jax.vmap(lambda a, t: matmul128.dot(a[None, :], t,
                                                   dot_impl)[0])(leaves,
                                                                 chunk)

    # chunk axis leads: [F, B, C, E]
    chunks = jnp.moveaxis(tables_perm.reshape(bsz, f, c, e), 1, 0)
    return _expand_contract_core(
        cw1, cw2, last, chunks, bdot,
        depth=depth, prf_method=prf_method, f=f, aes_impl=aes_impl,
        round_unroll=round_unroll, out_width=e)


def expand_leaves(cw1, cw2, last, *, depth: int, prf_method: int):
    """Full expansion to [B, N] low-32 leaf shares in natural index order.

    Debug/one-hot path (the reference's breadth-first strategy output,
    ``dpf_gpu/dpf/dpf_breadth_first.cu:93-103``, de-bit-reversed).
    Memory O(B * N); use expand_and_contract for large N.
    """
    seeds = last[:, None, :]
    for l in range(depth):
        seeds = _level_step(seeds, cw1, cw2, depth - 1 - l, prf_method)
    lo = seeds[..., 0].astype(jnp.int32)  # [B, N] BFS order
    perm = u128.bit_reverse_indices(1 << depth)
    return lo[:, perm]


def eval_points(cw1, cw2, last, indices, *, depth: int, prf_method: int,
                aes_impl: str = "gather"):
    """Per-index root-to-leaf walks on device: [B,...] keys x [Q] indices.

    The "naive strategy" analogue (reference ``dpf_gpu/dpf/dpf_naive.cu``):
    O(Q log N) PRF calls per key, no auxiliary memory, natural-order output.
    Useful for spot-checks and sparse queries.  Returns [B, Q] int32.
    ``aes_impl`` defaults to the gather S-box: these are scalar walks and
    bitslicing would pad every single-seed PRF call to 32 lanes.
    """
    indices = jnp.asarray(indices, dtype=jnp.uint32)

    def walk(cw1_k, cw2_k, last_k, idx):
        # one key, one index
        def level(l, carry):
            seed, rem = carry
            i = depth - 1 - l
            b = (rem & np.uint32(1)).astype(jnp.int32)
            out_pair = prf_pair(prf_method, seed[None, :], aes_impl)
            val = jnp.where(b == 0, out_pair[0][0], out_pair[1][0])
            sel = (seed[0] & np.uint32(1)).astype(bool)
            cw_pair = jnp.where(sel, cw2_k[2 * i + b], cw1_k[2 * i + b])
            nxt = u128.add128(val, cw_pair)
            return nxt, rem >> np.uint32(1)

        seed, _ = jax.lax.fori_loop(0, depth, level, (last_k, idx))
        return seed[0].astype(jnp.int32)

    per_key = jax.vmap(jax.vmap(walk, in_axes=(None, None, None, 0)),
                       in_axes=(0, 0, 0, None))
    return per_key(cw1, cw2, last, indices)


def pack_keys(flat_keys) -> tuple:
    """List of FlatKey -> (cw1 [B,64,4], cw2, last [B,4]) uint32 arrays.

    Scalar-codec packing (the batched wire path is
    ``keygen.decode_keys_batched``, which skips FlatKey entirely); the
    stacks here run at C level, only last_key needs per-key limb
    conversion.
    """
    cw1 = np.stack([k.cw1 for k in flat_keys]).astype(np.uint32, copy=False)
    cw2 = np.stack([k.cw2 for k in flat_keys]).astype(np.uint32, copy=False)
    last = np.stack([u128.int_to_limbs(k.last_key) for k in flat_keys])
    return cw1, cw2, last
