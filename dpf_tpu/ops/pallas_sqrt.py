"""Fused sqrt-N PRF-grid -> contract Pallas TPU kernel.

The XLA sqrt-N path (``core/sqrtn._eval_contract_batched_jit``) scans
``[B, rc, K]`` PRF grid slabs through HBM: every scan step materializes
the slab, applies the LSB codeword select/add, and hands ``matmul128``
a ``[B, rc*K]`` leaf-share tensor — at ChaCha's ~25 int-ops/byte that
slab traffic is comparable to the compute.  This module supplies the
fused alternative (the sqrt-N half of the ROADMAP megakernel item,
completing ``pallas_level.subtree_contract_pallas``'s logn half):

grid ``(B/TB, R/rc)`` — for each key tile, one ``rc``-row tile of the
``[R, K]`` PRF grid is expanded **entirely in VMEM** (one cipher call
over the ``[TB, rc*K]`` cell planes; the block-PRG ids evaluate one
512-bit core block per FOUR grid rows and interleave, exactly
``sqrtn._grid_vals``), the low-limb codeword select/add lands in
registers, and the ``[TB, E]`` table contraction accumulates in the
VMEM-resident output block (the documented reduction-dim pattern: the
innermost grid dimension does not appear in the output index map).  The
one-hot leaf share never touches HBM.

Cell order is natural: cell ``m = t*K + c`` of a tile holds grid row
``row0 + t``, column seed ``c`` — table rows line up with no
permutation, and a traced ``row0`` (the sharded path's per-shard row
base) rides in as one SMEM scalar; each tile adds ``j * rc``.

Only the low 32 output bits are contracted, and 128-bit adds carry
upward only, so the codeword add needs just the low limb — the kernel
ships ``cw*[..., 0]`` planes and skips the carry chain entirely.

**Kernel variants** (the generative-search space, ``tune/
kernel_search.py``): the structural choices PR 10 hard-coded are now
parameters — ``tb`` (key-tile height), ``max_cells`` (the VMEM cell
budget the row chunk halves down to), ``grid_order`` ("bk" = key tiles
outer / row tiles inner, the reduction-dim default; "kb" = row tiles
outer, valid only when one key tile covers the batch — revisiting an
output block from non-adjacent grid steps is not Mosaic-legal),
``dim_semantics`` (the KEY-tile axis as "parallel" or "arbitrary"; the
row axis accumulates and is always "arbitrary"), ``limbs`` ("low" =
low-limb-only codeword add; "multi" = all four value limbs + the full
128-bit carry chain, the scan path's exact arithmetic — bit-identical
because carries only propagate upward), and ``cw_add`` ("fused" = the
``jnp.where`` select; "staged" = base-add-then-masked-correction,
``cw1 + sel*(cw2-cw1)``, bit-identical mod 2^32).  Every variant is
equality-gated against the scan oracle before it is ever trusted.

Correctness: asserted against the scan-path oracle in tests (interpret
mode on CPU, compiled on TPU).  ChaCha20-12/Salsa20-12 cores and their
block-PRG variants; AES stays on the XLA path (see
``pallas_level``'s module docstring).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .pallas_level import (_BLK_CORES, _CORES, _add128_planes,
                           _compiler_params, _dot_digits, has_subtree_core,
                           table_digits)

# default tile knobs: widest live state = 16 cipher words x [TB, cells]
# u32 (the block-PRG ids quarter that — one block per 4 rows).  These
# are the PR-10 hand-tuned values; the kernel search treats them as the
# seed of the variant space, not the answer.
PALLAS_SQRT_TB = 32         # key tile (sublane-friendly multiple of 8)
PALLAS_SQRT_MAX_CELLS = 2048  # rc*K per tile -> ~4 MB cipher state


def pallas_sqrt_unsupported(prf_method: int, r: int) -> str | None:
    """Why the grid kernel cannot run this shape (None = it can).

    Callers that resolved ``kernel_impl="pallas"`` degrade to the xla
    scan path with provenance (``note_swallowed``) instead of raising —
    only an EXPLICIT pallas pin surfaces the reason as an error."""
    if not has_subtree_core(prf_method):
        return ("prf id %d has no Pallas plane core (AES stays on the "
                "XLA dispatch path)" % prf_method)
    if prf_method in _BLK_CORES and r % 4:
        return ("block-PRG sqrt-N grid kernel needs R (%d) to be a "
                "multiple of 4 (the 4-rows-per-core-block interleave "
                "cannot straddle a tile edge)" % r)
    return None


def pallas_sqrt_row_chunk(r: int, k: int, row_chunk: int | None = None,
                          max_cells: int | None = None) -> int:
    """Grid rows per kernel step.  The kernel's live state is the
    ``[TB, rc*K]`` cipher planes in VMEM, so the bound is the CELL count
    (``max_cells``, default ``PALLAS_SQRT_MAX_CELLS``), not the XLA
    scan's 64 MiB HBM slab.  Explicit/tuned values obey the shared
    row-chunk rules (divide R, multiple of 4 when chunking —
    ``sqrtn._resolve_row_chunk``) and are then halved down to the cell
    cap: the accumulation order changes, the bits do not (int32 adds
    wrap).  That halving used to be silent — callers that need to know
    whether the kernel they dispatch matches the chunk their cache
    entry claims compare this function's answer against the request
    (``api``'s ``row_chunk_effective`` provenance)."""
    from ..core.sqrtn import ROW_CHUNK_FLOOR, _resolve_row_chunk
    cap = PALLAS_SQRT_MAX_CELLS if max_cells is None else int(max_cells)
    rc = r if row_chunk is None else _resolve_row_chunk(r, k, 1, row_chunk)
    # halving preserves "divides R"; the %8 guard keeps rc a multiple
    # of 4 all the way down to the 4-row interleave floor
    while rc * k > cap and rc > ROW_CHUNK_FLOOR and rc % 8 == 0:
        rc //= 2
    return rc


def _make_sqrt_kernel(prf_method: int, tb: int, rc: int, k: int,
                      j_axis: int = 1, limbs: str = "low",
                      cw_add: str = "fused"):
    """Kernel body for one (key tile, row tile) grid step.

    ``j_axis``: which grid axis is the row-tile (accumulation) axis.
    ``limbs``/``cw_add``: emission and codeword-select structure (see
    the module docstring); every combination is bit-identical.

    Cell planes are built by concatenating ``rc`` K-wide row segments
    along the lanes: Mosaic refuses the lane-merging reshape of a
    ``[TB, rc, K]`` broadcast.
    """
    from jax.experimental import pallas as pl

    blk = _BLK_CORES.get(prf_method)
    core = None if blk is not None else _CORES[prf_method]
    nlimb = 4 if limbs == "multi" else 1

    def cat(parts):
        return jnp.concatenate(parts, axis=1)

    def rows(c):
        """[TB, rc] per-row values -> [TB, cells] (row t over K cells)."""
        return cat([jnp.broadcast_to(c[:, t:t + 1], (tb, k))
                    for t in range(rc)])

    def kernel(row0_ref, seeds_ref, cw1_ref, cw2_ref, table_ref, out_ref):
        j = pl.program_id(j_axis)
        # this tile's base row
        row0 = row0_ref[0] + j.astype(jnp.uint32) * np.uint32(rc)
        s = [seeds_ref[i] for i in range(4)]           # [TB, K]
        zero = s[0] - s[0]
        # cell m = t*K + c: grid row row0+t under column seed c —
        # natural order, matching the table tile rows directly
        if blk is not None:
            # ONE core block per 4 grid rows: counter plane c for rows
            # 4c..4c+3 (row0 is a multiple of 4 by the row-chunk rules)
            nctr = rc // 4
            planes = [cat([p] * nctr) for p in s]
            ctr = cat([zero + ((row0 >> np.uint32(2)) + np.uint32(c))
                       for c in range(nctr)])
            out16 = blk(planes, ctr)
            # row 4c+g = block words [4g..4g+3] MSW-first, so limb l of
            # that row is word 4g+3-l (``_grid_vals``/``_blk_group``)
            vals = [cat([out16[4 * (t % 4) + 3 - l][
                :, (t // 4) * k:(t // 4 + 1) * k] for t in range(rc)])
                for l in range(nlimb)]
        else:
            planes = [cat([p] * rc) for p in s]
            pos = cat([zero + (row0 + np.uint32(t)) for t in range(rc)])
            vals = list(core(planes, pos)[:nlimb])
        sel = cat([s[0] & np.uint32(1)] * rc)          # [TB, cells] 0/1

        def select(c1, c2):
            """The codeword the LSB picks, as a [TB, cells] plane."""
            if cw_add == "staged":
                # base + masked correction: cw1 + sel*(cw2-cw1), exact
                # mod 2^32 (u32 wraps) — two staged adds, no select op
                return rows(c1) + sel * rows(c2 - c1)
            return jnp.where(sel.astype(jnp.bool_), rows(c2), rows(c1))

        if limbs == "multi":
            # the scan path's exact arithmetic: all four value limbs +
            # the full 128-bit carry chain, low limb contracted (carries
            # only propagate upward, so the bits match the low-only path)
            cw = [select(cw1_ref[l], cw2_ref[l]) for l in range(4)]
            leaves = _add128_planes(vals, cw)[0].astype(jnp.int32)
        else:
            leaves = (vals[0] + select(cw1_ref[...], cw2_ref[...])) \
                .astype(jnp.int32)                     # [TB, cells]
        contrib = _dot_digits(leaves, table_ref)       # [TB, E]

        @pl.when(j == 0)
        def _():
            out_ref[:] = contrib

        @pl.when(j > 0)
        def _():
            out_ref[:] = out_ref[:] + contrib

    return kernel


def _sqrt_grid_contract_impl(seeds, cw1, cw2, table, row0, *,
                             prf_method: int, row_chunk: int | None = None,
                             interpret=False, tb: int | None = None,
                             max_cells: int | None = None,
                             grid_order: str = "bk",
                             dim_semantics: str = "parallel",
                             limbs: str = "low", cw_add: str = "fused"):
    """Traceable launcher (the sharded per-shard body calls this inside
    its own jit/shard_map with a TRACED ``row0``).

    seeds: [B, K, 4] u32; cw1/cw2: [B, R, 4] u32; table: [R*K, E] int32
    natural-order rows for grid rows row0..row0+R-1, or their
    ``table_digits`` (what ``DPF`` passes, built once).  Returns [B, E]
    int32 shares, bit-identical to the scan oracle for EVERY variant of
    (tb, max_cells, grid_order, dim_semantics, limbs, cw_add).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, k, _ = seeds.shape
    r = cw1.shape[1]
    e = table.shape[-1]
    assert table.shape[-2] == r * k, (table.shape, r, k)
    reason = pallas_sqrt_unsupported(prf_method, r)
    if reason:
        raise ValueError(reason)
    if grid_order not in ("bk", "kb"):
        raise ValueError("grid_order must be 'bk' or 'kb' (got %r)"
                         % (grid_order,))
    if dim_semantics not in ("parallel", "arbitrary"):
        raise ValueError("dim_semantics must be 'parallel' or "
                         "'arbitrary' (got %r)" % (dim_semantics,))
    if limbs not in ("low", "multi"):
        raise ValueError("limbs must be 'low' or 'multi' (got %r)"
                         % (limbs,))
    if cw_add not in ("fused", "staged"):
        raise ValueError("cw_add must be 'fused' or 'staged' (got %r)"
                         % (cw_add,))
    rc = pallas_sqrt_row_chunk(r, k, row_chunk, max_cells)
    steps = r // rc

    tb = tb or min(PALLAS_SQRT_TB, max(8, bsz))
    pb = (-bsz) % tb
    if pb:
        seeds = jnp.pad(seeds, ((0, pb), (0, 0), (0, 0)))
        cw1 = jnp.pad(cw1, ((0, pb), (0, 0), (0, 0)))
        cw2 = jnp.pad(cw2, ((0, pb), (0, 0), (0, 0)))
    bp = bsz + pb
    if grid_order == "kb" and bp > tb:
        # rows-outer revisits each output block from NON-adjacent grid
        # steps once there is more than one key tile — not Mosaic-legal
        # (the searcher's validity predicate mirrors this rule)
        raise ValueError(
            "grid_order='kb' needs the batch (%d padded) to fit one "
            "key tile (tb=%d): rows-outer iteration would revisit "
            "output blocks non-consecutively" % (bp, tb))

    sm = jnp.transpose(seeds, (2, 0, 1))               # [4, B, K]
    # codewords as [steps, (4,) B, rc]: a (TB, rc) block then spans the
    # array's last dim whole, as Mosaic requires
    if limbs == "multi":
        cw1_in, cw2_in = (jnp.transpose(c.reshape(bp, steps, rc, 4),
                                        (1, 3, 0, 2)) for c in (cw1, cw2))
        cw_block = (pl.squeezed, 4, tb, rc)
        cw_maps = (lambda i, j: (j, 0, i, 0)), (lambda j, i: (j, 0, i, 0))
    else:
        cw1_in, cw2_in = (jnp.transpose(c[:, :, 0].reshape(bp, steps, rc),
                                        (1, 0, 2)) for c in (cw1, cw2))
        cw_block = (pl.squeezed, tb, rc)
        cw_maps = (lambda i, j: (j, i, 0)), (lambda j, i: (j, i, 0))
    digits = (table if table.ndim == 3                 # [4, R*K, E] i8
              else table_digits(table))
    # the traced base row rides whole in SMEM; tiles add j * rc
    row0 = jnp.asarray(row0, jnp.uint32).reshape(1)

    if grid_order == "bk":
        grid = (bp // tb, steps)
        j_axis, cw_map = 1, cw_maps[0]
        maps = (lambda i, j: (0, i, 0),       # seeds
                lambda i, j: (0, j, 0),       # table digits
                lambda i, j: (i, 0))          # out
        semantics = (dim_semantics, "arbitrary")
    else:
        grid = (steps, bp // tb)
        j_axis, cw_map = 0, cw_maps[1]
        maps = (lambda j, i: (0, i, 0),
                lambda j, i: (0, j, 0),
                lambda j, i: (i, 0))
        semantics = ("arbitrary", dim_semantics)

    kernel = _make_sqrt_kernel(prf_method, tb, rc, k, j_axis=j_axis,
                               limbs=limbs, cw_add=cw_add)
    out = pl.pallas_call(
        kernel,
        name="dpf_sqrt_grid_contract",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((4, tb, k), maps[0]),
            pl.BlockSpec(cw_block, cw_map),
            pl.BlockSpec(cw_block, cw_map),
            pl.BlockSpec((4, rc * k, e), maps[1]),
        ],
        out_specs=pl.BlockSpec((tb, e), maps[2]),
        out_shape=jax.ShapeDtypeStruct((bp, e), jnp.int32),
        interpret=interpret,
        # key tiles are independent; the row-tile axis accumulates into
        # the same [tb, E] output block (reduction dim -> "arbitrary")
        compiler_params=_compiler_params(semantics),
    )(row0, sm, cw1_in, cw2_in, digits)
    return out[:bsz]


_VARIANT_FIELDS = ("tb", "max_cells", "grid_order", "dim_semantics",
                   "limbs", "cw_add")

_sqrt_grid_contract_jit = functools.partial(
    jax.jit, static_argnames=("prf_method", "row_chunk", "interpret")
    + _VARIANT_FIELDS)(_sqrt_grid_contract_impl)


def sqrt_grid_contract_pallas(seeds, cw1, cw2, table, *, prf_method: int,
                              row_chunk: int | None = None, row0=0,
                              interpret=False, tb: int | None = None,
                              max_cells: int | None = None,
                              grid_order: str = "bk",
                              dim_semantics: str = "parallel",
                              limbs: str = "low", cw_add: str = "fused"):
    """Jit-wrapped fused sqrt-N grid kernel; ``interpret=True`` runs
    EAGERLY (see ``pallas_level.chacha_level_step_pallas`` —
    interpret-under-jit compile blows up super-linearly on XLA-CPU).

    ``row0`` may be a traced uint32 scalar (the sharded path's
    per-shard row base); already-traced callers get the impl inlined.
    The variant keywords default to the PR-10 hand-tuned structure; the
    kernel search (``tune/kernel_search.py``) threads searched values
    through here.
    """
    args = (jnp.asarray(seeds), jnp.asarray(cw1), jnp.asarray(cw2),
            jnp.asarray(table), row0)
    fn = (_sqrt_grid_contract_impl if interpret
          else _sqrt_grid_contract_jit)
    return fn(*args, prf_method=prf_method, row_chunk=row_chunk,
              interpret=interpret, tb=tb, max_cells=max_cells,
              grid_order=grid_order, dim_semantics=dim_semantics,
              limbs=limbs, cw_add=cw_add)
