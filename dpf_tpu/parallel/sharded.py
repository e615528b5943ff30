"""Multi-chip DPF evaluation: table row-sharding + batch sharding on a mesh.

The reference has no multi-GPU path at all (SURVEY.md §2.4); this module is
where the TPU build goes beyond it.  Two orthogonal parallel axes map the
workload onto a ``jax.sharding.Mesh``:

* **"table" axis (the TP analogue)** — the bit-reverse-permuted table is
  row-sharded; each chip owns a contiguous range of BFS leaf positions,
  i.e. a set of whole GGM frontier subtrees.  Every chip replicates the
  cheap phase-1 expansion (root -> frontier, O(B*F)), expands only its own
  subtrees, contracts against its local table rows, and the partial int32
  outputs are summed with ``psum`` over ICI.  Valid because additive secret
  shares commute with partial dot products.
* **"batch" axis (the DP analogue)** — independent DPF keys are embarrassingly
  parallel; the key batch is sharded and outputs concatenated.

Keys are ~2 KB each and broadcast over the mesh; output is [B, E] int32 —
both negligible next to the O(N) expansion, so scaling is linear in chips
until N/n_table_shards stops covering a chip.

All three constructions run sharded (binary GGM here, radix-4 via the
mixed engines, sqrt-N via ``core.sqrtn.eval_sharded_sqrt`` over a
natural-order table), the psum can be issued per chunk-group
(``psum_group`` — overlapping ICI latency with the next chunk's PRF
expansion), and ``ShardedDPFServer`` resolves its knobs from the
mesh-aware tuning cache (``tune/mesh_tune.py``).  See docs/SHARDING.md.

Multi-host runs use the same code: construct the mesh from
``jax.distributed``-initialized global devices and lay the "table" axis on
the ICI-adjacent dimension so psum rides ICI, not DCN.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import expand, u128


def _pvary(x, axes):
    """Type a shard_map scan carry as varying over the mesh axes.  Empty
    ``axes`` (a caller outside any shard_map, e.g. the cluster tier's
    host-local leaf-range eval) is identity: a cast over axis names
    that don't exist would raise."""
    return jax.lax.pcast(x, tuple(axes), to="varying") if axes else x


def make_mesh(n_table: int | None = None, n_batch: int = 1,
              devices=None) -> Mesh:
    """Build a ("batch", "table") mesh over the available devices."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    if n_table is None:
        n_table = devices.size // n_batch
    assert n_table * n_batch == devices.size, \
        "mesh axes (%d x %d) must cover %d devices" % (
            n_batch, n_table, devices.size)
    return Mesh(devices.reshape(n_batch, n_table), ("batch", "table"))


def shard_table(table_i32: np.ndarray, mesh: Mesh):
    """Permute (bit-reversal) and row-shard a table over the "table" axis."""
    perm = expand.permute_table(np.asarray(table_i32, dtype=np.int32))
    sharding = NamedSharding(mesh, P("table", None))
    # from host memory, so each device receives its own shard only (a jnp
    # array here would first stage the whole table on device 0)
    return jax.device_put(perm, sharding)


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
        part, _ = jax.lax.scan(body, part0, xs_g)
        return acc + jax.lax.psum(part, axis_name), None

    acc, _ = jax.lax.scan(gbody, _pvary(zeros, tuple(outer_axes)), xs)
    return acc


@functools.partial(jax.jit,
                   static_argnames=("depth", "prf_method", "chunk_leaves",
                                    "mesh", "aes_impl", "psum_group"))
def eval_sharded(cw1, cw2, last, table_perm, *, depth: int, prf_method: int,
                 chunk_leaves: int, mesh: Mesh, aes_impl: str | None = None,
                 psum_group: int = 0):
    """Mesh-parallel fused DPF evaluation.

    Inputs as in ``expand.expand_and_contract``; ``table_perm`` must be
    row-sharded with ``shard_table``.  ``psum_group`` > 0 accumulates
    the share psum per group of that many frontier-subtree chunks
    instead of once at the end — each group's collective has no data
    dependency on the next group's PRF expansion, so an async backend
    overlaps ICI latency with compute (int32 adds wrap: grouping cannot
    change the result).  Returns [B, E] int32 shares, replicated over
    the "table" axis and sharded over "batch".
    """
    n_shards = mesh.shape["table"]
    n = table_perm.shape[0]
    shard_rows = n // n_shards
    assert shard_rows * n_shards == n

    def per_shard(cw1, cw2, last, tbl_shard):
        # tbl_shard: [n/shards, E] — this chip's BFS leaf range
        shard_ix = jax.lax.axis_index("table")
        out, psummed = _eval_leaf_range(
            cw1, cw2, last, tbl_shard, shard_ix * shard_rows,
            depth=depth, prf_method=prf_method,
            chunk_leaves=min(chunk_leaves, shard_rows),
            n_total=n, aes_impl=aes_impl, psum_group=psum_group,
            axis_name="table")
        return out if psummed else jax.lax.psum(out, "table")

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P("batch"), P("batch"), P("batch"), P("table", None)),
        out_specs=P("batch", None))
    return fn(cw1, cw2, last, table_perm)


def make_mesh_2d(n_table: int | None = None, n_byte: int = 1,
                 n_batch: int = 1, devices=None) -> Mesh:
    """Build a ("batch", "table", "byte") mesh: rows x entry-bytes over
    the host x chip grid.  ``n_byte=1`` degenerates to the 1D layout
    (and ``fingerprint.mesh_tag`` then emits the pre-2D tag, so tuned
    entries are shared).  Lay "table" on the ICI-adjacent dimension —
    the per-chunk psum rides it; the "byte" all_gather fires once per
    dispatch and tolerates the slower hops."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    if n_table is None:
        n_table = devices.size // (n_batch * n_byte)
    assert n_table * n_batch * n_byte == devices.size, \
        "mesh axes (%d x %d x %d) must cover %d devices" % (
            n_batch, n_table, n_byte, devices.size)
    return Mesh(devices.reshape(n_batch, n_table, n_byte),
                ("batch", "table", "byte"))


def shard_table_2d(table_i32: np.ndarray, mesh: Mesh):
    """Permute (bit-reversal) and block-shard a table over the
    ("table", "byte") plane: each chip holds one ``[rows/n_table,
    E/n_byte]`` block — contiguous BFS leaf rows x a contiguous slice
    of entry columns (int32 words; "byte axis" names the role, the
    unit is the table's column dtype).  This is what lets a table
    larger than ONE chip's HBM spread over the whole grid: per-chip
    bytes shrink by n_table x n_byte."""
    perm = expand.permute_table(np.asarray(table_i32, dtype=np.int32))
    if perm.shape[1] % mesh.shape["byte"]:
        raise ValueError(
            "entry columns (%d) must divide over %d byte shards"
            % (perm.shape[1], mesh.shape["byte"]))
    sharding = NamedSharding(mesh, P("table", "byte"))
    return jax.device_put(perm, sharding)


@functools.partial(jax.jit,
                   static_argnames=("depth", "prf_method", "chunk_leaves",
                                    "mesh", "aes_impl", "psum_group"))
def eval_sharded_2d(cw1, cw2, last, table_perm, *, depth: int,
                    prf_method: int, chunk_leaves: int, mesh: Mesh,
                    aes_impl: str | None = None, psum_group: int = 0):
    """Mesh-parallel fused DPF evaluation over a 2D row x entry-byte
    table layout (``shard_table_2d``).

    Each chip expands only its row shard's GGM subtrees (the PRF work
    is replicated along the "byte" axis — byte shards of the same row
    range need the same leaf bits) and contracts them against its
    ``[rows_shard, e_shard]`` block.  Partials combine in a two-phase
    reduction: (1) psum over "table" — blocks in one byte column cover
    disjoint row ranges of the SAME entry columns, and additive int32
    shares commute with partial dot products, so the sum is exact; with
    ``psum_group`` the psum fires per chunk group and overlaps the next
    group's PRF expansion exactly like the 1D path (the grouped carry
    stays varying over "byte": ``_scan_psum_groups(outer_axes=("batch",
    "byte"))``).  (2) concatenation along "byte" — byte shards hold
    DIFFERENT entry columns, so they concatenate, they never sum; the
    concat is expressed as the OUTPUT LAYOUT (``out_specs=P("batch",
    "byte")``), which costs no collective at all: the global [B, E]
    result is simply sharded over "byte" on the entry axis (and
    replicated over "table"), and a consumer that needs it replicated
    pays the gather on materialization."""
    n_shards = mesh.shape["table"]
    n = table_perm.shape[0]
    shard_rows = n // n_shards
    assert shard_rows * n_shards == n

    def per_shard(cw1, cw2, last, tbl_block):
        # tbl_block: [n/n_table, E/n_byte] — this chip's 2D block
        shard_ix = jax.lax.axis_index("table")
        out, psummed = _eval_leaf_range(
            cw1, cw2, last, tbl_block, shard_ix * shard_rows,
            depth=depth, prf_method=prf_method,
            chunk_leaves=min(chunk_leaves, shard_rows),
            n_total=n, aes_impl=aes_impl, psum_group=psum_group,
            axis_name="table", carry_axes=("batch", "table", "byte"))
        if not psummed:
            out = jax.lax.psum(out, "table")
        return out

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P("batch"), P("batch"), P("batch"), P("table", "byte")),
        out_specs=P("batch", "byte"))
    return fn(cw1, cw2, last, table_perm)


def _eval_leaf_range(cw1, cw2, last, tbl, row0, *, depth: int,
                     prf_method: int, chunk_leaves: int, n_total: int,
                     aes_impl: str | None = None, psum_group: int = 0,
                     axis_name: str | None = None,
                     carry_axes=("batch", "table")):
    """Expand only BFS leaves [row0, row0 + tbl.rows) and contract locally.

    Phase 1 walks root -> this shard's frontier; because the shard is a
    contiguous BFS range, its frontier nodes are a contiguous range at the
    frontier level, reachable by expanding all of phase 1 (cheap: width F)
    and slicing the local window with a dynamic slice on the node axis.

    Returns ``(out, psummed)``: with a valid ``psum_group`` (and an
    ``axis_name`` to reduce over) the scan psums every chunk group and
    ``out`` is already the mesh-wide sum (``psummed=True``); otherwise
    ``out`` is this shard's local partial and the caller applies the
    terminal psum.

    ``carry_axes`` types the scan carry for shard_map callers; pass
    ``()`` when calling OUTSIDE a mesh program (the multi-host cluster
    tier evaluates granules host-locally through exactly this path).
    """
    rows = tbl.shape[0]
    e = tbl.shape[1]
    bsz = last.shape[0]
    c = chunk_leaves
    f_local = rows // c                      # frontier nodes owned locally
    f_total = n_total // c                   # global frontier width
    f_levels = int(np.log2(f_total))

    seeds = expand.expand_levels(last[:, None, :], cw1, cw2, depth - 1,
                                 f_levels, prf_method, aes_impl)
    # take the local frontier window [row0/c, row0/c + f_local)
    node0 = row0 // c
    seeds = jax.lax.dynamic_slice_in_dim(seeds, node0, f_local, axis=1)

    def expand_subtree(node_seeds):
        s = expand.expand_levels(node_seeds[:, None, :], cw1, cw2,
                                 depth - 1 - f_levels, depth - f_levels,
                                 prf_method, aes_impl)
        return s[..., 0].astype(jnp.int32)

    tbl_chunks = tbl.reshape(f_local, c, e)
    if f_local == 1:
        return (expand._dot_i32(expand_subtree(seeds[:, 0, :]),
                                tbl_chunks[0]), False)

    frontier = jnp.moveaxis(seeds, 1, 0)  # [f_local, B, 4]

    def body(acc, xs):
        node_seeds, chunk = xs
        return acc + expand._dot_i32(expand_subtree(node_seeds), chunk), None

    zeros = jnp.zeros((bsz, e), dtype=jnp.int32)
    g = _valid_psum_group(psum_group, f_local) if axis_name else 0
    if not g:
        # inside shard_map the scan carry must be typed as varying over
        # the mesh axes (the body's output is), or the carry mismatches
        acc, _ = jax.lax.scan(body, _pvary(zeros, carry_axes),
                              (frontier, tbl_chunks))
        return acc, False
    return _scan_psum_groups(body, zeros, (
        frontier.reshape(f_local // g, g, bsz, 4),
        tbl_chunks.reshape(f_local // g, g, c, e)), axis_name,
        outer_axes=tuple(a for a in carry_axes if a != axis_name)), True


@functools.partial(jax.jit,
                   static_argnames=("depth", "prf_method", "chunk_leaves",
                                    "n_total", "aes_impl"))
def eval_leaf_range_local(cw1, cw2, last, tbl, row0, *, depth: int,
                          prf_method: int, chunk_leaves: int, n_total: int,
                          aes_impl: str | None = None):
    """Host-local partial evaluation of one contiguous BFS leaf range —
    the single-device (no-mesh) entry to ``_eval_leaf_range``.

    This is the multi-host cluster tier's per-host primitive
    (``parallel/cluster.py``): a host owning table rows
    [row0, row0 + tbl.rows) evaluates the FULL-domain key batch against
    only its rows and returns the [B, E] int32 partial share; partials
    from hosts covering disjoint ranges sum (int32 wrap) to the exact
    single-device answer, because additive secret shares commute with
    partial dot products.

    ``row0`` is a TRACED scalar (unlike the mesh path's
    ``shard_ix * shard_rows`` it arrives from the host), so one compiled
    program per (rows, batch) shape serves ANY granule — a re-shard
    after a host drop moves granules between hosts without recompiling.
    """
    out, _ = _eval_leaf_range(
        cw1, cw2, last, tbl, jnp.asarray(row0, dtype=jnp.int32),
        depth=depth, prf_method=prf_method, chunk_leaves=chunk_leaves,
        n_total=n_total, aes_impl=aes_impl, psum_group=0, axis_name=None,
        carry_axes=())
    return out


def shard_table_mixed(table_i32: np.ndarray, mesh: Mesh):
    """Digit-reverse-permute (radix-4 BFS order) and row-shard a table."""
    from ..core import radix4
    tbl = np.asarray(table_i32, dtype=np.int32)
    perm = radix4.mixed_reverse_indices(radix4.arities(tbl.shape[0]))
    sharding = NamedSharding(mesh, P("table", None))
    return jax.device_put(np.ascontiguousarray(tbl[perm]), sharding)


def shard_table_sqrt(table_i32: np.ndarray, mesh: Mesh):
    """Row-shard a NATURAL-order table over the "table" axis for the
    sqrt-N construction (the grid emits natural order — no permutation):
    a contiguous N/shards row block is exactly R/shards whole grid rows
    for any key split whose R divides over the shards."""
    sharding = NamedSharding(mesh, P("table", None))
    return jax.device_put(np.asarray(table_i32, dtype=np.int32), sharding)


@functools.partial(jax.jit,
                   static_argnames=("n", "prf_method", "chunk_leaves",
                                    "mesh", "aes_impl", "psum_group"))
def eval_sharded_mixed(cw1, cw2, last, table_perm, *, n: int,
                       prf_method: int, chunk_leaves: int, mesh: Mesh,
                       aes_impl: str | None = None, psum_group: int = 0):
    """Mesh-parallel radix-4 evaluation (the mixed-radix counterpart of
    ``eval_sharded``): each chip owns whole trailing radix-4 subtrees of
    the digit-reversed table, expands only those, psums partials —
    per ``psum_group`` chunks when set, terminally otherwise."""
    from ..core import radix4
    ars = radix4.arities(n)
    offs = radix4.cw_offsets(ars)
    n_shards = mesh.shape["table"]
    shard_rows = n // n_shards
    assert shard_rows * n_shards == n and shard_rows >= ars[-1]
    f_lv, c = radix4._suffix_chunk(ars, min(chunk_leaves, shard_rows))

    def _mixed_level(seeds, cw1_l, cw2_l, j):
        a = ars[j]
        return radix4._level_step_mixed(
            seeds, cw1_l[:, offs[j]:offs[j] + a, :],
            cw2_l[:, offs[j]:offs[j] + a, :], prf_method, a, aes_impl)

    def per_shard(cw1_l, cw2_l, last_l, tbl_shard):
        shard_ix = jax.lax.axis_index("table")
        rows = tbl_shard.shape[0]
        e = tbl_shard.shape[1]
        bsz = last_l.shape[0]
        f_local = rows // c

        seeds = last_l[:, None, :]
        for j in range(f_lv):
            seeds = _mixed_level(seeds, cw1_l, cw2_l, j)
        node0 = (shard_ix * rows) // c
        seeds = jax.lax.dynamic_slice_in_dim(seeds, node0, f_local, axis=1)

        def expand_subtree(node_seeds):
            s = node_seeds[:, None, :]
            for j in range(f_lv, len(ars)):
                s = _mixed_level(s, cw1_l, cw2_l, j)
            return s[..., 0].astype(jnp.int32)

        tbl_chunks = tbl_shard.reshape(f_local, c, e)
        if f_local == 1:
            out = expand._dot_i32(expand_subtree(seeds[:, 0, :]),
                                  tbl_chunks[0])
            return jax.lax.psum(out, "table")

        frontier = jnp.moveaxis(seeds, 1, 0)

        def body(acc, xs):
            node_seeds, chunk = xs
            return acc + expand._dot_i32(expand_subtree(node_seeds),
                                         chunk), None

        zeros = jnp.zeros((bsz, e), dtype=jnp.int32)
        g = _valid_psum_group(psum_group, f_local)
        if not g:
            out, _ = jax.lax.scan(body, _pvary(zeros, ("batch", "table")),
                                  (frontier, tbl_chunks))
            return jax.lax.psum(out, "table")
        return _scan_psum_groups(body, zeros, (
            frontier.reshape(f_local // g, g, bsz, 4),
            tbl_chunks.reshape(f_local // g, g, c, e)), "table")

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P("batch"), P("batch"), P("batch"), P("table", None)),
        out_specs=P("batch", None))
    return fn(cw1, cw2, last, table_perm)


class ShardedDPFServer:
    """Convenience server wrapper: one table, mesh-parallel evaluation.

    The multi-chip counterpart of ``DPF.eval_init``/``eval_tpu``, for
    all three constructions: ``scheme="logn"`` (binary GGM, or the
    radix-4 tree with ``radix=4``), ``scheme="sqrtn"`` (natural-order
    table, ``sqrtn.eval_sharded_sqrt``), or ``scheme="auto"`` — the
    measured per-shape winner from the scheme tuning cache, resolved at
    construction exactly like ``DPF(scheme="auto")``
    (``scheme_resolved_from`` says which path answered).

    Knob resolution (``resolved_eval_knobs``) follows the DPF
    precedence per knob: an EXPLICIT value (ctor argument, or the
    matching attribute assigned afterwards) wins; auto (None) fields
    take the MESH-tuned entry for this device x mesh split
    (``tune.cache.lookup_mesh_knobs``, populated by ``benchmark.py
    --multichip``), then the single-device tuned entry, then the static
    per-shard heuristic (chunk choices clamp against the SHARD row
    count, not the full table — a tuned single-device chunk must not
    exceed a shard's leaf range).
    """

    def __init__(self, table, mesh: Mesh | None = None, prf_method: int = 3,
                 batch_size: int = 512, radix: int = 2,
                 scheme: str = "logn", chunk_leaves: int | None = None,
                 row_chunk: int | None = None,
                 psum_group: int | None = None,
                 dot_impl: str | None = None,
                 kernel_impl: str | None = None):
        from ..core import keygen  # local import to avoid cycles
        from ..utils.config import check_construction
        self._keygen = keygen
        self.mesh = mesh if mesh is not None else make_mesh()
        tbl = np.asarray(table, dtype=np.int32)
        self.n, self.entry_size = tbl.shape
        assert self.n & (self.n - 1) == 0
        check_construction(scheme, radix)
        self.scheme_resolved_from = None
        if scheme == "auto":
            if radix == 4:
                raise ValueError(
                    "scheme='auto' resolves the whole construction "
                    "(scheme AND radix) from the tuning cache; leave "
                    "radix at 2")
            scheme, radix = self._resolve_auto_scheme(batch_size,
                                                     prf_method)
        self.scheme = scheme
        self.radix = radix
        self.depth = self.n.bit_length() - 1
        self.prf_method = prf_method
        self.batch_size = batch_size
        n_shards = self.mesh.shape["table"]
        if self.n % n_shards:
            raise ValueError(
                "table rows (%d) must divide over %d table shards"
                % (self.n, n_shards))
        self.n_byte = dict(self.mesh.shape).get("byte", 1)
        if self.n_byte > 1 and (self.scheme != "logn" or self.radix != 2):
            raise ValueError(
                "byte-axis (2D) sharding serves the binary GGM "
                "construction only (scheme=%r radix=%d)"
                % (self.scheme, self.radix))
        if self.scheme == "sqrtn":
            self.table_sharded = shard_table_sqrt(tbl, self.mesh)
        elif self.radix == 4:
            self.table_sharded = shard_table_mixed(tbl, self.mesh)
        elif self.n_byte > 1:
            self.table_sharded = shard_table_2d(tbl, self.mesh)
        else:
            self.table_sharded = shard_table(tbl, self.mesh)
        # the explicit knob layer: ctor args (None = auto); assigning
        # these attributes afterwards pins the knob the same way
        self.chunk = chunk_leaves
        self.row_chunk = row_chunk
        self.psum_group = psum_group
        self.dot_impl = dot_impl
        self.kernel_impl = kernel_impl  # sqrtn: "xla" | "pallas" | None
        self._tuned_memo = {}  # batch -> (mesh-tuned, single-tuned) dicts
        self._digits = None    # sqrt-N grid kernel's table, built once

    def _resolve_auto_scheme(self, batch_size: int, prf_method: int):
        """scheme="auto" -> the concrete construction, the DPF way:
        scheme tuning cache first (the ``benchmark.py --autotune-scheme``
        winner for this shape on this machine), else the conservative
        cold-cache heuristic."""
        from ..tune.cache import lookup_scheme
        rec = lookup_scheme(n=self.n, entry_size=self.entry_size,
                            batch=batch_size, prf_method=prf_method)
        if rec and rec.get("scheme") in ("logn", "sqrtn"):
            self.scheme_resolved_from = "cache"
        else:
            from ..tune.search import heuristic_scheme
            rec = heuristic_scheme(self.n)
            self.scheme_resolved_from = "heuristic"
        return rec["scheme"], int(rec.get("radix") or 2)

    @property
    def shard_rows(self) -> int:
        """Table rows each "table"-axis shard owns."""
        return self.n // self.mesh.shape["table"]

    def _decode_batch(self, keys):
        """Vectorized ingest: wire keys -> packed batch validated
        against this server's table (shared with the serving engine)."""
        if not len(keys):
            raise ValueError("empty key batch")
        if self.scheme == "sqrtn":
            from ..core import sqrtn
            pk = sqrtn.decode_sqrt_keys_batched(keys)
        elif self.radix == 4:
            from ..core import radix4
            pk = radix4.decode_mixed_keys_batched(keys)
        else:
            pk = self._keygen.decode_keys_batched(keys)
        if pk.n != self.n:
            raise ValueError("key generated for n=%d but table has n=%d"
                             % (pk.n, self.n))
        return pk

    def resolved_eval_knobs(self, batch: int) -> dict:
        """Concrete mesh-program knobs for one dispatch batch size:
        explicit attribute > mesh-tuned (this device x mesh split,
        ``lookup_mesh_knobs``) > single-device tuned > heuristic.
        Chunk knobs resolve against the PER-SHARD row count (the shard
        owns ``shard_rows`` leaves / R/shards grid rows, not N).

        scheme='sqrtn': ``row_chunk`` may come back None — the dispatch
        resolves it against the decoded batch's key split
        (``sqrtn.clamp_row_chunk``), which only the keys know."""
        from ..ops import matmul128
        from ..tune.cache import lookup_eval_knobs, lookup_mesh_knobs
        from ..tune.fingerprint import mesh_tag
        explicit = {"chunk_leaves": self.chunk,
                    "row_chunk": self.row_chunk,
                    "psum_group": self.psum_group,
                    "dot_impl": self.dot_impl,
                    "kernel_impl": self.kernel_impl}
        fields = (("row_chunk", "psum_group", "dot_impl", "kernel_impl")
                  if self.scheme == "sqrtn"
                  else ("chunk_leaves", "psum_group", "dot_impl"))
        if all(explicit[f] is not None for f in fields):
            # fully pinned (the mesh tuner measuring a candidate): no
            # cache reads — a stale entry must not leak into the knobs
            tuned = single = {}
        else:
            # the cache lookups are memoized per batch (this is the
            # serving hot path); the process-global fallbacks below are
            # re-read every call so set_dot_impl stays live, matching
            # DPF.resolved_eval_knobs
            memo = self._tuned_memo.get(batch)
            if memo is None:
                memo = (lookup_mesh_knobs(
                            n=self.n, entry_size=self.entry_size,
                            batch=batch, prf_method=self.prf_method,
                            scheme=self.scheme, radix=self.radix,
                            mesh=mesh_tag(self.mesh)) or {},
                        lookup_eval_knobs(
                            n=self.n, entry_size=self.entry_size,
                            batch=batch, prf_method=self.prf_method,
                            scheme=self.scheme, radix=self.radix) or {})
                self._tuned_memo[batch] = memo
            tuned, single = memo

        def pick(field, fallback=None):
            if explicit[field] is not None:
                return explicit[field]
            v = tuned.get(field, single.get(field))
            return v if v is not None else fallback

        out = {"psum_group": int(pick("psum_group", 0) or 0),
               "dot_impl": pick("dot_impl", matmul128.default_impl())}
        if self.scheme == "sqrtn":
            out["row_chunk"] = pick("row_chunk")
            # kernel_impl with provenance, the DPF rule: explicit >
            # tuned > "xla"; a resolved "pallas" without Pallas/TPU
            # here degrades to the xla scan instead of raising
            if explicit["kernel_impl"] is not None:
                kernel, kernel_from = explicit["kernel_impl"], "config"
            elif tuned.get("kernel_impl",
                           single.get("kernel_impl")) is not None:
                kernel = tuned.get("kernel_impl",
                                   single.get("kernel_impl"))
                kernel_from = "tuned"
            else:
                kernel, kernel_from = "xla", "heuristic"
            if kernel == "pallas":
                from ..utils.compat import has_pallas_sqrt_kernel
                if not has_pallas_sqrt_kernel():
                    from ..utils.profiling import note_swallowed
                    note_swallowed(
                        "sharded.sqrt_kernel_unavailable",
                        RuntimeError(
                            "kernel_impl='pallas' (from %s) but Pallas/"
                            "TPU is unavailable here" % kernel_from))
                    kernel, kernel_from = "xla", "degraded"
            if (out["row_chunk"] is not None
                    and explicit["row_chunk"] is None
                    and (tuned.get("kernel_impl",
                                   single.get("kernel_impl", "xla"))
                         or "xla") != kernel):
                # a tuned row_chunk rides only with ITS kernel
                out["row_chunk"] = None
            out["kernel_impl"] = kernel
            out["kernel_resolved_from"] = kernel_from
            return out
        if explicit["chunk_leaves"] is not None:
            out["chunk_leaves"] = min(int(explicit["chunk_leaves"]),
                                      self.shard_rows)
        else:
            # clamp against the shard's own leaf range: tuned entries
            # (mesh or single-device) key on the table shape, and a
            # single-device chunk can exceed what one shard holds
            out["chunk_leaves"] = expand.clamp_chunk(
                tuned.get("chunk_leaves", single.get("chunk_leaves")),
                self.shard_rows, batch)
        return out

    def _dispatch_packed(self, pk):
        """Pad to the mesh "batch" axis and dispatch WITHOUT a host sync
        (async, for the serving engine's host/device overlap).  The
        returned device array may carry pad rows — callers trim to the
        real batch."""
        from ..core import prf as _prf
        pk = pk.pad_to(pk.batch
                       + (-pk.batch) % max(self.mesh.shape["batch"], 1))
        kn = self.resolved_eval_knobs(pk.batch)
        if self.scheme == "sqrtn":
            from ..core import sqrtn
            n_shards = self.mesh.shape["table"]
            if pk.n_codewords % n_shards:
                raise ValueError(
                    "sqrt-N key split R=%d does not divide over %d "
                    "table shards" % (pk.n_codewords, n_shards))
            rc = kn["row_chunk"]
            if self.row_chunk is None:
                # harden a tuned/absent row_chunk against THIS batch's
                # key split; an explicit pin passes through so an
                # invalid value raises instead of silently measuring
                # the heuristic (the DPF dispatch rule)
                rc = sqrtn.clamp_row_chunk(
                    rc, pk.n_codewords // n_shards, pk.n_keys, pk.batch)
            kernel = kn.get("kernel_impl", "xla")
            if kernel == "pallas":
                # the shape-level gate only the decoded batch answers:
                # per-SHARD rows must fit the grid kernel (blk prf ids
                # need R/shards % 4 == 0); degrade with provenance
                from ..ops.pallas_sqrt import pallas_sqrt_unsupported
                reason = pallas_sqrt_unsupported(
                    self.prf_method, pk.n_codewords // n_shards)
                if reason is not None:
                    from ..utils.profiling import note_swallowed
                    note_swallowed("sharded.sqrt_kernel_unsupported",
                                   ValueError(reason))
                    kernel = "xla"
            table = self.table_sharded
            if kernel == "pallas":
                if self._digits is None:  # [4, N, E] int8, rows sharded
                    from ..ops.pallas_level import table_digits
                    self._digits = table_digits(table)
                table = self._digits
            return sqrtn.eval_sharded_sqrt(
                pk.seeds, pk.cw1, pk.cw2, table,
                prf_method=self.prf_method, mesh=self.mesh,
                dot_impl=kn["dot_impl"], row_chunk=rc,
                psum_group=kn["psum_group"], kernel_impl=kernel)
        if self.radix == 4:
            return eval_sharded_mixed(
                pk.cw1, pk.cw2, pk.last, self.table_sharded, n=self.n,
                prf_method=self.prf_method,
                chunk_leaves=kn["chunk_leaves"], mesh=self.mesh,
                aes_impl=_prf._aes_pair_impl(),
                psum_group=kn["psum_group"])
        if self.n_byte > 1:
            return eval_sharded_2d(
                pk.cw1, pk.cw2, pk.last, self.table_sharded,
                depth=self.depth, prf_method=self.prf_method,
                chunk_leaves=kn["chunk_leaves"], mesh=self.mesh,
                aes_impl=_prf._aes_pair_impl(),
                psum_group=kn["psum_group"])
        return eval_sharded(pk.cw1, pk.cw2, pk.last, self.table_sharded,
                            depth=self.depth, prf_method=self.prf_method,
                            chunk_leaves=kn["chunk_leaves"],
                            mesh=self.mesh,
                            aes_impl=_prf._aes_pair_impl(),
                            psum_group=kn["psum_group"])

    def eval(self, keys) -> np.ndarray:
        pk = self._decode_batch(keys)
        return np.asarray(self._dispatch_packed(pk))[:pk.batch]

    def serving_engine(self, **kwargs):
        """Mesh-path ``ServingEngine`` (serve/engine.py) over this server."""
        from ..serve import ServingEngine
        return ServingEngine(self, **kwargs)
