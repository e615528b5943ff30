"""Multi-chip DPF evaluation: table row-sharding + batch sharding on a mesh.

The reference has no multi-GPU path at all (SURVEY.md §2.4); this module is
where the TPU build goes beyond it.  Two orthogonal parallel axes map the
workload onto a ``jax.sharding.Mesh``:

* **"table" axis (the TP analogue)** — the bit-reverse-permuted table is
  row-sharded; each chip owns a contiguous range of BFS leaf positions,
  i.e. a set of whole GGM frontier subtrees.  Every chip replicates the
  cheap phase-1 expansion (root -> frontier, O(B*F)), expands only its own
  subtrees, contracts against its local table rows
  (``core.expand.eval_leaf_range``: the Pallas subtree kernel or the xla
  scan, by the one-chip kernel rule), and the partial int32 outputs are
  summed with ``psum`` over ICI.  Valid because additive secret shares
  commute with partial dot products.  ``place_table`` lays each chip's
  rows out from the host table directly, in the layout its kernel reads.
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

import collections
import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ..core import expand, u128
from ..core.expand import _pvary, _scan_psum_groups, _valid_psum_group
from ..obs.tracer import annotate, span
from ..ops.pallas_level import table_digits, table_digits_t


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


#: int32 bytes of table rows gathered on the host and sent to the chips
#: at a time by ``place_table``, by how many threads, and how many such
#: blocks may be gathered ahead of the chips
PLACE_BLOCK_BYTES = 256 << 20
PLACE_THREADS = 8
PLACE_AHEAD = 8


@functools.partial(jax.jit, static_argnames=("digits",),
                   donate_argnums=(0,))
def _write_rows(buf, rows, row0, digits: bool):
    """``buf`` with ``rows`` written from row ``row0`` on, in place
    (``buf`` is donated): [rows, E] int32 into an [N, E] buffer, or with
    ``digits`` their [4, E, rows] int8 planes into a [4, E, N] one."""
    if digits:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, table_digits_t(rows), row0, axis=2)
    return jax.lax.dynamic_update_slice_in_dim(buf, rows, row0, axis=0)


def _zeros_on(shape, dtype, device):
    """A zero buffer made on ``device`` itself: ``jnp.zeros(device=d)``
    fills it on the default device and copies it over, which on a
    four-chip host put three table-sized buffers on chip 0 at once."""
    return jax.jit(functools.partial(jnp.zeros, shape, dtype),
                   out_shardings=SingleDeviceSharding(device))()


def place_table(table, mesh: Mesh, chunk: int | None = None):
    """Lay a host table out over the mesh one chip's block at a time,
    with no permuted copy of the whole table on the host.

    ``chunk=None``: the xla scan's table, [N, E] int32 rows in BFS
    (bit-reversed) order.  ``chunk=C``: the subtree kernel's, [4, E, N]
    int8 digit planes (``table_digits_t``) with each subtree of C leaves
    in the kernel's leaf order: ``ops.pallas_level.subtree_digits`` of
    the permuted table, its last two axes swapped.
    Rows shard over "table", entry columns over "byte" where the mesh
    has that axis, and every "batch" replica gets its own copy.

    With F = N / C subtrees, BFS position g*C + r holds original row
    bitrev(r)*F + bitrev(g), and the kernel's leaf q of subtree g row
    q*F + bitrev(g): a chip's block is a strided gather of the original
    rows.  It is gathered in blocks of about ``PLACE_BLOCK_BYTES`` by a
    few threads, at most ``PLACE_AHEAD`` blocks ahead, each written into
    a buffer allocated on its chips once: the host holds the table and a
    few blocks, a chip its block and one int32 block in flight, never an
    int32 copy next to the digits."""
    tbl = np.asarray(table, dtype=np.int32)
    n, e = tbl.shape
    if e % dict(mesh.shape).get("byte", 1):
        raise ValueError("entry columns (%d) must divide over %d byte "
                         "shards" % (e, mesh.shape["byte"]))
    digits = chunk is not None
    # any power of two within a shard gives the same BFS order
    c = chunk if digits else min(n // mesh.shape["table"], 1 << 12)
    f = n // c
    rev_f = u128.bit_reverse_indices(f)
    leaf = np.arange(c) if digits else u128.bit_reverse_indices(c)
    cols = "byte" if "byte" in mesh.axis_names else None
    shape, spec = ((4, e, n), P(None, cols, "table")) if digits else \
        ((n, e), P("table", cols))
    sharding = NamedSharding(mesh, spec)
    blocks = {}   # (row range, column range) -> the chips that hold it
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        r, k = (idx[2], idx[1]) if digits else (idx[0], idx[1])
        r, k = r.indices(n), k.indices(e)
        blocks.setdefault((r[:2], k[:2]), []).append(dev)
    per_block = max(1, PLACE_BLOCK_BYTES // (4 * e * c))  # subtrees
    bufs, tasks = {}, []
    for ((r0, r1), (c0, c1)), devs in blocks.items():
        for d in devs:
            bufs[d] = _zeros_on(
                (4, c1 - c0, r1 - r0) if digits else (r1 - r0, c1 - c0),
                jnp.int8 if digits else jnp.int32, d)
        tasks += [(devs, r0, c0, c1, g0, min(g0 + per_block, r1 // c))
                  for g0 in range(r0 // c, r1 // c, per_block)]

    def gather(task):
        _, _, c0, c1, g0, g1 = task
        idx = (leaf[None, :] * f + rev_f[g0:g1, None]).reshape(-1)
        rows = np.take(tbl, idx, axis=0)   # releases the GIL, unlike [idx]
        return rows if (c0, c1) == (0, e) else rows[:, c0:c1]

    with ThreadPoolExecutor(PLACE_THREADS) as ex:
        ahead = collections.deque(
            (t, ex.submit(gather, t)) for t in tasks[:PLACE_AHEAD])
        later = iter(tasks[PLACE_AHEAD:])
        while ahead:
            (devs, r0, _, _, g0, _), rows = ahead.popleft()
            rows = rows.result()
            nxt = next(later, None)
            if nxt is not None:
                ahead.append((nxt, ex.submit(gather, nxt)))
            for d in devs:
                bufs[d] = _write_rows(bufs[d], jax.device_put(rows, d),
                                      g0 * c - r0, digits=digits)
            # the block's host rows are free once its chips hold them
            jax.block_until_ready([bufs[d] for d in devs])
    return jax.make_array_from_single_device_arrays(
        shape, sharding, [bufs[d] for d in bufs])


@functools.partial(jax.jit,
                   static_argnames=("depth", "prf_method", "chunk_leaves",
                                    "mesh", "aes_impl", "psum_group",
                                    "kernel_impl"))
def eval_sharded(cw1, cw2, last, table_perm, *, depth: int, prf_method: int,
                 chunk_leaves: int, mesh: Mesh, aes_impl: str | None = None,
                 psum_group: int = 0, kernel_impl: str = "xla"):
    """Mesh-parallel fused DPF evaluation.

    Inputs as in ``expand.expand_and_contract``; ``table_perm`` is laid
    out by ``place_table``: int32 BFS rows for ``kernel_impl="xla"``,
    the subtree kernel's digit planes (``chunk=chunk_leaves``) for
    ``"pallas"``.  Each chip runs ``expand.eval_leaf_range`` over its
    own subtrees, and the partials meet in one ``psum`` (``dpf.psum``).
    ``psum_group`` > 0 (xla scan only) psums per group of that many
    subtree chunks instead, so an async backend overlaps ICI latency
    with the next group's PRF expansion (int32 adds wrap: grouping
    cannot change the result).  Returns [B, E] int32 shares, replicated
    over the "table" axis and sharded over "batch".
    """
    return _mesh_eval(cw1, cw2, last, table_perm, depth=depth,
                      prf_method=prf_method, chunk_leaves=chunk_leaves,
                      mesh=mesh, aes_impl=aes_impl, psum_group=psum_group,
                      kernel_impl=kernel_impl, cols=None)


def _mesh_eval(cw1, cw2, last, table_perm, *, depth, prf_method,
               chunk_leaves, mesh, aes_impl, psum_group, kernel_impl, cols):
    """The binary mesh program: rows over "table", entry columns over
    ``cols`` (None, or "byte" for the 2D layout)."""
    n_shards = mesh.shape["table"]
    n = table_perm.shape[-1 if kernel_impl == "pallas" else 0]
    shard_rows = n // n_shards
    assert shard_rows * n_shards == n

    def per_shard(cw1, cw2, last, tbl_shard):
        # this chip's BFS leaf range: [rows, E] int32 or [4, E, rows] digits
        shard_ix = jax.lax.axis_index("table")
        out, psummed = expand.eval_leaf_range(
            cw1, cw2, last, tbl_shard, shard_ix * shard_rows,
            depth=depth, prf_method=prf_method,
            chunk_leaves=min(chunk_leaves, shard_rows), n_total=n,
            kernel_impl=kernel_impl, aes_impl=aes_impl,
            psum_group=psum_group, axis_name="table",
            carry_axes=("batch", "table") + ((cols,) if cols else ()))
        if psummed:
            return out
        with jax.named_scope("dpf.psum"):
            return jax.lax.psum(out, "table")

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P("batch"), P("batch"), P("batch"),
                  P(None, cols, "table") if kernel_impl == "pallas"
                  else P("table", cols)),
        out_specs=P("batch", cols),
        # a pallas_call's out_shape carries no mesh-axis typing
        check_vma=kernel_impl != "pallas")
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


@functools.partial(jax.jit,
                   static_argnames=("depth", "prf_method", "chunk_leaves",
                                    "mesh", "aes_impl", "psum_group",
                                    "kernel_impl"))
def eval_sharded_2d(cw1, cw2, last, table_perm, *, depth: int,
                    prf_method: int, chunk_leaves: int, mesh: Mesh,
                    aes_impl: str | None = None, psum_group: int = 0,
                    kernel_impl: str = "xla"):
    """Mesh-parallel fused DPF evaluation over a 2D row x entry-byte
    table layout (``place_table`` on a ``make_mesh_2d`` mesh).

    Each chip expands only its row shard's GGM subtrees (the PRF work
    is replicated along the "byte" axis — byte shards of the same row
    range need the same leaf bits) and contracts them against its
    ``[rows_shard, e_shard]`` block (``expand.eval_leaf_range``, either
    kernel).  Partials combine in a two-phase
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
    return _mesh_eval(cw1, cw2, last, table_perm, depth=depth,
                      prf_method=prf_method, chunk_leaves=chunk_leaves,
                      mesh=mesh, aes_impl=aes_impl, psum_group=psum_group,
                      kernel_impl=kernel_impl, cols="byte")


@functools.partial(jax.jit,
                   static_argnames=("depth", "prf_method", "chunk_leaves",
                                    "n_total", "aes_impl"))
def eval_leaf_range_local(cw1, cw2, last, tbl, row0, *, depth: int,
                          prf_method: int, chunk_leaves: int, n_total: int,
                          aes_impl: str | None = None):
    """Host-local partial evaluation of one contiguous BFS leaf range —
    the single-device (no-mesh) entry to ``expand.eval_leaf_range``.

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
    out, _ = expand.eval_leaf_range(
        cw1, cw2, last, tbl, jnp.asarray(row0, dtype=jnp.int32),
        depth=depth, prf_method=prf_method, chunk_leaves=chunk_leaves,
        n_total=n_total, aes_impl=aes_impl, carry_axes=())
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
    exceed a shard's leaf range).  The GGM kernel follows the one-chip
    rule (``ops.pallas_level.heuristic_kernel``): on a TPU, binary
    Salsa20/ChaCha20 runs the subtree kernel on every shard.

    Binary GGM places at construction the table the constructor's
    ``batch_size`` resolves (``place_table``): on the subtree kernel's
    path only its int8 digit planes, never an int32 copy.  The host
    table (the caller's array) stays referenced, for a dispatch that
    resolves the other kernel's layout.
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
        # the explicit knob layer: ctor args (None = auto); assigning
        # these attributes afterwards pins the knob the same way
        self.chunk = chunk_leaves
        self.row_chunk = row_chunk
        self.psum_group = psum_group
        self.dot_impl = dot_impl
        self.kernel_impl = kernel_impl  # "xla" | "pallas" | None
        self._tuned_memo = {}  # batch -> (mesh-tuned, single-tuned) dicts
        self._digits = None    # sqrt-N grid kernel's table, built once
        self._layouts = {}     # binary GGM: subtree chunk or None -> table
        if self.scheme == "sqrtn":
            self.table_sharded = shard_table_sqrt(tbl, self.mesh)
        elif self.radix == 4:
            self.table_sharded = shard_table_mixed(tbl, self.mesh)
        else:
            # binary GGM: the layout the constructor's batch resolves is
            # placed now; the host rows (the caller's array, not a copy)
            # stay referenced for a dispatch that resolves the other
            # kernel's layout
            self._host_table = tbl
            self.table_sharded = self._layout(
                self.resolved_eval_knobs(self._mesh_batch(batch_size)))

    def _mesh_batch(self, batch: int) -> int:
        """A dispatch's batch padded to the mesh "batch" axis."""
        return batch + (-batch) % max(self.mesh.shape["batch"], 1)

    def _layout(self, kn: dict):
        """The binary-GGM table as the resolved kernel reads it
        (``place_table``): int32 BFS rows for the xla scan, the subtree
        kernel's digit planes for its chunk.  Placed once per layout;
        on the subtree kernel's path no int32 table is placed at all."""
        key = kn["chunk_leaves"] if kn["kernel_impl"] == "pallas" else None
        if key not in self._layouts:
            self._layouts[key] = place_table(self._host_table, self.mesh,
                                             chunk=key)
        return self._layouts[key]

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
                  else ("chunk_leaves", "psum_group", "dot_impl",
                        "kernel_impl"))
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
            kernel, kernel_from = self._pick_kernel(explicit, tuned,
                                                    single, "xla")
            if kernel == "pallas":
                kernel, kernel_from = self._degrade(kernel, kernel_from,
                                                    "sqrt_kernel")
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
        # GGM: the kernel by the single-chip rule (explicit > mesh-tuned
        # > tuned > ``heuristic_kernel``); a "pallas" from a cache
        # written where the subtree kernel compiles degrades to the scan
        # here, an explicit one passes through
        from ..ops.pallas_level import heuristic_kernel, pallas_chunk_leaves
        kernel, kernel_from = self._pick_kernel(
            explicit, tuned, single,
            heuristic_kernel(self.prf_method, self.radix))
        if kernel == "pallas" and kernel_from == "tuned":
            kernel, kernel_from = self._degrade(kernel, kernel_from,
                                                "ggm_kernel")
        out["kernel_impl"] = kernel
        out["kernel_resolved_from"] = kernel_from
        tuned_chunk = tuned.get("chunk_leaves", single.get("chunk_leaves"))
        # a tuned chunk rides only with the kernel it was timed on (an
        # entry naming none was timed on the xla scan)
        if (tuned.get("kernel_impl", single.get("kernel_impl"))
                or "xla") != kernel:
            tuned_chunk = None
        if explicit["chunk_leaves"] is not None:
            out["chunk_leaves"] = min(int(explicit["chunk_leaves"]),
                                      self.shard_rows)
        elif kernel == "pallas":
            # bounded by the kernel's per-tile VMEM state, within a shard
            out["chunk_leaves"] = min(
                int(tuned_chunk or pallas_chunk_leaves(self.shard_rows)),
                self.shard_rows)
        else:
            # clamp against the shard's own leaf range: tuned entries
            # (mesh or single-device) key on the table shape, and a
            # single-device chunk can exceed what one shard holds
            out["chunk_leaves"] = expand.clamp_chunk(
                tuned_chunk, self.shard_rows, batch)
        return out

    @staticmethod
    def _pick_kernel(explicit, tuned, single, heuristic: str):
        """(kernel, provenance): explicit > mesh-tuned > single-device
        tuned > ``heuristic``."""
        if explicit["kernel_impl"] is not None:
            return explicit["kernel_impl"], "config"
        kernel = tuned.get("kernel_impl", single.get("kernel_impl"))
        if kernel is not None:
            return kernel, "tuned"
        return heuristic, "heuristic"

    @staticmethod
    def _degrade(kernel: str, kernel_from: str, what: str):
        """A resolved "pallas" where Pallas/TPU is unavailable: the xla
        scan, with provenance "degraded" (counted by note_swallowed)."""
        from ..utils.compat import has_pallas_sqrt_kernel
        if has_pallas_sqrt_kernel():
            return kernel, kernel_from
        from ..utils.profiling import note_swallowed
        note_swallowed("sharded.%s_unavailable" % what, RuntimeError(
            "kernel_impl='pallas' (from %s) but Pallas/TPU is "
            "unavailable here" % kernel_from))
        return "xla", "degraded"

    def _dispatch_packed(self, pk):
        """Pad to the mesh "batch" axis and dispatch WITHOUT a host sync
        (async, for the serving engine's host/device overlap).  The
        returned device array may carry pad rows — callers trim to the
        real batch."""
        from ..core import prf as _prf
        pk = pk.pad_to(self._mesh_batch(pk.batch))
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
            annotate(kernel=kernel)
            table = self.table_sharded
            if kernel == "pallas":
                if self._digits is None:  # [4, N, E] int8, rows sharded
                    self._digits = table_digits(table)
                table = self._digits
            return sqrtn.eval_sharded_sqrt(
                pk.seeds, pk.cw1, pk.cw2, table,
                prf_method=self.prf_method, mesh=self.mesh,
                dot_impl=kn["dot_impl"], row_chunk=rc,
                psum_group=kn["psum_group"], kernel_impl=kernel)
        kernel = kn["kernel_impl"]
        annotate(kernel=kernel)
        if self.radix == 4:
            if kernel != "xla":
                raise ValueError("the radix-4 mesh path runs the xla scan "
                                 "only (kernel_impl=%r)" % kernel)
            return eval_sharded_mixed(
                pk.cw1, pk.cw2, pk.last, self.table_sharded, n=self.n,
                prf_method=self.prf_method,
                chunk_leaves=kn["chunk_leaves"], mesh=self.mesh,
                aes_impl=_prf._aes_pair_impl(),
                psum_group=kn["psum_group"])
        from ..ops.pallas_level import has_subtree_core, tiled_levels
        if kernel not in ("xla", "pallas") or (
                kernel == "pallas" and not has_subtree_core(self.prf_method)):
            raise ValueError(
                "kernel_impl=%r: the binary mesh path runs the xla scan or "
                "the subtree kernel (Salsa20/ChaCha20 and their block "
                "forms), not prf %d" % (kernel, self.prf_method))
        if kernel == "pallas":
            levels = kn["chunk_leaves"].bit_length() - 1
            annotate(tiled_levels=tiled_levels((2,) * levels))
        fn = eval_sharded_2d if self.n_byte > 1 else eval_sharded
        return fn(pk.cw1, pk.cw2, pk.last, self._layout(kn),
                  depth=self.depth, prf_method=self.prf_method,
                  chunk_leaves=kn["chunk_leaves"], mesh=self.mesh,
                  aes_impl=_prf._aes_pair_impl(),
                  psum_group=kn["psum_group"], kernel_impl=kernel)

    def eval(self, keys) -> np.ndarray:
        """[len(keys), E] int32 shares of one key batch, in the spans
        ``mesh_eval`` > ``.decode`` / ``.dispatch`` (with the resolved
        ``kernel``) / ``.fetch`` (the device's wait and the copy back)."""
        with span("mesh_eval", batch=len(keys)):
            with span("mesh_eval.decode", batch=len(keys)):
                pk = self._decode_batch(keys)
            with span("mesh_eval.dispatch"):
                dev = self._dispatch_packed(pk)
            with span("mesh_eval.fetch"):
                return np.asarray(dev)[:pk.batch]

    def serving_engine(self, **kwargs):
        """Mesh-path ``ServingEngine`` (serve/engine.py) over this server."""
        from ..serve import ServingEngine
        return ServingEngine(self, **kwargs)
