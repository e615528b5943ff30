"""Compile the main path's kernels for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (``on-chip-measurement`` guide, section 2).
That catches what interpret mode cannot: block shapes Mosaic refuses,
scoped-VMEM overflow, programs that do not fit HBM.  Nothing runs, so
these tests say nothing about results (the interpret-mode parity tests
do) or times (only the chip does).

Only this file describes the chip.  The topology is built inside a
module-scoped fixture, never at import: one process at a time may load
the TPU library, and every xdist worker imports every test file.  The
persistent compilation cache is off around the compiles (an entry
written for a described device cannot be read back without one).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

B = 512          # the reference's batch
N = 1 << 20      # the reference's largest table
E = 16           # int32 words per entry
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def compile_tpu(topo):
    """compile(fn, *shapes) -> optimized HLO text, on one described chip
    (``sharding=None``) or on the shardings the shapes carry."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [s if s.sharding is not None
                else jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _level_step():
    from dpf_tpu.ops.pallas_level import _chacha_level_step_impl
    return (_chacha_level_step_impl,
            S((B, 4096, 4), U32), S((B, 2, 4), U32), S((B, 2, 4), U32))


def _subtree(prf):
    from dpf_tpu.ops.pallas_level import (_subtree_contract_pallas_impl,
                                          pallas_chunk_leaves)
    depth = N.bit_length() - 1
    c = pallas_chunk_leaves(N)
    fl = depth - (c.bit_length() - 1)

    def fn(f, a, b, t):
        return _subtree_contract_pallas_impl(
            f, a, b, t, depth=depth, f_levels=fl, prf_method=prf)
    return (fn, S((B, N // c, 4), U32), S((B, 64, 4), U32),
            S((B, 64, 4), U32), S((N, E), I32))


def _mixed(prf):
    from dpf_tpu.core import radix4

    def fn(cw1, cw2, last, t):
        return radix4._expand_contract_mixed_pallas_jit(
            cw1, cw2, last, t, n=N, prf_method=prf, interpret=False)
    return (fn, S((B, 64, 4), U32), S((B, 64, 4), U32), S((B, 4), U32),
            S((N, E), I32))


def _sqrt(prf, order):
    from dpf_tpu.core import sqrtn
    from dpf_tpu.ops.pallas_sqrt import _sqrt_grid_contract_impl
    k, r = sqrtn.default_split(N)
    b = B if order == "bk" else 32    # "kb" needs one key tile

    def fn(s, c1, c2, t):
        return _sqrt_grid_contract_impl(s, c1, c2, t, 0, prf_method=prf,
                                        grid_order=order)
    return (fn, S((b, k, 4), U32), S((b, r, 4), U32), S((b, r, 4), U32),
            S((N, E), I32))


def _planes(arity):
    from dpf_tpu.ops.aes_planes import _aes_level_step_impl

    def fn(s, c1, c2):
        return _aes_level_step_impl(s, c1, c2, arity=arity)
    return (fn, S((B, 256, 4), U32), S((B, arity, 4), U32),
            S((B, arity, 4), U32))


# prf ids: 1 Salsa20, 2 ChaCha20, 3 AES-128, 4 Salsa20-BLK, 5 ChaCha20-BLK
PALLAS_CASES = {
    "level_step.chacha": _level_step,
    "subtree.chacha": lambda: _subtree(2),
    "subtree.salsa": lambda: _subtree(1),
    "subtree.chacha_blk": lambda: _subtree(5),
    "subtree.salsa_blk": lambda: _subtree(4),
    "mixed.chacha": lambda: _mixed(2),
    "mixed.chacha_blk": lambda: _mixed(5),
    "sqrt.chacha.bk": lambda: _sqrt(2, "bk"),
    "sqrt.chacha.kb": lambda: _sqrt(2, "kb"),
    "sqrt.salsa.bk": lambda: _sqrt(1, "bk"),
    "sqrt.salsa.kb": lambda: _sqrt(1, "kb"),
    "planes.aes.a2": lambda: _planes(2),
    "planes.aes.a4": lambda: _planes(4),
}


# each case's kernel, by the stable name its pallas_call gives it
KERNEL_NAMES = {"level_step": "dpf_chacha_level",
                "subtree": "dpf_subtree_contract",
                "mixed": "dpf_subtree_contract",
                "sqrt": "dpf_sqrt_grid_contract",
                "planes": "dpf_aes_level"}


def _custom_calls(text):
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln]


@pytest.mark.parametrize("case", sorted(PALLAS_CASES))
def test_pallas_kernel_compiles_for_v5e(compile_tpu, case):
    fn, *shapes = PALLAS_CASES[case]()
    calls = _custom_calls(compile_tpu(fn, *shapes))
    assert calls
    name = KERNEL_NAMES[case.split(".")[0]]
    assert all(name in ln for ln in calls), (name, calls[0][:200])


V5E_SCOPED_VMEM = 16 << 20   # a v5e kernel's default scoped-VMEM limit


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                yield from _pallas_calls(sub)


def _vmem_bytes(fn, *shapes):
    """(scratch, total) VMEM bytes of the one kernel ``fn`` traces: its
    scratch operands, and those plus two buffers of every pipelined
    block, each padded to the chip's (sublane, 128-lane) tiles."""
    (eqn,) = _pallas_calls(jax.make_jaxpr(fn)(*shapes).jaxpr)
    gm = eqn.params["grid_mapping"]

    def padded(aval):
        shape, size = list(aval.shape), aval.dtype.itemsize
        if len(shape) >= 2:
            sub = 8 * 4 // size
            shape[-2] = -(-shape[-2] // sub) * sub
        shape[-1] = -(-shape[-1] // 128) * 128
        return int(np.prod(shape)) * size

    scratch = sum(padded(a) for a in gm.scratch_avals)
    blocks = sum(padded(bm.block_aval) for bm in gm.block_mappings)
    return scratch, scratch + 2 * blocks


@pytest.mark.parametrize("case,tb", [("subtree.chacha", 8),
                                     ("mixed.chacha", 32),
                                     ("mixed.chacha_blk", 32)])
def test_subtree_kernel_scratch_fits_scoped_vmem(compile_tpu, case, tb):
    """The subtree kernel's two [4, TB, C] u32 scratch buffers (4 MiB at
    the radix-4 kernel's 32-key tile) and its double-buffered blocks fit
    a v5e kernel's 16 MiB of scoped VMEM, and Mosaic compiles it so."""
    fn, *shapes = PALLAS_CASES[case]()
    scratch, total = _vmem_bytes(fn, *shapes)
    assert scratch == 2 * 4 * tb * 4096 * 4
    assert total <= V5E_SCOPED_VMEM, total
    calls = _custom_calls(compile_tpu(fn, *shapes))
    assert len(calls) == 1 and "dpf_subtree_contract" in calls[0]


def test_subtree_kernel_keeps_its_name_at_a_small_table(compile_tpu):
    """The device trace finds the production kernel by its name,
    whatever the shape: here 2^14 rows and 64 keys."""
    from dpf_tpu.ops.pallas_level import (_subtree_contract_pallas_impl,
                                          pallas_chunk_leaves)
    n, b = 1 << 14, 64
    depth = n.bit_length() - 1
    c = pallas_chunk_leaves(n)

    def fn(f, a, bb, t):
        return _subtree_contract_pallas_impl(
            f, a, bb, t, depth=depth, f_levels=depth - (c.bit_length() - 1),
            prf_method=2)
    calls = _custom_calls(compile_tpu(
        fn, S((b, n // c, 4), U32), S((b, 64, 4), U32), S((b, 64, 4), U32),
        S((n, E), I32)))
    assert len(calls) == 1
    assert 'op_name="' in calls[0] and "dpf_subtree_contract" in calls[0]


@pytest.mark.parametrize("prf,unroll", [(2, True), (3, None)])
def test_xla_fused_path_compiles_for_v5e(compile_tpu, prf, unroll):
    """The default path (``kernel_impl="xla"``) at N = 2^16 with the TPU
    defaults spelled out (this process's backend is the CPU): ChaCha20
    with unrolled rounds, and bitsliced AES, whose levels share one
    tiled level program with rolled rounds."""
    from dpf_tpu.core import expand
    n = 1 << 16
    depth = n.bit_length() - 1
    chunk = expand.clamp_chunk(None, n, B)

    def fn(cw1, cw2, last, t):
        return expand.expand_and_contract(
            cw1, cw2, last, t, depth=depth, prf_method=prf,
            chunk_leaves=chunk, aes_impl="bitsliced", round_unroll=unroll)
    compile_tpu(fn, S((B, 64, 4), U32), S((B, 64, 4), U32),
                S((B, 4), U32), S((n, E), I32))


def test_sharded_binary_eval_compiles_on_four_chips(compile_tpu, topo):
    """The row-sharded binary ChaCha20 eval (what ``chip_smoke.py
    --multichip`` serves) over all four described chips, with every
    argument's ``NamedSharding`` on the described mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dpf_tpu.core import expand
    from dpf_tpu.parallel import sharded
    mesh = sharded.make_mesh(devices=np.asarray(topo.devices))
    depth = N.bit_length() - 1
    chunk = expand.clamp_chunk(None, N // 4, B)

    def on(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def fn(cw1, cw2, last, t):
        return sharded.eval_sharded(
            cw1, cw2, last, t, depth=depth, prf_method=2,
            chunk_leaves=chunk, mesh=mesh)
    text = compile_tpu(fn, on((B, 64, 4), U32, P("batch")),
                       on((B, 64, 4), U32, P("batch")),
                       on((B, 4), U32, P("batch")),
                       on((N, E), I32, P("table", None)))
    assert "all-reduce" in text


@pytest.mark.parametrize("batch", [64, 512])
def test_resolved_binary_chacha_program_compiles_for_v5e(compile_tpu,
                                                         monkeypatch,
                                                         batch):
    """The program ``DPF(prf=PRF_CHACHA20)`` dispatches on a TPU at
    N = 2^16 (the benchmark's deployment), from the knobs the resolver
    gives there: one subtree kernel, reading the int8 digit table."""
    from dpf_tpu import DPF
    from dpf_tpu.core import expand
    from dpf_tpu.utils import compat
    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                        lambda backend=None: True)
    n = 1 << 16
    d = DPF(prf=DPF.PRF_CHACHA20)
    d.eval_init(np.zeros((n, E), np.int32))
    kn = d.resolved_eval_knobs(batch)
    assert kn["kernel_impl"] == "pallas"

    def fn(cw1, cw2, last, t):
        return expand.expand_and_contract(
            cw1, cw2, last, t, depth=n.bit_length() - 1,
            prf_method=DPF.PRF_CHACHA20, chunk_leaves=kn["chunk_leaves"],
            dot_impl=kn["dot_impl"], aes_impl=kn["aes_impl"],
            round_unroll=kn["round_unroll"], kernel_impl=kn["kernel_impl"],
            f_levels=kn["f_levels"])
    calls = _custom_calls(compile_tpu(
        fn, S((batch, 64, 4), U32), S((batch, 64, 4), U32),
        S((batch, 4), U32), S((4, n, E), jnp.int8)))
    assert len(calls) == 1 and "dpf_subtree_contract" in calls[0]


def test_four_chip_subtree_cell_compiles_and_fits(compile_tpu, topo,
                                                  monkeypatch):
    """The benchmark's four-chip cell as ``ShardedDPFServer`` resolves it
    on a TPU: 2^28 x 16 rows over the described v5e:2x2, 64 keys, each
    chip running the subtree kernel over its 2^26 rows' digit planes,
    then one all-reduce.  Per chip, the program's arguments, output and
    temporaries fit 9e9 bytes, and so does one block write of the
    placement."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dpf_tpu.parallel import sharded
    from dpf_tpu.utils import compat
    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                        lambda backend=None: True)
    n, b = 1 << 28, 64
    mesh = sharded.make_mesh(devices=np.asarray(topo.devices))
    srv = sharded.ShardedDPFServer.__new__(sharded.ShardedDPFServer)
    srv.mesh, srv.n, srv.entry_size = mesh, n, E
    srv.scheme, srv.radix, srv.prf_method = "logn", 2, 2
    srv.chunk = srv.row_chunk = srv.psum_group = srv.dot_impl = None
    srv.kernel_impl, srv._tuned_memo = None, {}
    kn = srv.resolved_eval_knobs(b)
    assert (kn["kernel_impl"], kn["chunk_leaves"]) == ("pallas", 4096)

    def on(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def fn(cw1, cw2, last, t):
        return sharded.eval_sharded(
            cw1, cw2, last, t, depth=28, prf_method=2,
            chunk_leaves=kn["chunk_leaves"], mesh=mesh,
            psum_group=kn["psum_group"], kernel_impl="pallas")
    args = (on((b, 64, 4), U32, P("batch")), on((b, 64, 4), U32, P("batch")),
            on((b, 4), U32, P("batch")),
            on((4, E, n), jnp.int8, P(None, None, "table")))
    jax.config.update("jax_enable_compilation_cache", False)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    calls = _custom_calls(text)
    assert len(calls) == 1 and "dpf_subtree_contract" in calls[0]
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert 4 * (n // 4) * E <= per_chip <= 9e9, per_chip

    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    rows = sharded.PLACE_BLOCK_BYTES // (4 * E)
    placed = sharded._write_rows.lower(
        jax.ShapeDtypeStruct((4, E, n // 4), jnp.int8, sharding=one),
        jax.ShapeDtypeStruct((rows, E), I32, sharding=one),
        jax.ShapeDtypeStruct((), I32, sharding=one), digits=True).compile()
    m = placed.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) <= 9e9
