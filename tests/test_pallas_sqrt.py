"""Fused sqrt-N grid kernel (ops/pallas_sqrt) vs the scan-path oracle.

Two interpreter lanes, same trade-off as test_pallas_level.py:

- ``interpret=True`` (the generic Pallas interpreter) runs EAGERLY, so
  the small parity cases and the full-API-path test below are the
  tier-1 guarantee that the kernel is bit-identical to the scan oracle.
- ``pltpu.force_tpu_interpret_mode()`` (the TPU-semantics interpreter)
  models the Mosaic memory spaces and runs the REAL jit-wrapped entry
  point.  On an actual TPU the kernels compile for real; their
  compiles for a described v5e are in ``tests/test_tpu_compile.py``.

The knob-resolution tests (degradation provenance, old-grammar cache
entries, the row_chunk riding rule) are plain CPU tests: the whole
point of the provenance plumbing is that a tuning cache written on a
TPU stays usable on a host with no Pallas at all.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

import dpf_tpu
from dpf_tpu.core import prf_ref, sqrtn
from dpf_tpu.ops import pallas_sqrt
from dpf_tpu.utils.config import EvalConfig


PLANE_PRFS = [prf_ref.PRF_SALSA20, prf_ref.PRF_CHACHA20,
              prf_ref.PRF_SALSA20_BLK, prf_ref.PRF_CHACHA20_BLK]


@pytest.fixture(scope="module")
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return jax.devices()


def _case(n, prf_method, n_keys=None, e=5, seed=7):
    """3 packed keys (2 distinct + 1 partner), a random table, and the
    scan-path oracle output for them."""
    pairs = [sqrtn.generate_sqrt_keys((i * 71 + 3) % n, n, b"pg%d" % i,
                                      prf_method, n_keys=n_keys)
             for i in range(2)]
    keys = [p[0] for p in pairs] + [pairs[0][1]]
    seeds, cw1, cw2 = sqrtn.pack_sqrt_keys(keys)
    table = np.random.default_rng(seed).integers(
        -2 ** 31, 2 ** 31, (n, e), dtype=np.int64).astype(np.int32)
    oracle = np.asarray(sqrtn.eval_contract_batched(
        seeds, cw1, cw2, jnp.asarray(table), prf_method=prf_method,
        dot_impl="i32", kernel_impl="xla"))
    return seeds, cw1, cw2, table, oracle


def _run_tpu_or_interpret(*args, **kw):
    """Compiled on a real TPU, TPU-semantics interpreter elsewhere."""
    if jax.default_backend() == "tpu":
        return np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
            *args, **kw))
    with pltpu.force_tpu_interpret_mode():
        return np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
            *args, **kw))


# ------------------------------------------------- always-on parity (CPU)


@pytest.mark.parametrize("prf_method", PLANE_PRFS)
def test_grid_kernel_matches_scan_oracle(prf_method):
    """Every plane-core PRF, both row chunkings, bit-identical to the
    scan path (generic interpreter, runs on the container jax)."""
    seeds, cw1, cw2, table, oracle = _case(64, prf_method)
    for rc in (None, 4):
        got = np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
            seeds, cw1, cw2, jnp.asarray(table), prf_method=prf_method,
            row_chunk=rc, interpret=True))
        assert np.array_equal(got, oracle), (prf_method, rc)


def test_grid_kernel_row0_offset_halves():
    """A nonzero row0 (the sharded path's per-shard row base) evaluates
    the correct half-grid: lo + hi row halves == the full oracle."""
    prf = prf_ref.PRF_CHACHA20_BLK
    seeds, cw1, cw2, table, oracle = _case(64, prf)
    r = cw1.shape[1]
    k = seeds.shape[1]
    half = r // 2
    t = jnp.asarray(table)
    lo = np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
        seeds, cw1[:, :half], cw2[:, :half], t[:half * k],
        prf_method=prf, row0=0, interpret=True))
    hi = np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
        seeds, cw1[:, half:], cw2[:, half:], t[half * k:],
        prf_method=prf, row0=half, interpret=True))
    assert np.array_equal(lo + hi, oracle)


def test_grid_kernel_wide_split():
    """A non-default K > R split (K=16 columns over R=4 rows): the tile
    covers the whole grid in one step and the blk interleave still
    lines up at the 4-row floor."""
    for prf in (prf_ref.PRF_SALSA20, prf_ref.PRF_SALSA20_BLK):
        seeds, cw1, cw2, table, oracle = _case(64, prf, n_keys=16)
        got = np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
            seeds, cw1, cw2, jnp.asarray(table), prf_method=prf,
            interpret=True))
        assert np.array_equal(got, oracle), prf


def test_kernel_full_api_path(monkeypatch):
    """kernel_impl='pallas' through the real DPF API: resolution
    provenance, the dispatch-layer shape gate, sqrtn routing, and the
    kernel itself (generic interpreter via a monkeypatched wrapper) —
    shares bit-identical to a stock sqrtn DPF."""
    from dpf_tpu.utils import compat

    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                        lambda backend=None: True)
    orig = pallas_sqrt.sqrt_grid_contract_pallas
    monkeypatch.setattr(
        pallas_sqrt, "sqrt_grid_contract_pallas",
        lambda *a, **kw: orig(*a, **{**kw, "interpret": True}))

    n = 128
    d = dpf_tpu.DPF(config=EvalConfig(
        prf_method=dpf_tpu.PRF_CHACHA20, scheme="sqrtn",
        kernel_impl="pallas"))
    ref = dpf_tpu.DPF(config=EvalConfig(
        prf_method=dpf_tpu.PRF_CHACHA20, scheme="sqrtn"))
    table = np.arange(n * 4, dtype=np.int32).reshape(n, 4)
    d.eval_init(table)
    ref.eval_init(table)
    kn = d.resolved_eval_knobs(2)
    assert kn["kernel_impl"] == "pallas"
    assert kn["kernel_resolved_from"] == "config"
    keys = [d.gen(7, n)[0], d.gen(100, n)[1]]
    got = np.asarray(d.eval_tpu(keys))
    want = np.asarray(ref.eval_tpu(keys))
    assert np.array_equal(got, want)


def test_api_shape_gate_degrades_unsupported_prf(monkeypatch):
    """A pallas pin with a PRF the kernel has no plane core for (AES)
    degrades AT DISPATCH to the scan path — correct answers, swallowed
    reason on record."""
    from dpf_tpu.utils import compat
    from dpf_tpu.utils.profiling import SWALLOWED_ERRORS

    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                        lambda backend=None: True)
    n = 128
    d = dpf_tpu.DPF(config=EvalConfig(
        prf_method=dpf_tpu.PRF_AES128, scheme="sqrtn",
        kernel_impl="pallas"))
    table = np.arange(n * 2, dtype=np.int32).reshape(n, 2)
    d.eval_init(table)
    before = sum(SWALLOWED_ERRORS.get(
        "api.sqrt_kernel_unsupported", {}).values())
    k0, k1 = d.gen(42, n)
    out = np.asarray(d.eval_tpu([k0, k1]))
    assert (out[0] - out[1]).astype(np.int32).tolist() == \
        table[42].tolist()
    assert sum(SWALLOWED_ERRORS.get(
        "api.sqrt_kernel_unsupported", {}).values()) > before


# ------------------------------------------- TPU-interpreter parity fuzz


@pytest.mark.parametrize("prf_method", PLANE_PRFS)
@pytest.mark.parametrize("n,n_keys", [(64, None), (64, 16), (256, None)])
def test_grid_kernel_parity_tpu_interpret(prf_method, n, n_keys):
    """The jit-wrapped entry point under the TPU-semantics interpreter
    (Mosaic memory spaces modeled): row_chunk sweep x (K, R) splits,
    bit-identical to the scan oracle."""
    seeds, cw1, cw2, table, oracle = _case(n, prf_method, n_keys=n_keys)
    r = cw1.shape[1]
    for rc in (None, 4, r):
        if rc is not None and (r % rc or (rc != r and rc % 4)):
            continue
        got = _run_tpu_or_interpret(
            seeds, cw1, cw2, jnp.asarray(table), prf_method=prf_method,
            row_chunk=rc)
        assert np.array_equal(got, oracle), (prf_method, n, n_keys, rc)


def test_grid_kernel_traced_row0_tpu_interpret():
    """row0 through the jit boundary (traced, the sharded path's
    contract): half-grids at both ciphers sum to the full oracle."""
    for prf in (prf_ref.PRF_CHACHA20, prf_ref.PRF_SALSA20_BLK):
        seeds, cw1, cw2, table, oracle = _case(64, prf)
        r = cw1.shape[1]
        k = seeds.shape[1]
        half = r // 2
        t = jnp.asarray(table)
        lo = _run_tpu_or_interpret(
            seeds, cw1[:, :half], cw2[:, :half], t[:half * k],
            prf_method=prf, row0=0)
        hi = _run_tpu_or_interpret(
            seeds, cw1[:, half:], cw2[:, half:], t[half * k:],
            prf_method=prf, row0=half)
        assert np.array_equal(lo + hi, oracle), prf


@pytest.mark.skipif(
    not os.environ.get("DPF_RUN_SLOW"),
    reason="large-N grid-kernel cell (N=2^18, B=512) runs in the "
           "DPF_RUN_SLOW lane; the small parity cells above cover the "
           "kernel structure per-commit")
def test_grid_kernel_large_n_bounded_vmem():
    """Acceptance cell mirroring test_sqrt_bounded_memory_large_grid:
    N=2^18 at B=512 — the kernel's VMEM cell cap must engage (rc*K <=
    PALLAS_SQRT_MAX_CELLS, far below the full R=512 row range) and the
    output stays bit-identical to the scan oracle."""
    n, batch, e, distinct = 1 << 18, 512, 2, 4
    prf = prf_ref.PRF_SALSA20
    pairs = [sqrtn.generate_sqrt_keys((i * 0x9E3779B1) % n, n,
                                      b"big%d" % i, prf)
             for i in range(distinct)]
    keys = [pairs[i % distinct][0] for i in range(batch)]
    seeds, cw1, cw2 = sqrtn.pack_sqrt_keys(keys)
    k_split, r_split = sqrtn.default_split(n)
    rc = pallas_sqrt.pallas_sqrt_row_chunk(r_split, k_split)
    assert rc * k_split <= pallas_sqrt.PALLAS_SQRT_MAX_CELLS
    assert rc < r_split                     # the cap actually engaged
    table = np.random.default_rng(18).integers(
        0, 2 ** 31, (n, e), dtype=np.int32, endpoint=False)
    oracle = np.asarray(sqrtn.eval_contract_batched(
        seeds, cw1, cw2, jnp.asarray(table), prf_method=prf,
        kernel_impl="xla"))
    got = _run_tpu_or_interpret(seeds, cw1, cw2, jnp.asarray(table),
                                prf_method=prf)
    assert np.array_equal(got, oracle)


# --------------------------------------------- knob resolution provenance


def test_kernel_degrades_without_pallas_tpu():
    """A tuned cache entry minted on a TPU (kernel_impl='pallas') on a
    host with no Pallas/TPU: the resolver answers the xla scan with
    'degraded' provenance, drops the riding row_chunk (it was gated
    with the OTHER kernel), counts the swallow — and still serves."""
    from dpf_tpu.utils.profiling import SWALLOWED_ERRORS

    if jax.default_backend() == "tpu":
        pytest.skip("degradation only happens off-TPU")
    n, batch = 256, 4
    d = dpf_tpu.DPF(prf=dpf_tpu.PRF_CHACHA20, scheme="sqrtn")
    table = np.arange(n * 3, dtype=np.int32).reshape(n, 3)
    d.eval_init(table)
    d._tuned_cache[batch] = {"row_chunk": 8, "dot_impl": "i32",
                             "kernel_impl": "pallas"}
    before = sum(SWALLOWED_ERRORS.get(
        "api.sqrt_kernel_unavailable", {}).values())
    kn = d.resolved_eval_knobs(batch)
    assert kn["kernel_impl"] == "xla"
    assert kn["kernel_resolved_from"] == "degraded"
    assert kn["row_chunk"] is None          # rode with the pallas win
    assert sum(SWALLOWED_ERRORS.get(
        "api.sqrt_kernel_unavailable", {}).values()) > before
    ks = [d.gen(i * 31, n)[0] for i in range(batch)]
    assert np.array_equal(np.asarray(d.eval_tpu(ks)),
                          np.asarray(d.eval_cpu(ks)))


def test_explicit_row_chunk_survives_degradation():
    """An EXPLICIT config row_chunk is the user's pin, not a tuned
    rider — degradation must not silently drop it."""
    if jax.default_backend() == "tpu":
        pytest.skip("degradation only happens off-TPU")
    n, batch = 256, 4
    d = dpf_tpu.DPF(config=EvalConfig(
        prf_method=dpf_tpu.PRF_CHACHA20, scheme="sqrtn", row_chunk=4,
        kernel_impl="pallas"))
    d.eval_init(np.arange(n * 2, dtype=np.int32).reshape(n, 2))
    kn = d.resolved_eval_knobs(batch)
    assert kn["kernel_resolved_from"] == "degraded"
    assert kn["row_chunk"] == 4


def test_sharded_server_degrades_with_provenance(eight_devices):
    """The mesh server's resolver applies the same rule."""
    from dpf_tpu.parallel import sharded

    if jax.default_backend() == "tpu":
        pytest.skip("degradation only happens off-TPU")
    n = 2048
    table = np.arange(n * 2, dtype=np.int32).reshape(n, 2)
    mesh = sharded.make_mesh(n_table=4, n_batch=2)
    srv = sharded.ShardedDPFServer(table, mesh,
                                   prf_method=dpf_tpu.DPF.PRF_SALSA20,
                                   scheme="sqrtn", kernel_impl="pallas")
    kn = srv.resolved_eval_knobs(4)
    assert kn["kernel_impl"] == "xla"
    assert kn["kernel_resolved_from"] == "degraded"


# ------------------------------------- cache grammar backward compat


def test_old_grammar_cache_entry_round_trip(tmp_path, monkeypatch):
    """A pre-kernel tuning.json entry (no kernel_impl field) still
    resolves: kernel falls back to the heuristic 'xla', the tuned
    row_chunk RIDES (it was gated on the scan path, which is what
    runs), and dispatch consumes it end to end."""
    from dpf_tpu.tune import cache as tcache
    from dpf_tpu.tune.fingerprint import cache_key

    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    c = tcache.default_cache(refresh=True)
    n, batch = 256, 4
    key = cache_key("eval", n=n, entry_size=3, batch=batch,
                    prf_method=dpf_tpu.PRF_CHACHA20, scheme="sqrtn",
                    radix=2)
    c.store(key, {"knobs": {"row_chunk": 8, "dot_impl": "i32"}})
    assert tcache.lookup_eval_knobs(
        n=n, entry_size=3, batch=batch,
        prf_method=dpf_tpu.PRF_CHACHA20,
        scheme="sqrtn") == {"row_chunk": 8, "dot_impl": "i32"}

    d = dpf_tpu.DPF(prf=dpf_tpu.PRF_CHACHA20, scheme="sqrtn")
    d.eval_init(np.arange(n * 3, dtype=np.int32).reshape(n, 3))
    kn = d.resolved_eval_knobs(batch)
    assert kn == {"dot_impl": "i32", "row_chunk": 8,
                  "kernel_impl": "xla",
                  "kernel_resolved_from": "heuristic"}
    ks = [d.gen(i * 17, n)[0] for i in range(batch)]
    assert np.array_equal(np.asarray(d.eval_tpu(ks)),
                          np.asarray(d.eval_cpu(ks)))


def test_knob_tag_grammar_backward_compatible():
    """The sqrtn knob tag keeps its pre-kernel spelling for the xla
    scan (old timing records stay comparable) and only grows a suffix
    for the grid kernel."""
    from dpf_tpu.tune.search import _knob_tag

    assert _knob_tag({"row_chunk": 8, "dot_impl": "i32"}) == "rc8.i32"
    assert _knob_tag({"row_chunk": 8, "dot_impl": "i32",
                      "kernel_impl": "xla"}) == "rc8.i32"
    assert _knob_tag({"row_chunk": 8, "dot_impl": "i32",
                      "kernel_impl": "pallas"}) == "rc8.i32.pallas"
    assert _knob_tag({"row_chunk": None, "dot_impl": None,
                      "kernel_impl": None}) == "rcNone.None"


def test_batch_pir_riding_rule():
    """The batch-PIR per-key-tables program is ALWAYS the fused xla
    scan, so a grid-kernel winner's VMEM-capped row_chunk must not be
    pinned onto it — while an xla-tuned (or pre-kernel) entry rides."""
    from dpf_tpu.apps.batch_pir import PrivateLookupServer

    table = np.arange(64 * 2, dtype=np.int32).reshape(64, 2)
    srv = PrivateLookupServer(table, [[0, 1], [2, 3]],
                              prf=dpf_tpu.PRF_CHACHA20, scheme="sqrtn")
    key = (64, 4, "sqrtn", 2)
    srv._tuned[key] = {"row_chunk": 8, "dot_impl": "i32",
                      "kernel_impl": "pallas"}
    assert srv._group_knobs(*key)["row_chunk"] is None
    srv._tuned[key] = {"row_chunk": 8, "dot_impl": "i32",
                      "kernel_impl": "xla"}
    assert srv._group_knobs(*key)["row_chunk"] == 8
    srv._tuned[key] = {"row_chunk": 8, "dot_impl": "i32"}
    assert srv._group_knobs(*key)["row_chunk"] == 8


# ------------------------------------------------------ shape predicates


def test_pallas_sqrt_unsupported_reasons():
    assert pallas_sqrt.pallas_sqrt_unsupported(
        prf_ref.PRF_DUMMY, 8) is not None
    assert pallas_sqrt.pallas_sqrt_unsupported(
        prf_ref.PRF_AES128, 8) is not None
    # block-PRG ids need R % 4 == 0 for the interleave
    assert "multiple of 4" in pallas_sqrt.pallas_sqrt_unsupported(
        prf_ref.PRF_SALSA20_BLK, 2)
    for prf in PLANE_PRFS:
        assert pallas_sqrt.pallas_sqrt_unsupported(prf, 8) is None
    # the word-at-a-time cores take any R
    assert pallas_sqrt.pallas_sqrt_unsupported(
        prf_ref.PRF_CHACHA20, 2) is None


def test_pallas_sqrt_row_chunk_properties():
    """The VMEM cell cap: every resolved chunk divides R, keeps the
    4-row interleave alignment whenever it chunks, and lands under the
    cap whenever halving can get there."""
    rng = np.random.default_rng(11)
    for _ in range(200):
        n = 1 << int(rng.integers(6, 21))
        k = 1 << int(rng.integers(1, n.bit_length() - 1))
        r = n // k
        rc = pallas_sqrt.pallas_sqrt_row_chunk(r, k)
        assert r % rc == 0, (r, k, rc)
        assert rc == r or rc % 4 == 0, (r, k, rc)
        # the cap holds unless alignment (rc down at the 4-row floor /
        # odd-power shapes) blocks further halving
        assert (rc * k <= pallas_sqrt.PALLAS_SQRT_MAX_CELLS
                or rc <= sqrtn.ROW_CHUNK_FLOOR or rc % 8), (r, k, rc)
    # an explicit chunk obeys the shared rules, then the cap
    assert pallas_sqrt.pallas_sqrt_row_chunk(64, 4, 16) == 16
    assert pallas_sqrt.pallas_sqrt_row_chunk(1024, 1024, 1024) == 4
    with pytest.raises(ValueError):
        pallas_sqrt.pallas_sqrt_row_chunk(64, 4, 3)


# --------------------------------------------------------- observability


def test_router_route_event_records_kernel(monkeypatch):
    """Every route event carries the winning construction's
    per-dispatch kernel_impl, and the EWMA cost-table metrics series
    grows the kernel label."""
    from dpf_tpu.obs.flight import FLIGHT
    from dpf_tpu.obs.metrics import MetricsRegistry, register_router
    from dpf_tpu.serve.router import SchemeRouter

    table = np.arange(256 * 2, dtype=np.int32).reshape(256, 2)
    rt = SchemeRouter(table, prf=dpf_tpu.DPF.PRF_DUMMY, cap=8,
                      buckets=(4,), probe=False)
    mark = FLIGHT.recorded
    rt.route(4)
    ev = [e for e in FLIGHT.dump() if e["seq"] > mark
          and e["kind"] == "route"][-1]
    assert ev["kernel_impl"] == "xla"
    assert rt.dispatch_kernel("sqrtn", 4) == "xla"
    assert rt.dispatch_kernel("no-such-construction", 4) is None

    reg = MetricsRegistry()
    register_router(rt, reg)
    rt._costs[("sqrtn", 4)] = 0.002
    text = reg.openmetrics()
    assert ('dpf_router_cost_seconds{bucket="4",construction="sqrtn",'
            'kernel="xla"} 0.002' in text)


def test_sharded_server_builds_digit_planes_once(eight_devices,
                                                 monkeypatch):
    """The mesh server's grid kernel reads the table's int8 digit planes,
    built once (sharded like the table) and reused by every call; the
    shares equal the host reference."""
    from jax.experimental.pallas import tpu as pltpu

    from dpf_tpu.parallel import sharded
    from dpf_tpu.utils import compat
    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                        lambda backend=None: True)
    n = 1024
    rng = np.random.default_rng(5)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 3),
                         dtype=np.int64).astype(np.int32)
    mesh = sharded.make_mesh(n_table=4, n_batch=2)
    srv = sharded.ShardedDPFServer(table, mesh,
                                   prf_method=dpf_tpu.PRF_CHACHA20,
                                   scheme="sqrtn", kernel_impl="pallas")
    d = dpf_tpu.DPF(prf=dpf_tpu.PRF_CHACHA20, scheme="sqrtn")
    d.eval_init(table)
    keys = [d.gen(i * 97 % n, n)[0] for i in range(4)]
    assert srv.resolved_eval_knobs(4)["kernel_resolved_from"] == "config"
    with pltpu.force_tpu_interpret_mode():
        got = srv.eval(keys)
        digits = srv._digits
        again = srv.eval(keys)
    assert digits is not None and srv._digits is digits
    assert digits.shape == (4, n, 3)
    assert np.array_equal(got, np.asarray(d.eval_cpu(keys)))
    assert np.array_equal(again, got)
