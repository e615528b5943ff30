"""Pallas expansion kernels vs the portable XLA path.

Interpreter engine choice matters enormously on this 1-core CPU host:
the generic ``interpret=True`` path compiles the interpreted grid with
XLA-CPU and blows up super-linearly with grid size (a 2x2-grid level
step was observed past 30 GB / 20 min of compile), while
``pltpu.force_tpu_interpret_mode()`` — the TPU-semantics interpreter —
runs the same case in ~2 s AND models the Mosaic memory spaces the real
kernel will see.  Every test here therefore uses the TPU interpreter;
cases stay tiny while covering the structure that matters: multiple key
tiles, multiple width tiles, multiple frontier subtrees, both ciphers,
both radices.  Their compiles for a described v5e are in
tests/test_tpu_compile.py; chip_smoke.py runs them on the chip.
"""

import numpy as np
import pytest

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from dpf_tpu.core import expand, keygen



def _keys(n, n_keys, method=2):
    flat = [keygen.generate_keys((i * 131) % n, n, b"plv%d" % i, method)[0]
            for i in range(n_keys)]
    return expand.pack_keys(flat)


def _level_case(width_levels, n_keys=1, tb=4, tw=2):
    from dpf_tpu.ops import pallas_level
    n, method = 512, 2  # ChaCha20
    cw1, cw2, last = _keys(n, n_keys)
    depth = 9
    seeds = jnp.asarray(last)[:, None, :]
    for l in range(width_levels):
        seeds = expand._level_step(seeds, jnp.asarray(cw1),
                                   jnp.asarray(cw2), depth - 1 - l, method)
    i = depth - 1 - width_levels
    want = expand._level_step(seeds, jnp.asarray(cw1), jnp.asarray(cw2),
                              i, method)
    with pltpu.force_tpu_interpret_mode():
        got = pallas_level.chacha_level_step_pallas(
            seeds, jnp.asarray(cw1[:, 2 * i:2 * i + 2, :]),
            jnp.asarray(cw2[:, 2 * i:2 * i + 2, :]), tb=tb, tw=tw)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_pallas_chacha_level_matches_portable():
    _level_case(0)


def test_pallas_chacha_level_multi_tile():
    """Several (batch, width) grid tiles — same tiny kernel, real tiling:
    3 keys pad to 4 = 2 tb-tiles of 2; width 4 = 2 tw-tiles of 2."""
    _level_case(2, n_keys=3, tb=2, tw=2)


def _subtree_case(n, n_keys, chunk, tb=None, method=2):
    """Fused subtree kernel (interpret) vs the XLA scan path, end to end."""
    depth = n.bit_length() - 1
    cw1, cw2, last = _keys(n, n_keys, method)
    rng = np.random.default_rng(5)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 16), dtype=np.int32)
    tperm = jnp.asarray(expand.permute_table(table))
    want = expand.expand_and_contract(
        cw1, cw2, last, tperm, depth=depth, prf_method=method,
        chunk_leaves=chunk)
    f = n // chunk
    f_levels = int(np.log2(f))
    seeds = jnp.asarray(last)[:, None, :]
    for l in range(f_levels):
        seeds = expand._level_step(seeds, jnp.asarray(cw1),
                                   jnp.asarray(cw2), depth - 1 - l, method)
    from dpf_tpu.ops import pallas_level
    with pltpu.force_tpu_interpret_mode():
        got = pallas_level.subtree_contract_pallas(
            seeds, jnp.asarray(cw1), jnp.asarray(cw2), tperm, depth=depth,
            f_levels=f_levels, tb=tb, prf_method=method)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_pallas_subtree_contract_minimal():
    # 2 subtrees of 64 leaves, 2 keys (padded to one tile of 8)
    _subtree_case(128, 2, 64)


def test_pallas_subtree_contract_salsa():
    _subtree_case(128, 2, 64, method=1)


def test_pallas_subtree_contract_multi_tile():
    # several key tiles (10 keys, tb=4 -> 3 tiles) and 4 frontier nodes,
    # same small per-tile kernel as the minimal case
    _subtree_case(256, 10, 64, tb=4)


# cases that reach the register-tiled levels (``tiled_levels``): two
# binary subtrees of 2,048 leaves (parent widths 256, 512 and 1,024 run
# tiled, the last two over 2 and 4 lane tiles), and the radix-4 block
# PRG at its 32-key tile, two subtrees of 4,096 leaves (parent widths
# 256 and 1,024 tiled, over 4 row groups and 4 and 16 tiles)
_TILED_CASES = {
    "chacha": 2,
    "salsa": 1,
    "chacha_blk.radix4": 5,
}


@pytest.mark.parametrize("case", sorted(_TILED_CASES))
def test_pallas_subtree_tiled_levels_match_portable(case):
    """The levels that run tile by tile from VMEM scratch give the
    portable XLA path's shares bit for bit."""
    from dpf_tpu.ops import pallas_level
    method = _TILED_CASES[case]
    if not case.endswith("radix4"):
        assert pallas_level.tiled_levels((2,) * 11) == 3
        _subtree_case(4096, 2, 2048, method=method)
        return
    from dpf_tpu.core import radix4
    n, n_keys = 1 << 13, 32
    assert pallas_level.tiled_levels(radix4.arities(n)[1:]) == 2
    mk = [radix4.generate_keys_r4((i * 97) % n, n, b"ptl%d" % i, method)[0]
          for i in range(n_keys)]
    cw1, cw2, last = radix4.pack_mixed_keys(mk)
    rng = np.random.default_rng(11)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 8), dtype=np.int32)
    perm = radix4.mixed_reverse_indices(radix4.arities(n))
    tperm = jnp.asarray(np.ascontiguousarray(table[perm]))
    want = np.asarray(radix4.expand_and_contract_mixed(
        cw1, cw2, last, tperm, n=n, prf_method=method, chunk_leaves=None))
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(radix4.expand_and_contract_mixed_pallas(
            cw1, cw2, last, tperm, n=n, prf_method=method))
    assert (got == want).all()


@pytest.mark.parametrize("sched,tiled", [
    ((2,) * 8, 0), ((2,) * 9, 1), ((2,) * 12, 4), ((4,) * 4, 0),
    ((4,) * 6, 2), ((2, 4, 4, 4, 4, 4), 1)])
def test_tiled_levels_count(sched, tiled):
    """A level runs tiled once its parents are a register tile (256
    lanes) wide: 4 of the 12 binary levels at 4,096 leaves."""
    from dpf_tpu.ops.pallas_level import tiled_levels
    assert tiled_levels(sched) == tiled


def test_pallas_subtree_mixed_radix4():
    """Radix-4 ChaCha through the mixed-arity subtree kernel
    (subtree_contract_pallas_mixed) vs the XLA mixed-radix path."""
    from dpf_tpu.core import radix4
    n, method, n_keys = 256, 2, 2
    mk = [radix4.generate_keys_r4((i * 97) % n, n, b"pmx%d" % i, method)[0]
          for i in range(n_keys)]
    cw1, cw2, last = radix4.pack_mixed_keys(mk)
    rng = np.random.default_rng(9)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 8), dtype=np.int32)
    perm = radix4.mixed_reverse_indices(radix4.arities(n))
    tperm = jnp.asarray(np.ascontiguousarray(table[perm]))
    want = np.asarray(radix4.expand_and_contract_mixed(
        cw1, cw2, last, tperm, n=n, prf_method=method, chunk_leaves=None))
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(radix4.expand_and_contract_mixed_pallas(
            cw1, cw2, last, tperm, n=n, prf_method=method))
    assert (got == want).all()


def test_pallas_full_path_via_config(monkeypatch):
    """kernel_impl='pallas' through the real DPF API: exercises the
    api.py branch (pallas_chunk_leaves selection + threading into
    expand_and_contract).  The Mosaic kernel itself runs in interpret
    mode on CPU via a monkeypatched wrapper."""
    import dpf_tpu
    from dpf_tpu.ops import pallas_level
    from dpf_tpu.utils.config import EvalConfig

    orig = pallas_level.subtree_contract_pallas
    monkeypatch.setattr(
        pallas_level, "subtree_contract_pallas",
        lambda *a, **kw: orig(*a, **{**kw, "interpret": True}))

    n = 256
    cfg = EvalConfig(prf_method=dpf_tpu.PRF_CHACHA20, kernel_impl="pallas")
    d = dpf_tpu.DPF(config=cfg)
    ref = dpf_tpu.DPF(prf=dpf_tpu.PRF_CHACHA20)
    table = np.arange(n * 4, dtype=np.int32).reshape(n, 4)
    d.eval_init(table)
    ref.eval_init(table)
    keys = [d.gen(7, n)[0], d.gen(200, n)[1]]
    got = np.asarray(d.eval_tpu(keys))
    want = np.asarray(ref.eval_tpu(keys))
    assert (got == want).all()


def test_dispatch_span_names_the_tiled_levels(monkeypatch):
    """``eval_tpu.dispatch`` carries the subtree kernel's tiled level
    count beside the kernel: at 512 leaves a subtree, 1 of 9 levels."""
    import dpf_tpu
    from dpf_tpu.obs import tracer
    from dpf_tpu.ops import pallas_level
    from dpf_tpu.utils.config import EvalConfig

    orig = pallas_level.subtree_contract_pallas
    monkeypatch.setattr(
        pallas_level, "subtree_contract_pallas",
        lambda *a, **kw: orig(*a, **{**kw, "interpret": True}))
    n = 512
    d = dpf_tpu.DPF(config=EvalConfig(prf_method=dpf_tpu.PRF_CHACHA20,
                                      kernel_impl="pallas"))
    d.eval_init(np.arange(n * 4, dtype=np.int32).reshape(n, 4))
    keys = [d.gen(7, n)[0], d.gen(300, n)[1]]
    t = tracer.enable()
    try:
        t.clear()
        d.eval_tpu(keys)
        spans = [e for e in t.events() if e["name"] == "eval_tpu.dispatch"]
    finally:
        tracer.disable()
    assert [s["attrs"] for s in spans] == [{"kernel": "pallas",
                                            "tiled_levels": 1}]


# the heuristic kernel rule: (case id) -> (DPF kwargs, probe patched to
# report a TPU, planted tuning entry, kernel, provenance)
_RULE_CASES = {
    "chacha.tpu": (dict(prf=2), True, None, "pallas", "heuristic"),
    "salsa.tpu": (dict(prf=1), True, None, "pallas", "heuristic"),
    "salsa_blk.tpu": (dict(prf=4), True, None, "pallas", "heuristic"),
    "chacha_blk.tpu": (dict(prf=5), True, None, "pallas", "heuristic"),
    "aes.tpu": (dict(prf=3), True, None, "xla", "heuristic"),
    "dummy.tpu": (dict(prf=0), True, None, "xla", "heuristic"),
    "radix4.tpu": (dict(radix=4), True, None, "xla", "heuristic"),
    "sqrtn.tpu": (dict(prf=2, scheme="sqrtn"), True, None, "xla",
                  "heuristic"),
    "config_xla.tpu": (dict(kernel_impl="xla"), True, None, "xla",
                       "config"),
    "tuned_xla.tpu": (dict(prf=2), True,
                      {"kernel_impl": "xla", "chunk_leaves": 1024},
                      "xla", "tuned"),
    # a tuned chunk naming no kernel was timed on the scan: it never
    # rides the subtree kernel
    "tuned_chunk_only.tpu": (dict(prf=2), True, {"chunk_leaves": 1024},
                             "pallas", "heuristic"),
    "chacha.cpu": (dict(prf=2), False, None, "xla", "heuristic"),
    "salsa.cpu": (dict(prf=1), False, None, "xla", "heuristic"),
    "salsa_blk.cpu": (dict(prf=4), False, None, "xla", "heuristic"),
    "chacha_blk.cpu": (dict(prf=5), False, None, "xla", "heuristic"),
    "aes.cpu": (dict(prf=3), False, None, "xla", "heuristic"),
}


@pytest.mark.parametrize("case", sorted(_RULE_CASES))
def test_heuristic_kernel_rule(case, monkeypatch):
    """With no config, tuned or searched kernel, binary GGM over a PRF
    with a subtree core resolves the subtree kernel where it compiles
    (the probe says TPU), with its own chunk; everything else, and
    every PRF on this CPU, resolves the xla scan."""
    import dpf_tpu
    from dpf_tpu.ops.pallas_level import pallas_chunk_leaves
    from dpf_tpu.utils import compat
    from dpf_tpu.utils.config import EvalConfig

    kwargs, on_tpu, tuned, kernel, prov = _RULE_CASES[case]
    if on_tpu:
        monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                            lambda backend=None: True)
    if "radix" in kwargs or "kernel_impl" in kwargs:
        d = dpf_tpu.DPF(config=EvalConfig(
            prf_method=kwargs.get("prf", 2), radix=kwargs.get("radix", 2),
            kernel_impl=kwargs.get("kernel_impl")))
    else:
        d = dpf_tpu.DPF(**kwargs)
    n, batch = 1 << 12, 8
    d.eval_init(np.zeros((n, 16), np.int32))
    if tuned is not None:
        d._tuned_cache[batch] = dict(tuned)
    kn = d.resolved_eval_knobs(batch)
    assert (kn["kernel_impl"], kn["kernel_resolved_from"]) == (kernel, prov)
    if kernel == "pallas":
        assert kn["chunk_leaves"] == pallas_chunk_leaves(n)
    elif tuned is not None:
        assert kn["chunk_leaves"] == tuned["chunk_leaves"]
