"""Generative kernel-variant search tests: variant grammar and
validity rules, property-fuzzed interpret-mode parity of sampled
variants against the scan oracle, the searched-slot resolution
precedence (provenance ``kernel_resolved_from="searched"`` + dispatch
parity), cache round-trip across processes, pre-variant cache-entry
compatibility, the surfaced row-chunk halving, and the route-event /
warmup consumption paths."""

import json
import os
import random
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dpf_tpu
from dpf_tpu.core import prf_ref, sqrtn
from dpf_tpu.ops import pallas_sqrt
import importlib

from dpf_tpu.tune import cache as tcache

# the package re-exports the kernel_search FUNCTION under the same
# name; the tests need the module
ks = importlib.import_module("dpf_tpu.tune.kernel_search")
from dpf_tpu.tune.fingerprint import cache_key
from dpf_tpu.utils.config import EvalConfig
from dpf_tpu.utils.profiling import SWALLOWED_ERRORS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PLANE_PRFS = [prf_ref.PRF_SALSA20, prf_ref.PRF_CHACHA20,
              prf_ref.PRF_SALSA20_BLK, prf_ref.PRF_CHACHA20_BLK]


# ------------------------------------------------------ variant grammar


def test_variant_round_trip_and_knobs():
    """to_dict/from_dict is the identity on every populated field, and
    eval_knobs() produces exactly the searched-slot knob dict."""
    v = ks.KernelVariant(family="pallas", tb=16, max_cells=1024,
                         grid_order="kb", dim_semantics="arbitrary",
                         limbs="multi", cw_add="staged")
    assert ks.KernelVariant.from_dict(v.to_dict()) == v
    assert ks.KernelVariant.from_dict(
        json.loads(json.dumps(v.to_dict()))) == v
    kn = v.eval_knobs()
    assert kn["kernel_impl"] == "pallas"
    assert kn["kernel_variant"] == v.to_dict()
    x = ks.KernelVariant(family="xla", row_chunk=8, dot_impl="i32")
    assert x.eval_knobs()["kernel_impl"] == "xla"
    assert x.tag() == "x.rc8.i32"
    # unknown keys (a future grammar) are dropped, not fatal
    assert ks.KernelVariant.from_dict({"family": "xla", "zzz": 1}) == \
        ks.KernelVariant(family="xla")


def test_variant_invalid_rules():
    n, batch, prf = 256, 32, prf_ref.PRF_CHACHA20
    ok = dict(n=n, batch=batch, prf_method=prf)
    assert ks.variant_invalid(ks.KernelVariant(family="xla"), **ok) is None
    assert ks.variant_invalid(ks.pr10_default_variant(), **ok) is None
    bad = [
        ks.KernelVariant(family="xla", row_chunk=3),      # %4 rule
        ks.KernelVariant(family="xla", row_chunk=5),      # divides R
        ks.KernelVariant(family="xla", dot_impl="nope"),
        ks.KernelVariant(family="mystery"),
        ks.KernelVariant(family="pallas", tb=12),         # %8 rule
        ks.KernelVariant(family="pallas", max_cells=8),   # < 4*K
        ks.KernelVariant(family="pallas", grid_order="zz"),
        ks.KernelVariant(family="pallas", limbs="hi"),
        ks.KernelVariant(family="pallas", cw_add="other"),
    ]
    for v in bad:
        assert ks.variant_invalid(v, **ok) is not None, v
    # the kb cross-field rule: legal with one key tile, rejected when
    # the padded batch spans several
    kb = ks.KernelVariant(family="pallas", tb=32, grid_order="kb")
    assert ks.variant_invalid(kb, n=n, batch=32, prf_method=prf) is None
    assert ks.variant_invalid(kb, n=n, batch=64, prf_method=prf) \
        is not None
    # DUMMY has no Pallas plane core: every pallas variant is invalid
    assert ks.variant_invalid(ks.pr10_default_variant(), n=n,
                              batch=batch, prf_method=0) is not None


def test_kb_multi_tile_guard_raises_in_launcher():
    """The launcher enforces the same kb rule the validator predicts:
    revisiting an output block non-consecutively is Mosaic-illegal."""
    prf = prf_ref.PRF_CHACHA20
    pairs = [sqrtn.generate_sqrt_keys(i, 64, b"kb%d" % i, prf)
             for i in range(9)]
    keys = [p[0] for p in pairs]
    seeds, cw1, cw2 = sqrtn.pack_sqrt_keys(keys)
    table = np.zeros((64, 3), np.int32)
    with pytest.raises(ValueError, match="kb"):
        pallas_sqrt.sqrt_grid_contract_pallas(
            seeds, cw1, cw2, jnp.asarray(table), prf_method=prf,
            interpret=True, tb=8, grid_order="kb")


def test_mutate_and_sample_are_valid_and_deterministic():
    """Fuzz: every mutation / sample is valid at its shape, mutates
    exactly one field, and the draw stream is reproducible under the
    same seed (the search must be replayable)."""
    n, batch, prf = 1024, 64, prf_ref.PRF_CHACHA20
    for fam in ("xla", "pallas"):
        r1, r2 = random.Random(99), random.Random(99)
        r3 = random.Random(7)
        base = (ks.KernelVariant(family="xla", row_chunk=4,
                                 dot_impl="i32")
                if fam == "xla" else ks.pr10_default_variant())
        for _ in range(40):
            m1 = ks.mutate_variant(r1, base, n=n, batch=batch,
                                   prf_method=prf)
            m2 = ks.mutate_variant(r2, base, n=n, batch=batch,
                                   prf_method=prf)
            assert m1 == m2
            if m1 is not None:
                assert ks.variant_invalid(m1, n=n, batch=batch,
                                          prf_method=prf) is None
                diff = [f for f in m1.to_dict()
                        if m1.to_dict().get(f) != base.to_dict().get(f)]
                assert len(diff) == 1, (base, m1)
            s = ks.sample_variant(r3, fam, n=n, batch=batch,
                                  prf_method=prf)
            assert s is not None and s.family == fam
            assert ks.variant_invalid(s, n=n, batch=batch,
                                      prf_method=prf) is None


# ------------------------------- property-fuzzed parity (the real gate)


@pytest.mark.parametrize("prf_method", PLANE_PRFS)
def test_sampled_variants_parity_fuzzed(prf_method):
    """Property fuzz: random VALID Pallas variants are bit-identical to
    the scan oracle in interpret mode — the exact gate the search runs,
    across all four plane PRFs."""
    rng = random.Random(0xF0 + prf_method)
    seen = {ks.pr10_default_variant()}
    for _ in range(4):
        v = ks.sample_variant(rng, "pallas", n=64, batch=8,
                              prf_method=prf_method)
        assert v is not None
        seen.add(v)
    for v in seen:
        assert ks.pallas_parity_ok(v, prf_method=prf_method), v.tag()


def test_variant_row0_offset_halves():
    """A searched structure still sums split-row halves to the full
    oracle under a nonzero row0 (the sharded per-shard row base)."""
    prf = prf_ref.PRF_CHACHA20_BLK
    pairs = [sqrtn.generate_sqrt_keys((i * 71 + 3) % 64, 64,
                                      b"r0%d" % i, prf)
             for i in range(2)]
    keys = [p[0] for p in pairs] + [pairs[0][1]]
    seeds, cw1, cw2 = sqrtn.pack_sqrt_keys(keys)
    table = np.random.default_rng(3).integers(
        -2 ** 31, 2 ** 31, (64, 5), dtype=np.int64).astype(np.int32)
    oracle = np.asarray(sqrtn.eval_contract_batched(
        seeds, cw1, cw2, jnp.asarray(table), prf_method=prf,
        dot_impl="i32", kernel_impl="xla"))
    r = cw1.shape[1]
    half = r // 2
    t = jnp.asarray(table)
    for v in (ks.KernelVariant(family="pallas", limbs="multi",
                               cw_add="staged"),
              ks.KernelVariant(family="pallas", tb=8,
                               dim_semantics="arbitrary")):
        kw = v.launcher_kwargs()
        lo = np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
            seeds, cw1[:, :half], cw2[:, :half], t[:half * 8],
            prf_method=prf, row0=0, interpret=True, **kw))
        hi = np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
            seeds, cw1[:, half:], cw2[:, half:], t[half * 8:],
            prf_method=prf, row0=half, interpret=True, **kw))
        assert np.array_equal(lo + hi, oracle), v.tag()


# ------------------------------------- search, persistence, resolution


def _fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("DPF_TPU_TUNE_CACHE", str(tmp_path / "t.json"))
    return tcache.default_cache(refresh=True)


def test_kernel_search_persists_and_resolves_searched(tmp_path,
                                                      monkeypatch):
    """End-to-end: the search wins cleanly (0 rejections, 0 escapes),
    persists a kvariant entry, a fresh all-auto DPF resolves it with
    provenance "searched", and the dispatched program stays bit-exact
    against the scalar oracle."""
    _fresh_cache(tmp_path, monkeypatch)
    n, batch, prf = 256, 8, prf_ref.PRF_CHACHA20
    rec = ks.kernel_search(n, batch, prf_method=prf, reps=1,
                           generations=2, population=3, distinct=4)
    assert rec["searched"] is True and rec["gated"] is True
    m = rec["measured"]
    assert m["rejected"] == 0 and m["gate_escapes"] == 0
    assert m["candidates_tried"] >= 3
    assert all(p["parity"] for p in rec["pallas_pinned"])
    # the winner can never regress its seeds
    assert m["best_s"] <= (m["seed_s"] or np.inf) + 1e-12
    assert m["best_s"] <= (m["heuristic_s"] or np.inf) + 1e-12

    # warm re-search answers from the cache without measuring
    again = ks.kernel_search(n, batch, prf_method=prf, reps=1,
                             generations=2, population=3, distinct=4)
    assert again["searched"] is False
    assert again["knobs"] == rec["knobs"]

    # consumption: all-auto resolution (NO EvalConfig — its defaults
    # are explicit pins that outrank the searched slot)
    dpf = dpf_tpu.DPF(prf=prf, scheme="sqrtn")
    table = np.random.default_rng(5).integers(
        0, 2 ** 31, (n, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    kn = dpf.resolved_eval_knobs(batch)
    assert kn["kernel_resolved_from"] == "searched"
    assert kn["kernel_variant"] == rec["knobs"]["kernel_variant"]
    keys = [dpf.gen((i * 31) % n, n)[0] for i in range(batch)]
    assert np.array_equal(np.asarray(dpf.eval_tpu(keys)),
                          np.asarray(dpf.eval_cpu(keys)))
    # explicit config knobs still outrank the searched entry
    dpf2 = dpf_tpu.DPF(config=EvalConfig(prf_method=prf, scheme="sqrtn",
                                         radix=2, row_chunk=None,
                                         dot_impl=None,
                                         kernel_impl="xla"))
    dpf2.eval_init(table)
    assert dpf2.resolved_eval_knobs(batch)["kernel_resolved_from"] \
        == "config"


def test_pre_variant_cache_entry_still_parses(tmp_path, monkeypatch):
    """A pre-search tuning.json (eval entries only, no kvariant kind)
    still loads and resolves to the exact pre-variant knob dict — the
    old grammar is untouched."""
    cache = _fresh_cache(tmp_path, monkeypatch)
    n, batch = 256, 8
    cache.store(cache_key("eval", n=n, entry_size=16, batch=batch,
                          prf_method=2, scheme="sqrtn", radix=2),
                {"knobs": {"row_chunk": 4, "dot_impl": "i32",
                           "kernel_impl": "xla"}})
    dpf = dpf_tpu.DPF(prf=2, scheme="sqrtn")
    table = np.random.default_rng(5).integers(
        0, 2 ** 31, (n, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    kn = dpf.resolved_eval_knobs(batch)
    assert kn == {"dot_impl": "i32", "row_chunk": 4,
                  "kernel_impl": "xla", "kernel_resolved_from": "tuned"}


def test_searched_row_chunk_never_mixes_with_tuned_kernel(tmp_path,
                                                          monkeypatch):
    """The searched row_chunk/dot_impl ride ONLY with the searched
    kernel: a config kernel pin drops the variant and its row_chunk."""
    from dpf_tpu.utils import compat
    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel", lambda: True)
    _fresh_cache(tmp_path, monkeypatch)
    dpf = dpf_tpu.DPF(prf=2, scheme="sqrtn")
    table = np.zeros((256, 16), np.int32)
    dpf.eval_init(table)
    v = ks.KernelVariant(family="pallas", tb=8, max_cells=512,
                         row_chunk=8)
    dpf._tuned_cache[dpf._pow2_domain(8)] = {"_searched": v.eval_knobs()}
    kn = dpf.resolved_eval_knobs(8)
    assert kn["kernel_resolved_from"] == "searched"
    assert kn["kernel_impl"] == "pallas" and kn["row_chunk"] == 8
    cfg = EvalConfig(prf_method=2, scheme="sqrtn", radix=2,
                     kernel_impl="xla", dot_impl=None, row_chunk=None)
    dpf2 = dpf_tpu.DPF(config=cfg)
    dpf2.eval_init(table)
    dpf2._tuned_cache[dpf2._pow2_domain(8)] = {"_searched": v.eval_knobs()}
    kn2 = dpf2.resolved_eval_knobs(8)
    assert kn2["kernel_resolved_from"] == "config"
    assert kn2["kernel_impl"] == "xla"
    assert kn2.get("kernel_variant") is None
    assert kn2["row_chunk"] != 8 or kn2["row_chunk"] is None


def test_row_chunk_halving_surfaced(tmp_path, monkeypatch):
    """Satellite: the silent VMEM-cap halving in pallas_sqrt_row_chunk
    is surfaced — resolution reports row_chunk_effective and counts the
    halved request at api.sqrt_row_chunk_halved."""
    from dpf_tpu.utils import compat
    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel", lambda: True)
    _fresh_cache(tmp_path, monkeypatch)
    n, batch = 4096, 8                      # K=64: cap(512) = rc 2
    dpf = dpf_tpu.DPF(prf=2, scheme="sqrtn")
    dpf.eval_init(np.zeros((n, 16), np.int32))
    v = ks.KernelVariant(family="pallas", tb=8, max_cells=512,
                         row_chunk=64)
    dpf._tuned_cache[dpf._pow2_domain(batch)] = {
        "_searched": v.eval_knobs()}
    before = sum(SWALLOWED_ERRORS.get("api.sqrt_row_chunk_halved",
                                      {}).values())
    kn = dpf.resolved_eval_knobs(batch)
    assert kn["kernel_impl"] == "pallas" and kn["row_chunk"] == 64
    assert kn["row_chunk_effective"] < 64
    after = sum(SWALLOWED_ERRORS.get("api.sqrt_row_chunk_halved",
                                     {}).values())
    assert after == before + 1


def test_route_event_carries_kernel_provenance(tmp_path, monkeypatch):
    """SchemeRouter's dispatch_kernel_info threads resolution
    provenance (and, for Pallas, the effective row chunk) into every
    route event."""
    from dpf_tpu.obs.flight import FLIGHT
    from dpf_tpu.serve.router import SchemeRouter

    _fresh_cache(tmp_path, monkeypatch)
    table = np.arange(256 * 2, dtype=np.int32).reshape(256, 2)
    rt = SchemeRouter(table, prf=dpf_tpu.DPF.PRF_DUMMY, cap=8,
                      buckets=(4,), probe=False)
    info = rt.dispatch_kernel_info("sqrtn", 4)
    assert info["kernel_impl"] == "xla"
    assert info["kernel_resolved_from"] in ("heuristic", "tuned",
                                            "config", "degraded")
    assert "row_chunk_effective" not in info    # xla: no VMEM cap
    assert rt.dispatch_kernel_info("no-such-construction", 4) == {}
    # steer the cost model so the sqrtn construction wins the route:
    # its resolution is the one that reports searched/halved provenance
    for lb in rt.engines:
        rt._costs[(lb, 4)] = 0.5
    rt._costs[("sqrtn", 4)] = 0.001
    mark = FLIGHT.recorded
    rt.route(4)
    ev = [e for e in FLIGHT.dump() if e["seq"] > mark
          and e["kind"] == "route"][-1]
    assert ev["construction"] == "sqrtn"
    assert ev["kernel_impl"] == "xla"
    assert ev["kernel_resolved_from"] == info["kernel_resolved_from"]


def test_warmup_precompiles_searched_variant(tmp_path, monkeypatch):
    """ServingEngine.warmup through a searched kvariant entry: the
    engine's resolver answers "searched" and the first real dispatch is
    served by the warmed program, bit-exact."""
    from dpf_tpu.serve import ServingEngine

    cache = _fresh_cache(tmp_path, monkeypatch)
    n, batch, prf = 256, 4, prf_ref.PRF_CHACHA20
    v = ks.KernelVariant(family="xla", row_chunk=4, dot_impl="i32")
    cache.store(cache_key(ks.VARIANT_KIND, n=n, entry_size=16,
                          batch=batch, prf_method=prf, scheme="sqrtn",
                          radix=2),
                {"knobs": v.eval_knobs()})
    dpf = dpf_tpu.DPF(prf=prf, scheme="sqrtn")
    table = np.random.default_rng(9).integers(
        0, 2 ** 31, (n, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    eng = ServingEngine(dpf, buckets=(batch,), warmup=True)
    try:
        assert dpf.resolved_eval_knobs(batch)["kernel_resolved_from"] \
            == "searched"
        keys = [dpf.gen(i * 17 % n, n)[0] for i in range(batch)]
        out = np.asarray(eng.submit(keys).result())
        assert np.array_equal(out, np.asarray(dpf.eval_cpu(keys)))
    finally:
        eng.drain()


# ----------------------------------------------- warm second process

_WARM_DRIVER = textwrap.dedent("""
    import importlib
    import json
    import numpy as np
    import dpf_tpu
    ks = importlib.import_module("dpf_tpu.tune.kernel_search")

    rec = ks.kernel_search(256, 8, prf_method=2, reps=1, generations=2,
                           population=3, distinct=4)
    dpf = dpf_tpu.DPF(prf=2, scheme="sqrtn")
    table = np.random.default_rng(5).integers(
        0, 2 ** 31, (256, 16), dtype=np.int32, endpoint=False)
    dpf.eval_init(table)
    kn = dpf.resolved_eval_knobs(8)
    keys = [dpf.gen(i * 31 % 256, 256)[0] for i in range(8)]
    ok = bool(np.array_equal(np.asarray(dpf.eval_tpu(keys)),
                             np.asarray(dpf.eval_cpu(keys))))
    print(json.dumps({"searched": rec["searched"],
                      "knobs": rec["knobs"],
                      "resolved_from": kn["kernel_resolved_from"],
                      "variant": kn.get("kernel_variant"),
                      "parity": ok}))
""")


def test_kvariant_cache_round_trip_second_process(tmp_path):
    """Acceptance: a SECOND process with the warm tuning cache loads
    the searched variant without re-searching and resolves it with
    provenance "searched"."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DPF_TPU_TUNE_CACHE": str(tmp_path / "tuning.json"),
        "PYTHONPATH": REPO,
    })

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _WARM_DRIVER], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["searched"] is True
    assert cold["resolved_from"] == "searched" and cold["parity"]
    warm = run()
    assert warm["searched"] is False            # no re-search
    assert warm["knobs"] == cold["knobs"]
    assert warm["resolved_from"] == "searched" and warm["parity"]
    assert warm["variant"] == cold["knobs"]["kernel_variant"]


def test_kernel_search_sweep_dryrun_record(tmp_path, monkeypatch):
    """The --autotune-kernel --dryrun record: checked means 0 gate
    escapes AND full Pallas parity, and the winner persisted."""
    cache = _fresh_cache(tmp_path, monkeypatch)
    rec = ks.kernel_search_sweep(dryrun=True, quiet=True)
    assert rec["dryrun"] is True and rec["checked"] is True
    (pt,) = rec["points"]
    assert pt["rejected"] == 0 and pt["gate_escapes"] == 0
    assert pt["pallas_all_parity"] is True
    key = cache_key(ks.VARIANT_KIND, n=pt["entries"], entry_size=16,
                    batch=pt["batch"], prf_method=2, scheme="sqrtn",
                    radix=2)
    stored = tcache.default_cache(refresh=True).lookup(key)
    assert stored is not None
    assert stored["knobs"] == pt["winner_knobs"]
