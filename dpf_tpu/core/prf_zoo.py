"""Extended PRF zoo: round-parameterized Salsa/ChaCha cores + benchmark.

The reference's paper tree benchmarked 13 candidate PRFs to pick GPU-friendly
ones (``paper/kernel/gpu/dpf_gpu/prf/prf.cu:8-95``; most implementations
were never shipped).  This module reproduces that exploration capability for
TPU: the shipped wire-compatible PRFs stay in ``prf.py``; here are
round-count variants (Salsa20/8, Salsa20/20, ChaCha8, ChaCha20, ...) and a
throughput benchmark to compare candidates on real hardware.

NOTE: zoo variants are NOT wire-compatible with the reference keys — they
exist for PRF-selection studies, like the paper's.  15 candidates: the
paper's 13 plus the two block-PRG additions (``chacha12_blk`` /
``salsa20_12_blk``, 4 GGM children per core call).  Of these,
``highway_proxy`` is an op-mix *proxy* for the HighwayHash family (same
instruction mix and widths, NOT the published constants/algorithm — see
``prf_zoo_hash.py``); every summary of the zoo should carry that asterisk.
"""

from __future__ import annotations

import time

import numpy as np

from . import u128
from .prf import _chacha_state, _rotl, _salsa_state


def make_salsa_core(rounds: int):
    """Salsa20/<rounds> with the framework's key/pos placement."""
    assert rounds % 2 == 0

    def fn(seeds, pos: int):
        import jax
        import jax.numpy as jnp
        init = _salsa_state(seeds, pos)

        def double_round(_, s):
            x = [s[i] for i in range(16)]
            for (a, b, c, d) in ((0, 4, 8, 12), (5, 9, 13, 1),
                                 (10, 14, 2, 6), (15, 3, 7, 11),
                                 (0, 1, 2, 3), (5, 6, 7, 4),
                                 (10, 11, 8, 9), (15, 12, 13, 14)):
                x[b] = x[b] ^ _rotl(x[a] + x[d], 7)
                x[c] = x[c] ^ _rotl(x[b] + x[a], 9)
                x[d] = x[d] ^ _rotl(x[c] + x[b], 13)
                x[a] = x[a] ^ _rotl(x[d] + x[c], 18)
            return jnp.stack(x)

        x = jax.lax.fori_loop(0, rounds // 2, double_round, init)
        out = x + init
        return u128._stack_last([out[4], out[3], out[2], out[1]])

    fn.__name__ = "salsa20_%d" % rounds
    return fn


def make_chacha_core(rounds: int):
    """ChaCha<rounds> with the framework's key/pos placement."""
    assert rounds % 2 == 0

    def fn(seeds, pos: int):
        import jax
        import jax.numpy as jnp
        init = _chacha_state(seeds, pos)

        def double_round(_, s):
            x = [s[i] for i in range(16)]
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

        x = jax.lax.fori_loop(0, rounds // 2, double_round, init)
        out = x + init
        return u128._stack_last([out[7], out[6], out[5], out[4]])

    fn.__name__ = "chacha%d" % rounds
    return fn


from .prf_zoo_hash import HASH_ZOO  # noqa: E402 (needs _rotl et al above)

ZOO = {
    "salsa20_8": make_salsa_core(8),
    "salsa20_12": make_salsa_core(12),
    "salsa20_20": make_salsa_core(20),
    "chacha8": make_chacha_core(8),
    "chacha12": make_chacha_core(12),
    "chacha20": make_chacha_core(20),
    **HASH_ZOO,
}


def _blk_candidate(words_fn):
    def fn(seeds, pos: int):
        from .prf import _prf_blk
        return _prf_blk(lambda s, c: words_fn(s, c, None), seeds, pos)
    return fn


_BLK_WORDS_FNS = {}  # name -> (seeds, ctr, unroll) 16-word core closure


def _init_blk_candidates():
    """Block-PRG candidates (core/prf_ref.py::prf_*_blk): one core call
    yields FOUR GGM children, so their selection metric is children/sec
    = 4x their calls/sec (``CHILDREN_PER_CALL``)."""
    from .prf import _chacha20_12_words_jax, _salsa20_12_words_jax
    ZOO["chacha12_blk"] = _blk_candidate(_chacha20_12_words_jax)
    ZOO["salsa20_12_blk"] = _blk_candidate(_salsa20_12_words_jax)
    _BLK_WORDS_FNS["chacha12_blk"] = _chacha20_12_words_jax
    _BLK_WORDS_FNS["salsa20_12_blk"] = _salsa20_12_words_jax


_init_blk_candidates()

# GGM children produced per candidate call (default 1): the DPF cost
# model counts children, so benchmark_zoo scales by this
CHILDREN_PER_CALL = {"chacha12_blk": 4, "salsa20_12_blk": 4}


def benchmark_zoo(n_calls=1 << 20, reps=5, names=None):
    """Throughput of each candidate on the default backend.

    Returns {name: ggm_children_per_sec} — calls/sec scaled by
    ``CHILDREN_PER_CALL`` (1 for classic per-child PRFs, 4 for the
    block-PRG candidates), the metric the DPF cost model actually
    selects on.  For the block-PRG candidates the timed program
    materializes ALL FOUR 128-bit children from the one core block (the
    ``prf_multi`` serving path), so the x4 scaling never excludes the
    extraction cost.  Prints one result-dict line
    per candidate (the paper's PRF-selection experiment, on TPU).
    """
    import json

    import jax
    import jax.numpy as jnp

    from .prf import _blk_group

    rng = np.random.default_rng(0)
    seeds = jnp.asarray(
        rng.integers(0, 2 ** 32, (n_calls, 4), dtype=np.uint32))
    results = {}
    for name in (names or ZOO):
        kids = CHILDREN_PER_CALL.get(name, 1)
        if kids > 1:
            # one block -> all four children, as prf_multi serves them
            wf = _BLK_WORDS_FNS[name]

            def all_children(s, wf=wf):
                out = wf(s, 0, None)
                return jnp.stack([_blk_group(out, 4 * b)
                                  for b in range(4)])

            fn = jax.jit(all_children)
        else:
            fn = jax.jit(lambda s, f=ZOO[name]: f(s, 1))
        jax.block_until_ready(fn(seeds))
        t0 = time.time()
        for _ in range(reps):
            out = fn(seeds)
        jax.block_until_ready(out)
        per_sec = n_calls * reps / (time.time() - t0)
        results[name] = per_sec * kids
        print(json.dumps({"prf_candidate": name, "calls": n_calls,
                          "reps": reps, "children_per_call": kids,
                          "timed_children_materialized": kids,
                          "prf_calls_per_sec": int(per_sec),
                          "ggm_children_per_sec": int(per_sec * kids)}))
    return results
