"""The chip entry points (``chip_smoke.py``, ``bench.py``) and the
compile-cache placement, on the CPU.

The smoke script's phase functions run here at N = 2^12 with the same
checks they make on the chip (the Pallas phase in the TPU-semantics
interpreter, no ``tpu_custom_call`` to look for); its device check lives
in ``main()`` only, and both scripts must refuse a CPU backend.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import dpf_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

N = 1 << 12
BATCH = 16


@pytest.fixture(scope="module")
def table():
    return chip_smoke.make_table(N, 0)


@pytest.mark.parametrize("prf", [dpf_tpu.PRF_AES128, dpf_tpu.PRF_CHACHA20])
def test_pir_phase(table, prf):
    recs = []
    servers, idx, keys, shares = chip_smoke.phase_pir(table, prf, BATCH,
                                                      recs.append)
    assert recs[-1]["ok"] and recs[-1]["rows"] == BATCH
    assert len(idx) == len(set(idx.tolist())) == BATCH
    assert {"compile_s", "cold_s", "warm_s"} <= set(recs[0])


def test_engine_phase(table):
    servers, idx, keys, _ = chip_smoke.phase_pir(
        table, dpf_tpu.PRF_CHACHA20, BATCH, lambda r: None)
    recs = []
    chip_smoke.phase_engine(servers, idx, keys, table, recs.append,
                            sizes=(1, 5, 9), buckets=(4, 16))
    assert recs[-1]["ok"] and recs[-1]["buckets"] == [4, 16]


@pytest.mark.parametrize("family", ["binary", "radix4", "sqrtn"])
def test_pallas_phase_interpret(table, family, monkeypatch):
    """The Pallas families against the XLA path through ``eval_tpu``,
    resolved from "config".  The plane-AES family is left to
    test_aes_planes.py: its unrolled cipher under the jitted
    interpreter takes minutes of XLA-CPU compile."""
    from jax.experimental.pallas import tpu as pltpu

    from dpf_tpu.utils import compat
    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                        lambda backend=None: True)
    recs = []
    with pltpu.force_tpu_interpret_mode():
        chip_smoke.phase_pallas(table, 8, recs.append, want_kernel=False,
                                families=(family,))
    assert recs[-1]["ok"] and recs[-1]["resolved_from"] == "config"


def test_one_chip_phases_together(table, monkeypatch):
    """``main``'s one-chip sequence at N = 2^12, ChaCha Pallas families
    in the TPU interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    from dpf_tpu.utils import compat
    monkeypatch.setattr(compat, "has_pallas_sqrt_kernel",
                        lambda backend=None: True)
    recs = []
    with pltpu.force_tpu_interpret_mode():
        chip_smoke.phase_one_chip(table, BATCH, recs.append,
                                  want_kernel=False,
                                  families=("binary", "sqrtn"),
                                  engine_sizes=(1, 5, 9))
    done = {r["phase"] for r in recs if r.get("ok")}
    assert {"pir.AES128", "pir.CHACHA20", "engine", "pallas.CHACHA20.binary",
            "pallas.CHACHA20.sqrtn"} <= done


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_script_refuses_cpu(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, script], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout and "dpfs/sec" not in r.stdout


# ----------------------------------------------------- compile cache

@pytest.fixture()
def fresh_compcache(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from dpf_tpu.tune import compcache
    monkeypatch.setattr(compcache, "_ENABLED_DIR", None)
    monkeypatch.delenv("DPF_TPU_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prior = jax.config.jax_compilation_cache_dir
    yield compcache
    jax.config.update("jax_compilation_cache_dir", prior)
    compilation_cache.reset_cache()


def test_compcache_env_dir_wins(fresh_compcache, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR is JAX's own setting: the program uses
    that directory and configures no other."""
    import jax
    d = str(tmp_path / "jaxcache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    jax.config.update("jax_compilation_cache_dir", d)  # as JAX reads it
    assert fresh_compcache.enable() == d
    assert jax.config.jax_compilation_cache_dir == d
    assert os.path.isdir(d)


def test_compcache_default_is_repo_path(fresh_compcache):
    import jax
    want = os.path.join(REPO, ".jax_compile_cache")
    assert fresh_compcache.default_dir() == want
    assert fresh_compcache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compcache_off_switch(fresh_compcache, monkeypatch, tmp_path):
    import jax
    monkeypatch.setenv("DPF_TPU_COMPILE_CACHE", "0")
    before = jax.config.jax_compilation_cache_dir
    assert fresh_compcache.enable() is None
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setenv("DPF_TPU_COMPILE_CACHE", str(tmp_path))
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        fresh_compcache.enable()  # the path form is gone


# ------------------------------------------- the kernels' int8 contraction

@pytest.mark.parametrize("case", ["random", "extremes", "zeros", "wide"])
def test_digit_dot_is_exact_int32_matmul(case):
    """``_dot_digits`` (the MXU int8 path the Pallas kernels contract
    with) equals the wrapping int32 matmul."""
    import jax.numpy as jnp

    from dpf_tpu.ops.pallas_level import _dot_digits, table_digits
    rng = np.random.default_rng(len(case))
    m, k, e = (8, 4096, 16) if case == "wide" else (8, 64, 5)
    a = rng.integers(-2 ** 31, 2 ** 31, (m, k), dtype=np.int64)
    b = rng.integers(-2 ** 31, 2 ** 31, (k, e), dtype=np.int64)
    if case == "extremes":
        a[:, ::2] = -2 ** 31
        b[::3] = 2 ** 31 - 1
    if case == "zeros":
        a[:] = 0
    a, b = a.astype(np.int32), b.astype(np.int32)
    want = (a.astype(np.uint32).astype(np.uint64)
            @ b.astype(np.uint32).astype(np.uint64)).astype(np.uint32)
    got = _dot_digits(jnp.asarray(a), table_digits(b))
    assert np.array_equal(np.asarray(got).view(np.uint32), want)
