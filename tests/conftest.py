"""Test configuration: run JAX hermetically on a simulated 8-device CPU mesh.

Multi-chip hardware is not required for tests — sharding correctness is
validated on virtual CPU devices (the TPU answer to "multi-node without a
cluster", SURVEY.md §4).

The recipe (rewrite XLA_FLAGS + pin jax_platforms before any backend
init) lives in ``dpf_tpu.utils.hermetic.force_cpu_mesh``; conftest import
precedes all test code, so this runs before any backend is initialized.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Hermetic caches: the tune/ subsystem persists tuning results (under
# ~/.cache) and XLA executables (under the repo) by default — tests must
# neither read a developer's warm caches nor leave state behind.
# Tests that exercise the caches point them at tmp paths explicitly
# (monkeypatch.setenv).
os.environ["DPF_TPU_TUNE_CACHE"] = "0"
os.environ["DPF_TPU_COMPILE_CACHE"] = "0"

from dpf_tpu.utils.hermetic import force_cpu_mesh  # noqa: E402

force_cpu_mesh(8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop jit executables after every test module.

    The AES-circuit graphs (bitsliced XLA + plane-domain Pallas) leave
    multi-GB compiled executables in the jit cache; accumulated across
    modules the suite's RSS passed 30 GB and a later XLA-CPU compile
    segfaulted (deterministic, 2026-07-30).  Re-compiles
    within a module still share the cache; cross-module reuse is rare
    and not worth the blowup.
    """
    yield
    import jax

    jax.clear_caches()
