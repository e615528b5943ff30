"""Two-process jax.distributed test on the CPU backend.

The TPU answer to "multi-node without a cluster": two OS processes form a
real jax.distributed cluster over localhost (coordinator + worker), build
one global ("batch", "table") mesh spanning both processes' virtual CPU
devices, and run a table-sharded DPF evaluation whose psum crosses the
process boundary.  Each worker asserts recovery and prints MULTIHOST_OK.
"""

import os
import socket
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_mesh():
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # worker pins its own device count
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(
        [sys.executable, WORKER, str(rank), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        text=True) for rank in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multihost workers timed out; outputs so far: %r"
                    % outs)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, "rank %d failed:\n%s" % (rank, out)
        assert "MULTIHOST_OK %d" % rank in out, out


# ------------------------------------------- initialize() failure story

@pytest.fixture()
def _fresh_multihost():
    """Snapshot/restore the module's init bookkeeping around a test."""
    from dpf_tpu.parallel import multihost
    saved = (multihost._initialized, multihost._init_error)
    multihost._initialized, multihost._init_error = False, None
    yield multihost
    multihost._initialized, multihost._init_error = saved


def test_initialize_timeout_kwarg_passthrough(_fresh_multihost,
                                              monkeypatch):
    """``initialization_timeout_s`` reaches jax.distributed.initialize
    as ``initialization_timeout`` (when the signature has it) and a
    timeout failure surfaces its CAUSE through init_error()."""
    multihost = _fresh_multihost
    import jax
    seen = {}

    def fake_init(coordinator_address=None, num_processes=None,
                  process_id=None, initialization_timeout=300, **kw):
        seen["timeout"] = initialization_timeout
        raise RuntimeError("deadline exceeded waiting for coordinator")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    with pytest.raises(RuntimeError):
        multihost.initialize(coordinator_address="127.0.0.1:1",
                             num_processes=2, process_id=0,
                             initialization_timeout_s=7)
    assert seen["timeout"] == 7
    err = multihost.init_error()
    assert err is not None and "InitializationTimeout" in err
    assert "127.0.0.1:1" in err and "7s" in err


def test_initialize_autodetect_fallback_records_cause(
        _fresh_multihost, monkeypatch):
    multihost = _fresh_multihost
    import jax

    def fake_init(**kw):
        raise RuntimeError("no cluster detected")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.delenv("DPF_EXPECT_CLUSTER", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    # no args + no cluster-looking env: silent fallback, cause recorded
    assert multihost.initialize() is False
    assert "no cluster detected" in multihost.init_error()


def test_initialize_raises_when_cluster_expected(_fresh_multihost,
                                                 monkeypatch):
    multihost = _fresh_multihost
    import jax

    def fake_init(**kw):
        raise RuntimeError("boom")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.setenv("DPF_EXPECT_CLUSTER", "1")
    with pytest.raises(RuntimeError):
        multihost.initialize()      # env says cluster: fail LOUDLY
    assert "boom" in multihost.init_error()


def test_cluster_expected_env_hints(monkeypatch):
    from dpf_tpu.parallel.multihost import _cluster_expected
    for var in ("DPF_EXPECT_CLUSTER", "JAX_COORDINATOR_ADDRESS",
                "COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE",
                "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(var, raising=False)
    assert _cluster_expected() is False
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    assert _cluster_expected() is True
    monkeypatch.setenv("JAX_NUM_PROCESSES", "1")
    assert _cluster_expected() is False
    monkeypatch.setenv("JAX_NUM_PROCESSES", "not-a-number")
    assert _cluster_expected() is False   # unparsable hint != cluster
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "a,b")
    assert _cluster_expected() is True
    # the explicit override wins in BOTH directions
    monkeypatch.setenv("DPF_EXPECT_CLUSTER", "0")
    assert _cluster_expected() is False
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES")
    monkeypatch.setenv("DPF_EXPECT_CLUSTER", "1")
    assert _cluster_expected() is True
