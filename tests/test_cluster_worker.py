"""Socket tier of the serving cluster: one real ``cluster_worker`` OS
process behind a ``RemoteHost`` client.

Unlike tests/test_multihost.py's jax.distributed rehearsal this needs
NO multi-process jax backend — each worker is its own single-process
jax runtime, so the round-trip runs on every toolchain (it only costs a
subprocess spawn + jax import, hence one worker, small table).
"""

import numpy as np
import pytest

from dpf_tpu import DPF
from dpf_tpu.core import expand, keygen
from dpf_tpu.parallel.cluster import HostUnreachable
from dpf_tpu.parallel.cluster_net import make_table, spawn_worker

N, ENTRY, SEED = 128, 4, 9


@pytest.fixture(scope="module")
def worker():
    node = spawn_worker({"label": "host0", "row0s": [0, 64],
                         "granule": 64, "n": N, "entry_size": ENTRY,
                         "table_seed": SEED, "prf_method": DPF.PRF_DUMMY,
                         "process_index": 0, "buckets": [1, 2, 4],
                         "max_in_flight": 2}, timeout_s=120.0)
    yield node
    node.close()


def test_worker_round_trip(worker):
    # hello handshake cached the shard geometry
    assert worker.granules == (0, 64)
    assert worker.n == N and worker.entry_size == ENTRY
    assert worker.process_index == 0

    # serve: the worker rebuilt the SAME deterministic table, so its
    # full-coverage partial sum equals the local oracle answer
    dpf = DPF(prf=DPF.PRF_DUMMY)
    dpf.eval_init(make_table(N, ENTRY, SEED))
    keys = [dpf.gen((i * 13) % N, N, seed=b"worker-%d" % i)[0]
            for i in range(4)]
    pk = keygen.decode_keys_batched(keys)
    out = worker.submit(pk).result()
    assert np.array_equal(out, np.asarray(dpf.eval_tpu(keys)))

    # liveness + management ops over the same connection
    status = worker.heartbeat()
    assert status["host"] == "host0"
    stats = worker.stats()
    assert stats["counters"]["batches_submitted"] >= 1
    assert worker.counters().batches_submitted >= 1


def test_worker_error_envelope(worker):
    # a bad op comes back as an error envelope, raised client-side,
    # and the connection stays serviceable afterwards
    with pytest.raises(RuntimeError):
        worker._call({"op": "no-such-op"})
    assert worker.heartbeat()["host"] == "host0"


def test_killed_worker_raises_host_unreachable():
    node = spawn_worker({"label": "victim", "row0s": [0],
                         "granule": N, "n": N, "entry_size": ENTRY,
                         "table_seed": SEED, "prf_method": DPF.PRF_DUMMY,
                         "process_index": 1}, timeout_s=120.0)
    try:
        assert node.heartbeat()["host"] == "victim"
        node.proc.kill()
        node.proc.wait()
        with pytest.raises(HostUnreachable):
            for _ in range(3):     # first call may still flush a frame
                node.heartbeat()   # into the dead socket's buffers
    finally:
        node.kill()


def test_two_process_cluster_survives_host_kill():
    """The multiprocess rehearsal the --multihost bench runs, minimal:
    two real worker processes behind a ClusterRouter, SIGKILL one
    mid-stream, assert the flight-recorded drop -> degrade decision and
    bit-exact answers before AND after the loss.

    The socket tier needs no cross-process jax collectives (each worker
    is its own single-process runtime); only a sandbox that cannot
    spawn subprocesses skips.
    """
    from dpf_tpu.obs.flight import FLIGHT, flight_dump
    from dpf_tpu.parallel.cluster import ClusterRouter
    from dpf_tpu.parallel.cluster_net import spawn_cluster

    seq0 = FLIGHT.recorded
    try:
        nodes = spawn_cluster(N, ENTRY, 2, table_seed=SEED,
                              prf_method=DPF.PRF_DUMMY, buckets=(1, 2, 4),
                              timeout_s=120.0)
    except HostUnreachable as e:        # no-subprocess sandbox
        pytest.skip("cannot spawn cluster workers here: %s" % e)
    dpf = DPF(prf=DPF.PRF_DUMMY)
    dpf.eval_init(make_table(N, ENTRY, SEED))
    keys = [dpf.gen((i * 7) % N, N, seed=b"2proc-%d" % i)[0]
            for i in range(4)]
    ref = np.asarray(dpf.eval_tpu(keys))
    c = ClusterRouter(nodes, granule=N // 2,
                      table_perm=expand.permute_table(
                          make_table(N, ENTRY, SEED)),
                      policy="degrade", prf_method=DPF.PRF_DUMMY,
                      spare_engine_kw={"buckets": (1, 2, 4)})
    try:
        assert np.array_equal(c.submit_resilient(keys).result(), ref)
        nodes[1].proc.kill()            # a REAL process death
        nodes[1].proc.wait()
        assert np.array_equal(c.submit_resilient(keys).result(), ref)
        assert c.host_state("host1") == "down"
        assert c.decision_counts["degrade"] == 1
        evs = [e for e in flight_dump() if e["seq"] > seq0]
        assert any(e["kind"] == "host_drop" and e["host"] == "host1"
                   for e in evs)
        assert any(e["kind"] == "cluster_recovery"
                   and e["host"] == "host1"
                   and e["decision"] == "degrade" and e["ok"]
                   for e in evs)
    finally:
        c.close()
        for node in nodes:
            node.kill()
