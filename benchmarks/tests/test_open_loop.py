"""The open-loop client against a simulated device of fixed service
time: its latencies follow the queue that the schedule makes, and no
answer waits behind a run of late submits."""

import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.drivers import open_loop
from dpf_tpu.serve.engine import ServingEngine

SERVICE_S = 0.0128


class _DeviceArray:
    """Ready at ``end``; reading it waits until then."""

    def __init__(self, end, rows):
        self.end, self.rows = end, rows

    def is_ready(self):
        return time.perf_counter() >= self.end

    def __array__(self, dtype=None, copy=None):
        wait = self.end - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        return np.zeros((self.rows, 16), np.int32)


class _Packed:
    def __init__(self, batch):
        self.batch = batch

    def slice(self, lo, hi):
        return _Packed(hi - lo)

    def pad_to(self, size):
        return _Packed(size)


class _Server:
    """One device that runs dispatches one after another."""
    table_num_entries = 1 << 16
    table_effective_entry_size = 16
    BATCH_SIZE = 512

    def __init__(self):
        self.free = 0.0

    def _decode_batch(self, keys):
        return _Packed(len(keys))

    def _dispatch_packed(self, packed):
        self.free = max(time.perf_counter(), self.free) + SERVICE_S
        return _DeviceArray(self.free, packed.batch)


def queue_ms(times):
    """Latency of each arrival in a FIFO queue of one server."""
    free, out = 0.0, []
    for t in times:
        free = max(t, free) + SERVICE_S
        out.append(free - t)
    return np.asarray(out) * 1e3


@pytest.mark.parametrize("rate", [59.2, 70.0])
def test_latency_follows_the_queue(rate):
    times = open_loop.arrival_times(rate, 4.0, 2 ** 33 + 5)
    server = _Server()
    st = open_loop.State(server, ServingEngine(server), times, None,
                         np.zeros((len(times), 524), np.int32), None)
    win = open_loop.window(st, 4.0, harness.no_annotation)
    got = np.asarray(win.record["latency_ms"])
    want = queue_ms(times)
    assert win.attempted == len(times) and (got > 0).all()
    # polling, sleeps and the engine's host work add a few ms at most
    for q in (50, 95, 100):
        assert abs(np.percentile(got, q) - np.percentile(want, q)) < 6.0
    assert win.end_to_end["latency_p95_ms"] == pytest.approx(
        np.percentile(got, 95))


def test_arrivals_offer_the_same_bursts_to_every_seed():
    a = open_loop.arrival_times(59.2, 30.0, 1)
    b = open_loop.arrival_times(59.2, 30.0, 2 ** 40 + 3)
    assert not np.array_equal(a, b)
    ga, gb = np.diff(a, prepend=0), np.diff(b, prepend=0)
    # one fixed order of gaps, rotated: b's gaps are a's from another start
    k = int(np.flatnonzero(np.isclose(ga, gb[0]))[0])
    assert np.allclose(np.roll(ga, -k), gb)
    assert np.percentile(queue_ms(a), 95) == pytest.approx(
        np.percentile(queue_ms(b), 95), rel=0.1)
