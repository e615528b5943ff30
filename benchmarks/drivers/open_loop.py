"""Open loop: one-key lookups through the serving engine on a schedule.

Traffic parameters: ``rate`` (requests per second, fixed in the mix)
and ``warmup_requests``.  Arrivals over the window are drawn in set-up
(``arrival_times``); each request carries one fresh key.  No cap is put
on outstanding requests.

One thread plays the schedule, as a front end that owns the engine
does.  It hands out every answer whose device work has finished,
oldest first, through ``result()``; then, when a request is due, it
goes to ``ServingEngine.submit`` at once; else the thread sleeps in
steps of ``POLL_S``.  It never blocks in ``result()`` while requests
remain to be sent, so the engine's own backpressure (``max_in_flight``)
is the only thing that holds a submit back, and no answer waits behind
a run of late submits.

Latency runs from the scheduled arrival until the share is in the
client's hands, over every request of the window; the stamps of each
request (submit start, submit end, answer) go into the record.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from benchmarks.harness import Window, rng_for

POLL_S = 200e-6
GAPS_SEED = 0x0DF0


def arrival_times(rate: float, duration_s: float, seed: int) -> np.ndarray:
    """Open-loop arrival offsets (seconds) over ``duration_s``.

    After ``dpf_tpu/serve/loadgen.poisson_trace`` (memoryless arrivals at
    ``rate``, one key each), with the exponential gaps stratified: the
    gaps are the ``rate * duration_s`` quantiles of the exponential
    distribution at the midpoints of equal steps, put in one order that
    is fixed for the mix, and the seed picks where in that order the
    window starts (a rotation).  Every seed then offers the same gaps and
    the same bursts, so a seed moves where a burst falls, not how large
    it is."""
    m = int(round(rate * duration_s))
    u = (np.arange(m) + 0.5) / m
    gaps = np.random.default_rng(GAPS_SEED).permutation(
        -np.log1p(-u) / rate)
    start = int(rng_for(seed, 3).integers(m))
    return np.cumsum(np.roll(gaps, -start))


@dataclasses.dataclass
class State:
    dpf: object
    engine: object
    times: np.ndarray
    rows: np.ndarray
    keys0: np.ndarray
    keys1: np.ndarray


def setup(ctx) -> State:
    from dpf_tpu import DPF
    dpf = DPF(prf=ctx.prf_id)
    dpf.eval_init(ctx.table)
    engine = dpf.serving_engine()
    ctx.mark("eval_init and engine")
    times = arrival_times(ctx.traffic["rate"], ctx.seconds, ctx.seed)
    m, w = len(times), ctx.traffic["warmup_requests"]
    rows, k0, k1 = ctx.keys(dpf, m + w, tag=2)
    ctx.mark("keys")
    for j in range(m, m + w):   # warm-up requests have keys of their own
        engine.submit([k0[j]]).result()
    ctx.mark("warm-up requests")
    return State(dpf, engine, times, rows[:m], k0[:m], k1[:m])


def answered(fut) -> bool:
    """Whether ``fut.result()`` would return without waiting on the
    device: every dispatched part of it has left the device.

    ``EngineFuture`` has no public test for this, so it reads the parts
    the future holds; a future that holds none it can read counts as
    answered, and ``result()`` then blocks as it would anyway."""
    if fut.done():
        return True
    for p in getattr(fut, "_parts", ()):
        out, dev = getattr(p, "out", None), getattr(p, "dev", None)
        if out is None and dev is not None and not dev.is_ready():
            return False
    return True


def window(st: State, seconds: float, annotate) -> Window:
    m = len(st.times)
    width = st.dpf.table_effective_entry_size
    shares = np.zeros((m, width), np.int32)
    stamps = np.zeros((3, m))    # submit start, submit end, answer
    waiting = collections.deque()
    before = st.engine.stats.as_dict()
    t0 = time.perf_counter()
    due = t0 + st.times
    j = 0
    while j < m or waiting:
        if waiting and (j == m or answered(waiting[0][1])):
            k, fut = waiting.popleft()
            with annotate("bench.result"):
                out = fut.result()
            stamps[2, k] = time.perf_counter()
            shares[k] = out[0]
            continue
        now = time.perf_counter()
        if now >= due[j]:
            stamps[0, j] = now
            with annotate("bench.submit"):
                waiting.append((j, st.engine.submit([st.keys0[j]])))
            stamps[1, j] = time.perf_counter()
            j += 1
        else:
            time.sleep(min(due[j] - now, POLL_S))
    after = st.engine.stats.as_dict()
    lat_ms = (stamps[2] - due) * 1e3
    engine = {k: after[k] - before[k]
              for k in ("dispatches", "padded_queries", "queries_submitted")}
    return Window(end_to_end={"latency_p50_ms":
                              float(np.percentile(lat_ms, 50)),
                              "latency_p95_ms":
                              float(np.percentile(lat_ms, 95))},
                  attempted=m, failed=0, served=np.arange(m), shares=shares,
                  record={"answered": m, "engine": engine,
                          "latency_ms": lat_ms.tolist(),
                          "client_late_s": (stamps[0] - due).tolist(),
                          "submit_s": (stamps[1] - stamps[0]).tolist()})


def server1(st: State, idx: np.ndarray) -> np.ndarray:
    """The program's server 1 shares of pool keys ``idx``, in requests
    as large as the engine's smallest bucket: the window's only program."""
    size = st.engine.buckets.sizes[0]
    out = []
    for lo in range(0, len(idx), size):
        out.append(np.asarray(st.engine.submit(
            list(st.keys1[idx[lo:lo + size]])).result()))
    return np.concatenate(out)


def release(st: State) -> None:
    st.engine.close()
    st.dpf.eval_free()
