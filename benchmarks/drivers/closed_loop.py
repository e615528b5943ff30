"""Closed loop: back-to-back ``DPF.eval_tpu`` calls on server 0.

Traffic parameters: ``batch`` keys per call and ``pool_keys`` distinct
keys minted in set-up, so that no call re-sends a key.  The window runs
whole calls while it is open; ``dpfs_per_s`` is the keys answered over
the time from the window's start to the last answer.  A pool that runs
dry closes the window early (said on standard error): the rate stays
the rate of the work done.  The record keeps when each call ended.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from benchmarks.harness import Window


@dataclasses.dataclass
class State:
    dpf: object
    batch: int
    rows: np.ndarray
    keys0: np.ndarray
    keys1: np.ndarray


def setup(ctx) -> State:
    from dpf_tpu import DPF
    dpf = DPF(prf=ctx.prf_id)
    dpf.eval_init(ctx.table)
    ctx.mark("eval_init")
    batch, pool = ctx.traffic["batch"], ctx.traffic["pool_keys"]
    rows, k0, k1 = ctx.keys(dpf, pool + batch, tag=1)
    ctx.mark("keys")
    # the warm-up call has keys of its own: the window's are all fresh
    np.asarray(dpf.eval_tpu(list(k0[pool:])))
    ctx.mark("warm-up call")
    return State(dpf, batch, rows[:pool], k0[:pool], k1[:pool])


def window(st: State, seconds: float, annotate) -> Window:
    outs = []
    ends = []
    sent = 0
    t0 = t_end = time.perf_counter()
    while (sent + st.batch <= len(st.keys0)
           and time.perf_counter() - t0 < seconds):
        with annotate("bench.eval_tpu"):
            outs.append(np.asarray(st.dpf.eval_tpu(
                list(st.keys0[sent:sent + st.batch]))))
        t_end = time.perf_counter()
        ends.append(t_end - t0)
        sent += st.batch
    if t_end - t0 < seconds:
        print("closed_loop: key pool of %d ran dry after %.3f s"
              % (len(st.keys0), t_end - t0), file=sys.stderr)
    return Window(end_to_end={"dpfs_per_s": sent / (t_end - t0)},
                  attempted=sent, failed=0, served=np.arange(sent),
                  shares=np.concatenate(outs),
                  record={"answered": sent, "calls": sent // st.batch,
                          "call_end_s": ends})


def server1(st: State, idx: np.ndarray) -> np.ndarray:
    """The program's server 1 shares of pool keys ``idx``, in calls of
    the window's own batch (short ones padded with repeats)."""
    out = []
    for lo in range(0, len(idx), st.batch):
        keys = list(st.keys1[idx[lo:lo + st.batch]])
        n = len(keys)
        keys += [keys[-1]] * (st.batch - n)
        out.append(np.asarray(st.dpf.eval_tpu(keys))[:n])
    return np.concatenate(out)


def release(st: State) -> None:
    st.dpf.eval_free()
