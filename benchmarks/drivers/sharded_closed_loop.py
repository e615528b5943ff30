"""Closed loop on a mesh: back-to-back ``ShardedDPFServer.eval`` calls on
server 0, whose table is row-sharded over the cell's chips.

The configuration's ``mesh.table`` names the chips (the first that many
of ``jax.devices()``); the traffic's ``batch`` keys go in each call and
``pool_keys`` distinct keys are minted in set-up through the client
entry point ``DPF.gen_batch``, so that no call re-sends a key.  The
window runs whole calls while it is open; ``dpfs_per_s`` is the keys
answered over the time from the window's start to the last answer.  A
pool that runs dry closes the window early (said on standard error).
The record keeps when each call ended, the kernel the server resolved
for the batch (``resolved_eval_knobs``' ``kernel_impl``), the device
kind, the chips, the batch and what one chip holds, for the per-layer
readers.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

from benchmarks.harness import Window


@dataclasses.dataclass
class State:
    server: object
    kernel: str
    device_kind: str
    chips: int
    batch: int
    rows: np.ndarray
    keys0: np.ndarray
    keys1: np.ndarray


def setup(ctx) -> State:
    import jax
    from dpf_tpu import DPF
    from dpf_tpu.parallel import sharded
    from dpf_tpu.parallel.sharded import ShardedDPFServer, make_mesh
    # A program without per-chip placement permutes a copy of the whole
    # table on the host first, which at 2^28 rows takes minutes: stop here.
    if not hasattr(sharded, "place_table"):
        raise SystemExit("sharded_closed_loop: dpf_tpu.parallel.sharded has"
                         " no place_table, so no per-chip table placement")
    chips = ctx.config["mesh"]["table"]
    batch, pool = ctx.traffic["batch"], ctx.traffic["pool_keys"]
    mesh = make_mesh(n_table=chips, devices=jax.devices()[:chips])
    server = ShardedDPFServer(ctx.table, mesh, prf_method=ctx.prf_id,
                              batch_size=batch)
    # which kernel serves this batch, as the server resolved it
    kernel = server.resolved_eval_knobs(batch)["kernel_impl"]
    ctx.mark("table on %d chips (%s)" % (chips, kernel))
    rows, k0, k1 = ctx.keys(DPF(prf=ctx.prf_id), pool + batch, tag=1)
    ctx.mark("keys")
    # the warm-up call has keys of its own: the window's are all fresh
    server.eval(list(k0[pool:]))
    ctx.mark("warm-up call")
    return State(server, kernel, jax.devices()[0].device_kind, chips, batch,
                 rows[:pool], k0[:pool], k1[:pool])


def window(st: State, seconds: float, annotate) -> Window:
    outs = []
    ends = []
    sent = 0
    t0 = t_end = time.perf_counter()
    while (sent + st.batch <= len(st.keys0)
           and time.perf_counter() - t0 < seconds):
        with annotate("bench.eval_tpu"):
            outs.append(st.server.eval(list(st.keys0[sent:sent + st.batch])))
        t_end = time.perf_counter()
        ends.append(t_end - t0)
        sent += st.batch
    if t_end - t0 < seconds:
        print("sharded_closed_loop: key pool of %d ran dry after %.3f s"
              % (len(st.keys0), t_end - t0), file=sys.stderr)
    return Window(end_to_end={"dpfs_per_s": sent / (t_end - t0)},
                  attempted=sent, failed=0, served=np.arange(sent),
                  shares=np.concatenate(outs),
                  record={"answered": sent, "calls": sent // st.batch,
                          "call_end_s": ends, "kernel": st.kernel,
                          "device_kind": st.device_kind,
                          "chips": st.chips, "batch": st.batch,
                          "shard_rows": st.server.shard_rows,
                          "entry_words": st.server.entry_size})


def server1(st: State, idx: np.ndarray) -> np.ndarray:
    """The program's server 1 shares of pool keys ``idx``, in calls of
    the window's own batch (short ones padded with repeats)."""
    out = []
    for lo in range(0, len(idx), st.batch):
        keys = list(st.keys1[idx[lo:lo + st.batch]])
        n = len(keys)
        keys += [keys[-1]] * (st.batch - n)
        out.append(st.server.eval(keys)[:n])
    return np.concatenate(out)


def release(st: State) -> None:
    """Drop the server, and with it its table on every chip."""
    st.server = None
    gc.collect()
