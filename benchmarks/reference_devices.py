"""The plain reference on the cell's devices: ``reference.py``'s blocked
walk and contraction, the same code, run under ``jax.numpy``.

A configuration selects it with ``"reference_on": "devices"`` in its
``check``, for tables too large for the host walk.  The table stays on
the host.  The frontier is walked there; then, round by round, block
``r`` (the rows ``table[r::2^t]`` and each key's frontier node ``r``) is
copied to device ``r mod len(devices)``, and one program over all the
devices (``shard_map``, compiled once for every block of the run)
expands the keys' subtrees and contracts them, each device its own
block.  A PRF's rounds run in a rolled loop (``fori_loop``): on a TPU
v5e that ran 1.8x faster than the unrolled rounds at 2^24 rows and 32
keys and compiled in a fifth of the time, and XLA's CPU compiler does
not finish 12 unrolled ChaCha rounds at all.  A few threads gather and
copy the next rounds while the devices work, and at most ``AHEAD``
rounds are in flight, so host and device memory hold a few blocks
whatever the table's size.  The float32 control runs its matrix
product at float32 (``highest``) precision.

It imports nothing of the program.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks import reference as R

GATHER_THREADS = 8
AHEAD = 8


def _rolled(n: int, body, state):
    return jax.lax.fori_loop(0, n, lambda i, s: body(s), state)


@functools.lru_cache(maxsize=None)
def _program(devices: tuple, levels: int, prf: str, contraction: str):
    """(the jitted round, the blocks' sharding, the replicated one)."""
    mesh = Mesh(np.array(devices), ("d",))

    def block(seeds, cw, rows):         # [1, S, 4], [2, S, 2l, 4], [1, L, E]
        leaves = R.walk(seeds[0][:, None, :], cw, levels, 0, prf, jnp,
                        _rolled)
        return R.contract(leaves[..., 0], rows[0], contraction, jnp)[None]

    run = jax.jit(jax.shard_map(block, mesh=mesh,
                                in_specs=(P("d"), P(), P("d")),
                                out_specs=P("d"), check_vma=False))
    return run, NamedSharding(mesh, P("d")), NamedSharding(mesh, P())


def share(keys, table: np.ndarray, prf: str, contraction: str = "exact",
          devices=None, block_seeds: int = R.BLOCK_SEEDS) -> np.ndarray:
    """[S, E] int32: each key's share of ``table``, as
    ``reference.share`` gives it, with the blocks dealt round-robin over
    ``devices`` (default: all of JAX's; a power of two of them)."""
    if contraction not in ("exact", "float32"):
        raise ValueError("unknown contraction %r" % contraction)
    if not R.runs_under(prf, "jax.numpy"):
        raise ValueError("PRF %r has no jax.numpy reference" % prf)
    devices = tuple(devices or jax.devices())
    nd = len(devices)
    k = R.parse_keys(keys)
    R.check_table(k, table)
    t = max(R.split(k["depth"], len(k["seed"]), block_seeds),
            (nd - 1).bit_length())
    nb = 1 << t
    if nb % nd or t > k["depth"]:
        raise ValueError("%d devices cannot share %d blocks" % (nd, nb))
    levels = k["depth"] - t
    front = R.frontier(k, t, prf)
    run, blocks, replicated = _program(devices, levels, prf, contraction)
    cw = jax.device_put(k["cw"][:, :, :2 * levels], replicated)

    def put(q):
        rs = range(q * nd, (q + 1) * nd)
        return (jax.device_put(front[:, rs.start:rs.stop].swapaxes(0, 1),
                               blocks),
                jax.device_put(np.stack([table[r::nb] for r in rs]), blocks))

    rounds = nb // nd
    parts = []
    with ThreadPoolExecutor(GATHER_THREADS) as ex, \
            jax.default_matmul_precision("highest"):
        puts = [ex.submit(put, q) for q in range(min(AHEAD, rounds))]
        for q in range(rounds):
            seeds, rows = puts[q].result()
            puts[q] = None
            if q + AHEAD < rounds:
                puts.append(ex.submit(put, q + AHEAD))
            if q >= AHEAD:
                parts[q - AHEAD].block_until_ready()
            parts.append(run(seeds, cw, rows))
    return R.finish(np.concatenate(jax.device_get(parts)), contraction)
