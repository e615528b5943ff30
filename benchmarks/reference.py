"""Plain evaluation of one server's DPF share: the benchmark's yardstick.

It imports nothing of the system under test.  From the wire key alone
(524 int32 words = 131 little-endian 128-bit slots: ``[0]`` depth,
``[1..64]`` first correction words, ``[65..128]`` second correction
words, ``[129]`` the server's start seed, ``[130]`` the table size) it
walks the binary GGM tree of facebookresearch/GPU-DPF:

    child(seed, b) = PRF(seed, b) + cw[lsb(seed)][2 i + b]   (mod 2^128)

for the flat level ``i`` from ``depth - 1`` down to 0, where ``cw[0]``
is the first correction-word array and ``cw[1]`` the second.  Step ``l``
consumes bit ``l`` of the leaf index, so appending the ``b = 1``
children after the ``b = 0`` ones keeps the leaves in natural order.
The share is the table contracted with the low 32 bits of every leaf,
mod 2^32.

Blocks.  The subtree under the node that the first ``t`` steps reach by
the bits of ``r`` holds exactly the rows ``r, r + 2^t, r + 2 * 2^t,
...``, that is ``table[r::2^t]``.  So ``share`` walks the first ``t``
steps for all keys (the frontier, ``2^t`` nodes a key), then for each
frontier node expands the remaining ``depth - t`` steps and contracts
them against its rows, and sums the blocks mod 2^32.  ``t`` is the
least that keeps a block within ``block_seeds`` leaf seeds over all
keys, so memory does not grow with the table.

The walk is written against an array namespace ``xp``: NumPy here, or
``jax.numpy`` on the cell's devices (``reference_devices.py``), the
same code in both places.

Each PRF is a file of its own, ``prfs/<name>.py``, found by the name
the configuration gives (``aes128``: AES-128 of the position under the
seed; ``chacha20``: GPU-DPF's 12-round ChaCha).  Its ``NAMESPACES``
names the array namespaces its ``pair`` runs under.

``share(..., contraction="float32")`` is the control: the same leaves,
contracted in float32 block by block, the precision a matrix unit would
tempt one to use.  It cannot reproduce an exact share.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np

KEY_WORDS = 524
BLOCK_SEEDS = 1 << 22


# ------------------------------------------------------------------ keys

def parse_keys(keys) -> dict:
    """[S, 524] int32 wire keys -> limb arrays (uint32, little endian)."""
    w = np.ascontiguousarray(np.asarray(keys, dtype=np.int32))
    if w.ndim != 2 or w.shape[1] != KEY_WORDS:
        raise ValueError("keys must be [S, %d] int32, got %s"
                         % (KEY_WORDS, w.shape))
    slots = w.view(np.uint32).reshape(-1, 131, 4)
    depth = slots[:, 0, 0].astype(np.int64)
    n = slots[:, 130, 0].astype(np.int64) | (
        slots[:, 130, 1].astype(np.int64) << 32)
    if (depth != depth[0]).any() or (n != n[0]).any():
        raise ValueError("keys for different table sizes")
    if n[0] != 1 << int(depth[0]):
        raise ValueError("key depth %d does not match n %d"
                         % (depth[0], n[0]))
    return {"depth": int(depth[0]), "n": int(n[0]),
            "cw": np.stack([slots[:, 1:65], slots[:, 65:129]]),  # [2,S,64,4]
            "seed": slots[:, 129].copy()}


def add128(a, b, xp=np):
    """Limb-wise 128-bit addition mod 2^128 of [..., 4] uint32 arrays.
    Carries come from 32-bit compares: JAX has no uint64 by default."""
    limbs = []
    carry = None
    for j in range(4):
        s = a[..., j] + b[..., j]
        c = s < a[..., j]
        if carry is not None:
            t = s + carry.astype(xp.uint32)
            c = c | (t < s)
            s = t
        limbs.append(s)
        carry = c
    return xp.stack(limbs, axis=-1)


# ------------------------------------------------------------------ PRFs

PRF_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "prfs")


@functools.lru_cache(maxsize=None)
def prf_module(name: str):
    """The PRF module ``prfs/<name>.py``: ``pair(seeds, xp, loop)`` gives
    the children at positions 0 and 1 of each [M, 4] uint32 seed."""
    path = os.path.join(PRF_DIR, name + ".py")
    if not os.path.exists(path):
        raise ValueError("unknown PRF %r: no %s" % (name, path))
    spec = importlib.util.spec_from_file_location("bench_prf_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prf_pair(name: str, seeds: np.ndarray):
    return prf_module(name).pair(seeds)


def runs_under(name: str, namespace: str) -> bool:
    """Whether PRF ``name`` runs under the array namespace ``namespace``
    (``"numpy"``, ``"jax.numpy"``)."""
    return namespace in getattr(prf_module(name), "NAMESPACES", ("numpy",))


# ------------------------------------------------------------ evaluation

def walk(seeds, cw, top: int, bottom: int, prf: str, xp=np, loop=None):
    """The steps of levels ``top - 1`` down to ``bottom`` from [S, W, 4]
    uint32 seeds; returns [S, W * 2^(top - bottom), 4], natural order
    within each of the W subtrees.  ``cw`` is [2, S, >= 2 top, 4];
    ``loop`` goes to the PRF's rounds."""
    pair = prf_module(prf).pair
    s_keys = seeds.shape[0]
    for i in range(top - 1, bottom - 1, -1):
        width = seeds.shape[1]
        flat = seeds.reshape(-1, 4)
        odd = (flat[:, 0] & 1).astype(bool).reshape(s_keys, width, 1)
        kids = []
        for b, out in enumerate(pair(flat, xp, loop)):
            c = xp.where(odd, cw[1][:, None, 2 * i + b],
                         cw[0][:, None, 2 * i + b])
            kids.append(add128(out.reshape(s_keys, width, 4), c, xp))
        seeds = xp.concatenate(kids, axis=1)
    return seeds


def leaves_low32(keys, prf: str) -> np.ndarray:
    """[S, N] uint32: the low 32 bits of every leaf, natural order."""
    k = parse_keys(keys)
    return walk(k["seed"][:, None, :], k["cw"], k["depth"], 0,
                prf)[..., 0].copy()


def split(depth: int, s_keys: int, block_seeds: int = BLOCK_SEEDS) -> int:
    """``t``: the frontier's depth, the least that keeps a block's
    ``s_keys * 2^(depth - t)`` leaf seeds within ``block_seeds``."""
    per_key = max(1, block_seeds // max(1, s_keys))
    return max(0, depth - (per_key.bit_length() - 1))


def frontier(k: dict, t: int, prf: str) -> np.ndarray:
    """[S, 2^t, 4] uint32: every key's nodes after its first ``t``
    steps; node ``r`` roots the subtree of rows ``r::2^t``."""
    return walk(k["seed"][:, None, :], k["cw"], k["depth"],
                k["depth"] - t, prf)


def contract(low, rows, contraction: str, xp=np):
    """One block's contraction of [S, L] uint32 leaves with [L, E] int32
    rows: uint32 sums mod 2^32 (``exact``, elementwise, no float), or
    the float32 product (``float32``, wrapped by ``finish``)."""
    if contraction == "exact":
        return (low[:, :, None] * rows.view(xp.uint32)[None]).sum(
            axis=1, dtype=xp.uint32)
    if contraction == "float32":
        return low.view(xp.int32).astype(xp.float32) @ rows.astype(
            xp.float32)
    raise ValueError("unknown contraction %r" % contraction)


def finish(parts: np.ndarray, contraction: str) -> np.ndarray:
    """[B, S, E] per-block ``contract`` results -> [S, E] int32 shares:
    float32 blocks wrapped mod 2^32 each, then all summed mod 2^32."""
    parts = np.asarray(parts)
    if contraction == "float32":
        wrapped = np.fmod(parts.astype(np.float64), 2.0 ** 32)
        parts = (wrapped.astype(np.int64) & 0xFFFFFFFF).astype(np.uint32)
    return parts.sum(axis=0, dtype=np.uint32).view(np.int32)


def check_table(k: dict, table) -> None:
    if k["n"] != table.shape[0]:
        raise ValueError("keys for n=%d, table has %d rows"
                         % (k["n"], table.shape[0]))


def share(keys, table: np.ndarray, prf: str, contraction: str = "exact",
          block_seeds: int = BLOCK_SEEDS) -> np.ndarray:
    """[S, E] int32: each key's share of ``table`` ([N, E] int32),
    block by block on the host."""
    if contraction not in ("exact", "float32"):
        raise ValueError("unknown contraction %r" % contraction)
    k = parse_keys(keys)
    check_table(k, table)
    t = split(k["depth"], len(k["seed"]), block_seeds)
    front = frontier(k, t, prf)
    nb = 1 << t
    parts = [contract(walk(front[:, r:r + 1], k["cw"], k["depth"] - t, 0,
                           prf)[..., 0], table[r::nb], contraction)
             for r in range(nb)]
    return finish(np.stack(parts), contraction)
