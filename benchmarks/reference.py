"""Plain NumPy evaluation of one server's DPF share: the benchmark's yardstick.

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

Each PRF is a file of its own, ``prfs/<name>.py``, found by the name
the configuration gives (``aes128``: AES-128 of the position under the
seed; ``chacha20``: GPU-DPF's 12-round ChaCha).

``share(..., contraction="float32")`` is the control: the same leaves,
contracted in float32, the precision a matrix unit would tempt one to
use.  It cannot reproduce an exact share.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np

KEY_WORDS = 524


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


def add128(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Limb-wise 128-bit addition mod 2^128 of [..., 4] uint32 arrays."""
    out = np.empty(np.broadcast_shapes(a.shape, b.shape), np.uint32)
    carry = np.zeros(out.shape[:-1], np.uint64)
    for j in range(4):
        s = a[..., j].astype(np.uint64) + b[..., j] + carry
        out[..., j] = s.astype(np.uint32)
        carry = s >> np.uint64(32)
    return out


# ------------------------------------------------------------------ PRFs

PRF_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "prfs")


@functools.lru_cache(maxsize=None)
def prf(name: str):
    """The PRF module ``prfs/<name>.py``: ``pair(seeds)`` gives the
    children at positions 0 and 1 of each [M, 4] uint32 seed."""
    path = os.path.join(PRF_DIR, name + ".py")
    if not os.path.exists(path):
        raise ValueError("unknown PRF %r: no %s" % (name, path))
    spec = importlib.util.spec_from_file_location("bench_prf_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prf_pair(name: str, seeds: np.ndarray):
    return prf(name).pair(seeds)


# ------------------------------------------------------------ evaluation

def leaves_low32(keys, prf: str) -> np.ndarray:
    """[S, N] uint32: the low 32 bits of every leaf, natural order."""
    k = parse_keys(keys)
    s_keys = k["seed"].shape[0]
    seeds = k["seed"][:, None, :]                       # [S, W=1, 4]
    for step in range(k["depth"]):
        i = k["depth"] - 1 - step
        width = seeds.shape[1]
        flat = seeds.reshape(-1, 4)
        odd = (flat[:, 0] & 1).astype(bool).reshape(s_keys, width, 1)
        kids = []
        for b, out in enumerate(prf_pair(prf, flat)):
            cw = np.where(odd, k["cw"][1][:, None, 2 * i + b],
                          k["cw"][0][:, None, 2 * i + b])
            kids.append(add128(out.reshape(s_keys, width, 4), cw))
        seeds = np.concatenate(kids, axis=1)
    return np.ascontiguousarray(seeds[..., 0])


def share(keys, table: np.ndarray, prf: str,
          contraction: str = "exact") -> np.ndarray:
    """[S, E] int32: each key's share of ``table`` ([N, E] int32)."""
    low = leaves_low32(keys, prf)
    if low.shape[1] != table.shape[0]:
        raise ValueError("keys for n=%d, table has %d rows"
                         % (low.shape[1], table.shape[0]))
    if contraction == "exact":
        prod = low @ np.ascontiguousarray(table).view(np.uint32)
        return prod.astype(np.uint32).view(np.int32)
    if contraction == "float32":
        f = low.view(np.int32).astype(np.float32) @ table.astype(np.float32)
        wrapped = np.fmod(f.astype(np.float64), 2.0 ** 32).astype(np.int64)
        return (wrapped & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    raise ValueError("unknown contraction %r" % contraction)
