"""ChaCha as facebookresearch/GPU-DPF's PRF_CHACHA20 runs it.

12 rounds (6 double rounds), its own spelling of the constant words,
the seed in state words 4..7 most significant word first, the 64-bit
position in words 12..13 high word first, and state words 4..7 of the
output block (feed-forward added) as the child, most significant word
first.  Written against an array namespace ``xp`` (NumPy, or
``jax.numpy`` on the devices); it imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

# "expand 32-byte k" as GPU-DPF spells it: each word read big-endian,
# not the little-endian words of RFC 8439
_SIGMA = (0x65787061, 0x6E642033, 0x322D6279, 0x7465206B)
ROUNDS = 12
NAMESPACES = ("numpy", "jax.numpy")


def _rotl(x, n: int):
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


# the quarter rounds of one double round: four columns, four diagonals
_QUARTERS = ((0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15),
             (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14))


def _double_round(x):
    x = list(x)
    for a, b, c, d in _QUARTERS:
        x[a] += x[b]
        x[d] = _rotl(x[d] ^ x[a], 16)
        x[c] += x[d]
        x[b] = _rotl(x[b] ^ x[c], 12)
        x[a] += x[b]
        x[d] = _rotl(x[d] ^ x[a], 8)
        x[c] += x[d]
        x[b] = _rotl(x[b] ^ x[c], 7)
    return x


def _repeat(n: int, body, x):
    for _ in range(n):
        x = body(x)
    return x


def block(seeds, pos: int, xp=np, loop=None):
    """[M, 4] uint32 child of each [M, 4] uint32 seed at ``pos``.
    ``loop(n, body, state)`` applies ``body`` n times (default: a Python
    loop; on the devices a rolled one)."""
    m = seeds.shape[0]
    x = [xp.full(m, c, xp.uint32) for c in _SIGMA]
    x += [seeds[:, 3].copy(), seeds[:, 2].copy(), seeds[:, 1].copy(),
          seeds[:, 0].copy()]
    x += [xp.zeros(m, xp.uint32) for _ in range(4)]
    x += [xp.zeros(m, xp.uint32), xp.full(m, pos, xp.uint32),
          xp.zeros(m, xp.uint32), xp.zeros(m, xp.uint32)]
    init = [v.copy() for v in x]
    x = (loop or _repeat)(ROUNDS // 2, _double_round, x)
    out = [x[i] + init[i] for i in (7, 6, 5, 4)]   # back to LE limbs
    return xp.stack(out, axis=1)


def pair(seeds, xp=np, loop=None):
    """The children at positions 0 and 1 of each seed."""
    return block(seeds, 0, xp, loop), block(seeds, 1, xp, loop)
