"""ChaCha as facebookresearch/GPU-DPF's PRF_CHACHA20 runs it.

12 rounds (6 double rounds), its own spelling of the constant words,
the seed in state words 4..7 most significant word first, the 64-bit
position in words 12..13 high word first, and state words 4..7 of the
output block (feed-forward added) as the child, most significant word
first.  Plain NumPy; it imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

# "expand 32-byte k" as GPU-DPF spells it: each word read big-endian,
# not the little-endian words of RFC 8439
_SIGMA = (0x65787061, 0x6E642033, 0x322D6279, 0x7465206B)
ROUNDS = 12


def _rotl(x: np.ndarray, n: int) -> np.ndarray:
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


def block(seeds: np.ndarray, pos: int) -> np.ndarray:
    """[M, 4] uint32 child of each [M, 4] uint32 seed at ``pos``."""
    m = seeds.shape[0]
    x = [np.full(m, c, np.uint32) for c in _SIGMA]
    x += [seeds[:, 3].copy(), seeds[:, 2].copy(), seeds[:, 1].copy(),
          seeds[:, 0].copy()]
    x += [np.zeros(m, np.uint32) for _ in range(4)]
    x += [np.zeros(m, np.uint32), np.full(m, pos, np.uint32),
          np.zeros(m, np.uint32), np.zeros(m, np.uint32)]
    init = [v.copy() for v in x]

    def qr(a, b, c, d):
        x[a] += x[b]
        x[d] = _rotl(x[d] ^ x[a], 16)
        x[c] += x[d]
        x[b] = _rotl(x[b] ^ x[c], 12)
        x[a] += x[b]
        x[d] = _rotl(x[d] ^ x[a], 8)
        x[c] += x[d]
        x[b] = _rotl(x[b] ^ x[c], 7)

    for _ in range(ROUNDS // 2):
        qr(0, 4, 8, 12)
        qr(1, 5, 9, 13)
        qr(2, 6, 10, 14)
        qr(3, 7, 11, 15)
        qr(0, 5, 10, 15)
        qr(1, 6, 11, 12)
        qr(2, 7, 8, 13)
        qr(3, 4, 9, 14)
    out = [x[i] + init[i] for i in (7, 6, 5, 4)]   # back to LE limbs
    return np.stack(out, axis=1)


def pair(seeds: np.ndarray):
    """The children at positions 0 and 1 of each seed."""
    return block(seeds, 0), block(seeds, 1)
