"""AES-128 (FIPS-197) as facebookresearch/GPU-DPF's PRF_AES128 uses it.

The seed's 16 little-endian bytes are the key, the position's 16
little-endian bytes the plaintext, and the ciphertext is read back
little-endian.  Plain NumPy only (it indexes tables and writes in
place); it imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

NAMESPACES = ("numpy",)


def _gf_mul(a: int, b: int) -> int:
    p = 0
    for _ in range(8):
        if b & 1:
            p ^= a
        hi = a & 0x80
        a = (a << 1) & 0xFF
        if hi:
            a ^= 0x1B
        b >>= 1
    return p


def _make_sbox() -> np.ndarray:
    """The AES S-box from its definition: inverse in GF(2^8), then the
    affine map."""
    inv = [0] * 256
    for x in range(1, 256):
        for y in range(1, 256):
            if _gf_mul(x, y) == 1:
                inv[x] = y
                break
    box = []
    for x in range(256):
        b = inv[x]
        s = b
        for k in range(1, 5):
            s ^= ((b << k) | (b >> (8 - k))) & 0xFF
        box.append(s ^ 0x63)
    return np.array(box, np.uint8)


SBOX = _make_sbox()
XTIME = np.array([_gf_mul(x, 2) for x in range(256)], np.uint8)
# state byte 4c + r sits in row r, column c; ShiftRows moves row r left by r
SHIFT_ROWS = np.array([(r + 4 * ((c + r) % 4)) for c in range(4)
                       for r in range(4)])
RCON = (0x01, 0x02, 0x04, 0x08, 0x10, 0x20, 0x40, 0x80, 0x1B, 0x36)


def _mix_columns(s: np.ndarray) -> np.ndarray:
    a = s.reshape(-1, 4, 4)
    rot = np.roll(a, -1, axis=2)
    t = a[..., 0] ^ a[..., 1] ^ a[..., 2] ^ a[..., 3]
    return (a ^ t[..., None] ^ XTIME[a ^ rot]).reshape(s.shape)


def encrypt(keys: np.ndarray, plaintexts) -> list:
    """AES-128 of each [M, 16] uint8 plaintext block under the [M, 16]
    uint8 keys (one key schedule for all of them)."""
    rk = keys.copy()
    states = [p ^ rk for p in plaintexts]
    for rnd in range(1, 11):
        t = SBOX[rk[:, [13, 14, 15, 12]]]       # RotWord, SubWord
        t[:, 0] ^= np.uint8(RCON[rnd - 1])
        nk = np.empty_like(rk)
        nk[:, 0:4] = rk[:, 0:4] ^ t
        for w in range(4, 16, 4):
            nk[:, w:w + 4] = rk[:, w:w + 4] ^ nk[:, w - 4:w]
        rk = nk
        for i, st in enumerate(states):
            st = SBOX[st][:, SHIFT_ROWS]
            if rnd < 10:
                st = _mix_columns(st)
            states[i] = st ^ rk
    return states


def pair(seeds: np.ndarray, xp=np, loop=None):
    """AES-128 under each seed of positions 0 and 1: two [M, 4] uint32
    arrays (little-endian limbs).  ``xp`` has to be NumPy."""
    if xp is not np:
        raise ValueError("the AES-128 reference runs under NumPy only")
    m = seeds.shape[0]
    keys = np.ascontiguousarray(seeds).view(np.uint8).reshape(m, 16)
    pts = []
    for pos in (0, 1):
        pt = np.zeros((m, 16), np.uint8)
        pt[:, 0] = pos
        pts.append(pt)
    return tuple(np.ascontiguousarray(ct).view(np.uint32).reshape(m, 4)
                 for ct in encrypt(keys, pts))
