"""The subtree kernel's share of its HBM roofline in the four-chip mesh
cell, in percent: the bytes the kernel has to move on one chip over the
window, over that chip's kernel time at the device's peak HBM bandwidth
(``peaks.json`` ``hbm_bytes_per_s``).

Bytes (``kernel_bytes``): the kernel runs a grid of key tiles x the
shard's subtrees and reads the shard's whole [4, rows, E] int8 digit
table once per key tile, each subtree's seeds and the tile's codeword
slots, and writes each tile's [TB, E] int32 answer.  Kernel time: the
self time of the ops named ``dpf_subtree_contract`` among the window's
top ops, summed over the chips, over the chips.  ``None`` where no such
op ran, or the device has no peak in ``peaks.json``."""

import json
import os

NAME = "dpf_subtree_contract"
PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def kernel_bytes(calls, batch, shard_rows, entry_words, tile, chunk,
                 levels):
    """Bytes one chip's subtree kernel moves in ``calls`` calls of
    ``batch`` keys over ``shard_rows`` rows in subtrees of ``chunk``
    leaves, ``levels`` tree levels each, with key tile ``tile``."""
    tiles = -(-batch // tile)
    subtrees = shard_rows // chunk
    digits = 4 * shard_rows * entry_words             # int8 planes
    seeds = subtrees * tile * 4 * 4                   # u32 limbs
    codewords = 2 * 4 * tile * 2 * levels * 4         # both arrays
    answer = tile * entry_words * 4
    return calls * tiles * (digits + seeds + codewords + answer)


def read(record):
    tr = record.get("trace")
    chips = record.get("chips")
    if not tr or not chips or not record.get("calls"):
        return None
    kernel_s = sum(s for op, s in tr["top_ops"] if NAME in op) / chips
    with open(PEAKS) as f:
        peak = (json.load(f)["devices"].get(record.get("device_kind"))
                or {}).get("hbm_bytes_per_s")
    if not kernel_s or not peak:
        return None
    try:
        from dpf_tpu.ops.pallas_level import PALLAS_TB, pallas_chunk_leaves
    except ImportError:
        return None
    rows = record["shard_rows"]
    chunk = pallas_chunk_leaves(rows)
    moved = kernel_bytes(record["calls"], record["batch"], rows,
                         record["entry_words"], PALLAS_TB, chunk,
                         chunk.bit_length() - 1)
    return 100.0 * moved / (kernel_s * peak)
