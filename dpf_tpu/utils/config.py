"""Runtime evaluation config (replaces the reference's compile-time flag
tiers — SURVEY.md §5: ``DPF_STRATEGY``/``PRF_METHOD``/``Z``/``BATCH_SIZE``
``-D`` flags become one dataclass; jit specializes per value).

Fields left at their *auto* state (``None`` or ``"auto"``) are resolved at
dispatch time: explicit values win, then per-shape knobs from the
persistent tuning cache (``tune/cache.py``, populated by
``benchmark.py --autotune``), then the static heuristics
(``expand.choose_chunk`` et al.).  ``is_auto`` defines the auto state;
``api.DPF.resolved_eval_knobs`` implements the precedence.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, replace


def is_auto(value) -> bool:
    """True when a knob is at its auto state (resolve via tuning cache
    then heuristic): ``None`` or the string ``"auto"``."""
    return value is None or value == "auto"


def check_construction(scheme: str, radix: int,
                       schemes=("logn", "sqrtn", "auto")) -> None:
    """The one scheme/radix membership rule for every construction
    surface — the ``DPF`` ctor and the batch-PIR server, client, and
    cost model all validate here.  Pass a narrower ``schemes`` tuple to
    drop "auto" at call sites that need a concrete construction."""
    if scheme not in schemes:
        raise ValueError("scheme must be one of %s (got %r)"
                         % (schemes, scheme))
    if radix not in (2, 4):
        raise ValueError("radix must be 2 or 4")
    if scheme == "sqrtn" and radix == 4:
        raise ValueError("scheme='sqrtn' has no radix; use radix=2")


@dataclass(frozen=True)
class EvalConfig:
    """Everything that selects a compiled evaluation program."""
    prf_method: int = 3  # PRF_AES128; 0..3 = reference ids, 4/5 =
    #                 SALSA20_BLK/CHACHA20_BLK block-PRG variants (one
    #                 512-bit core block feeds four GGM children —
    #                 core/prf_ref.py::prf_salsa20_12_blk)
    batch_size: int = 512          # device dispatch cap (reference parity)
    chunk_leaves: int | None = None  # None = auto (tuned, else choose_chunk)
    dot_impl: str | None = "i32"   # "i32" | "mxu" (ops/matmul128) |
    #                 None/"auto" (tuned, else module default)
    round_unroll: bool | None = None  # None = auto (rolled rounds)
    aes_impl: str = "auto"  # "auto"|"gather"|"bitsliced"[":bp"|":tower"]
    kernel_impl: str | None = "xla"  # "xla" | "pallas" (ChaCha/Salsa subtree
    #                 kernel) | "dispatch" (per-level programs; fast compile)
    #                 | None/"auto" (tuned, else "pallas" for binary
    #                 Salsa/ChaCha GGM on a TPU, "xla" elsewhere)
    dispatch_group: int | None = None  # dispatch mode: frontier subtrees
    #                 expanded per pass (None = auto; larger = fewer host
    #                 round-trips, more live leaf memory per pass)
    radix: int = 2  # 2 = reference-wire-compatible binary GGM;
    #                 4 = TPU-native radix-4 (core/radix4.py): 2/3 the PRF
    #                 children, half the levels, 2x AES schedule amortization
    scheme: str = "logn"  # "logn" (GGM tree, O(log N) keys) | "sqrtn"
    #                 (core/sqrtn.py: O(sqrt N) keys, flat single-level PRF
    #                 grid — the latency play for mid-sized tables)
    row_chunk: int | None = None  # sqrtn: grid rows PRF-expanded per scan
    #                 step (None = auto: tuned, else sqrtn.choose_row_chunk
    #                 bounding the live [B, rc, K, 4] slab at the 64 MiB
    #                 CHUNK_SEED_BYTES_BOUND); multiple of 4, divides R

    def with_(self, **kw) -> "EvalConfig":
        return replace(self, **kw)

    def apply_globals(self):
        """Push the process-wide knobs (round_unroll, aes/dot defaults).

        Fields at their auto state RESET their global to its auto
        default (``ROUND_UNROLL=None``, ``AES_PAIR_IMPL="auto"``, dot
        ``"i32"``) — sweep scripts apply configs in sequence and must
        not leak one config's knobs into the next measurement.  Prefer
        the scoped ``applied()`` in any code that measures candidates."""
        from ..core import prf
        from ..ops import matmul128
        prf.ROUND_UNROLL = self.round_unroll
        prf.AES_PAIR_IMPL = (self.aes_impl
                             if not is_auto(self.aes_impl) else "auto")
        matmul128.set_dot_impl(self.dot_impl
                               if not is_auto(self.dot_impl) else "i32")
        return self

    @contextlib.contextmanager
    def applied(self):
        """Scoped ``apply_globals``: snapshot the process-wide knobs,
        push this config's values, and restore the snapshot on exit —
        exception or not.  The tuner wraps every candidate measurement
        in this so a crashed search can't leave the process mis-knobbed.
        """
        from ..core import prf
        from ..ops import matmul128
        snap = (prf.ROUND_UNROLL, prf.AES_PAIR_IMPL,
                matmul128.default_impl())
        try:
            yield self.apply_globals()
        finally:
            prf.ROUND_UNROLL, prf.AES_PAIR_IMPL = snap[0], snap[1]
            matmul128.set_dot_impl(snap[2])
