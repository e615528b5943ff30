"""Generative kernel-variant search over the DPF kernel spaces.

``tune/search.py`` does staged coordinate descent over a hand-enumerated
scalar knob grid.  This module searches the KERNEL space itself; each
point is a serializable :class:`KernelVariant` and the search is seeded
mutate/tournament (the AlphaEvolve-for-FHE generate-then-verify move,
PAPERS.md arXiv:2605.14718, and the NTT codegen loop arXiv:2502.11110)
over a population that always contains the staged-descent winner and
the static heuristics, so it can never regress either.

Three variant FAMILIES share the machinery (``KernelVariant.family``):

- ``"xla"`` / ``"pallas"`` — the sqrt-N PRF->contract space PR 15
  introduced: the Pallas grid kernel's tile shape / VMEM cell budget /
  grid iteration order / dimension semantics / limb emission /
  codeword-select structure, and the XLA scan's (row_chunk, dot_impl)
  pairing (:func:`kernel_search`);
- ``"ggm"`` — the log-N/GGM expansion space (:func:`kernel_search_ggm`):
  ``chunk_leaves`` x the ``f_levels`` level-fusion frontier (the
  phase-1/phase-2 split ``expand.expand_and_contract`` hard-coded
  pre-search) x per-level-vs-fused dispatch (``engine`` =
  "fused"/"dispatch"/"pallas", with the dispatch engine's group knob)
  x contraction ``dot_impl``, plus the subtree kernel's key tile;
- ``"keygen"`` — the batched-keygen space (:func:`keygen_search`):
  SHAKE squeeze batching (``squeeze_draws``) x vectorized ``prf_v``
  limb-call grouping (``prf_group``) x target-path seed reuse
  (``path_reuse``), per construction (``gen_batched`` /
  ``gen_batched_r4`` / ``gen_sqrt_batched``); fitness is keys/s and the
  key bytes are invariant by construction.

**Trust model** — zero new correctness machinery:

- every TIMED eval candidate first passes the scalar-oracle equality
  gate (full [B, E] shares bit-identical to ``DPF.eval_cpu``), exactly
  the ``tune_eval`` contract; every TIMED keygen candidate is
  bit-identical per key to the scalar generator oracle (every wire
  byte, both servers); a mutation that produces an invalid variant is
  rejected by :func:`variant_invalid` BEFORE it is ever built, so a
  clean search reports ``rejected == 0`` and ``gate_escapes == 0``;
- every PALLAS variant (sqrt-N grid and GGM subtree alike)
  additionally proves interpret-mode parity against its scan oracle on
  a small grid (eager, CPU-safe), which is what makes the search
  meaningful off-TPU: the XLA families race on wall-clock, the Pallas
  families are parity-gated and PINNED in the record for a TPU
  run to race natively.

Winners persist in the tuning cache as ``kvariant|...`` entries
(fingerprint x shape keyed, the key carrying (scheme, radix) so the
families never answer each other's lookups; keygen entries use the
``entry_size=0`` sentinel — keygen cost is table-width independent),
consumed by ``api.resolved_eval_knobs`` (provenance
``kernel_resolved_from="searched"``) and ``DPF.gen_batch``
(``DPF._resolved_keygen_knobs``).  ``benchmark.py --autotune-kernel
--family=sqrtn|logn|keygen|all`` drives :func:`kernel_search_sweep`;
the multi-family record is committed as ``BENCH_KSEARCH2_r18.json``
(the sqrt-N-only PR-15 record stays as ``BENCH_KSEARCH_r15.json``).
"""

from __future__ import annotations

import dataclasses
import json
import random
import time

import numpy as np

from ..core import expand
from ..core.prf_ref import PRF_CHACHA20, PRF_NAMES
from ..ops import matmul128
from ..utils.config import EvalConfig
from ..utils.profiling import CACHE_COUNTERS
from . import compcache
from .cache import TuningCache, default_cache
from .fingerprint import cache_key, device_fingerprint
from .search import _workload, heuristic_knobs, tune_eval

#: tuning-cache entry kind for searched kernel variants
VARIANT_KIND = "kvariant"

#: sampled Pallas tile heights (multiples of 8 — the f32/i32 sublane)
#: and VMEM cell budgets around the PR-10 hand-tuned 2048.  Larger
#: tiles (64) or budgets (8192) overflow v5e's 16 MiB scoped VMEM at
#: N = 2^20 (described-chip compile, PR 21), so they are invalid.
_TB_CHOICES = (8, 16, 32)
_MAX_CELLS_CHOICES = (512, 1024, 2048, 4096)
#: sampled DRBG squeeze-chunk widths (None = one squeeze for all draws,
#: the PR-4 baseline; byte-identical stream either way)
_SQUEEZE_CHOICES = (None, 1, 2, 4, 8, 16)

#: GGM engine -> the ``kernel_impl`` the resolver runs it as
_GGM_ENGINE_IMPL = {"fused": "xla", "dispatch": "dispatch",
                    "pallas": "pallas"}
_IMPL_GGM_ENGINE = {v: k for k, v in _GGM_ENGINE_IMPL.items()}


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One point in the kernel space, serializable into the tuning
    cache.  ``family`` picks the program: ``"xla"`` (the sqrt-N chunked
    scan — searched fields ``row_chunk``/``dot_impl``), ``"pallas"``
    (the fused sqrt-N grid kernel — searched fields ``tb``/
    ``max_cells``/``grid_order``/``dim_semantics``/``limbs``/
    ``cw_add``, the ``ops.pallas_sqrt`` launcher keywords), ``"ggm"``
    (the log-N expansion: ``engine`` picks the driver — "fused" scan
    with ``chunk_leaves``/``f_levels``/``dot_impl``, "dispatch"
    per-level programs with ``chunk_leaves``/``dispatch_group``/
    ``dot_impl``, or "pallas" subtree kernel with ``f_levels``/``tb``
    where C = N >> f_levels), or ``"keygen"`` (the batched generators:
    ``prf_group``/``path_reuse``/``squeeze_draws``).  ``None`` fields
    mean "the launcher's default"; every variant is bit-identical to
    its scalar oracle by construction, so a variant only ever changes
    the schedule, never the answer (nor, for keygen, a single wire
    byte)."""
    family: str = "xla"
    row_chunk: int | None = None
    dot_impl: str | None = None
    tb: int | None = None
    max_cells: int | None = None
    grid_order: str | None = None
    dim_semantics: str | None = None
    limbs: str | None = None
    cw_add: str | None = None
    # --- ggm family (log-N expansion) ---
    engine: str | None = None
    chunk_leaves: int | None = None
    f_levels: int | None = None
    dispatch_group: int | None = None
    # --- keygen family (batched generators) ---
    prf_group: str | None = None
    path_reuse: str | None = None
    squeeze_draws: int | None = None

    def to_dict(self) -> dict:
        return {k: v for k, v in dataclasses.asdict(self).items()
                if v is not None}

    @classmethod
    def from_dict(cls, d: dict) -> "KernelVariant":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in dict(d).items() if k in known})

    def launcher_kwargs(self) -> dict:
        """The ``sqrt_grid_contract_pallas`` structural keywords."""
        from ..ops.pallas_sqrt import _VARIANT_FIELDS
        return {k: v for k, v in self.to_dict().items()
                if k in _VARIANT_FIELDS}

    def eval_knobs(self) -> dict:
        """This variant as a resolved knob dict (what the ``_searched``
        slot of the tuned-cache memo carries into
        ``api.resolved_eval_knobs``).  The ``kernel_variant`` payload
        carries the family, which is how the resolver's riding rules
        keep a sqrt-N variant off a logn dispatch and vice versa."""
        if self.family == "ggm":
            return {
                "kernel_impl": _GGM_ENGINE_IMPL[self.engine or "fused"],
                "chunk_leaves": self.chunk_leaves,
                "dot_impl": self.dot_impl,
                "dispatch_group": self.dispatch_group,
                "f_levels": self.f_levels,
                "kernel_variant": self.to_dict(),
            }
        if self.family == "keygen":
            raise ValueError(
                "keygen variants carry no eval knobs — use keygen_knobs()")
        return {
            "kernel_impl": "pallas" if self.family == "pallas" else "xla",
            "row_chunk": self.row_chunk,
            "dot_impl": self.dot_impl,
            "kernel_variant": self.to_dict(),
        }

    def keygen_knobs(self) -> dict:
        """This variant as the ``knobs=`` dict the batched generators
        accept (``keygen.gen_batched`` / ``radix4.gen_batched_r4`` /
        ``sqrtn.gen_sqrt_batched``); {} is the PR-4 baseline."""
        if self.family != "keygen":
            raise ValueError("not a keygen variant: %s" % self.tag())
        return {k: getattr(self, k) for k in _KEYGEN_FIELDS
                if getattr(self, k) is not None}

    def tag(self) -> str:
        if self.family == "ggm":
            eng = self.engine or "fused"
            if eng == "pallas":
                return "g.p.fl%s.tb%s" % (self.f_levels, self.tb)
            if eng == "dispatch":
                return "g.d.c%s.g%s.%s" % (self.chunk_leaves,
                                           self.dispatch_group,
                                           self.dot_impl)
            return "g.f.c%s.fl%s.%s" % (self.chunk_leaves,
                                        self.f_levels, self.dot_impl)
        if self.family == "keygen":
            return "k.%s.%s.sq%s" % (self.prf_group or "pair",
                                     self.path_reuse or "walk",
                                     self.squeeze_draws or "all")
        if self.family == "pallas":
            return "p.tb%s.mc%s.%s.%s.%s.%s" % (
                self.tb, self.max_cells, self.grid_order or "bk",
                (self.dim_semantics or "parallel")[:3],
                self.limbs or "low", self.cw_add or "fused")
        return "x.rc%s.%s" % (self.row_chunk, self.dot_impl)


#: the PR-10 hand-tuned Pallas structure — the seed of the Pallas
#: family (and the baseline any searched winner must not regress)
def pr10_default_variant() -> KernelVariant:
    from ..ops import pallas_sqrt
    return KernelVariant(
        family="pallas", tb=pallas_sqrt.PALLAS_SQRT_TB,
        max_cells=pallas_sqrt.PALLAS_SQRT_MAX_CELLS, grid_order="bk",
        dim_semantics="parallel", limbs="low", cw_add="fused")


def variant_invalid(v: KernelVariant, *, n: int, batch: int,
                    prf_method: int) -> str | None:
    """Why this variant may not even be BUILT for this shape (None =
    valid).  Mutation consults this before proposing, so an invalid
    variant never reaches the gate and a clean search rejects nothing."""
    from ..core import sqrtn
    k, r = sqrtn.default_split(n)
    if v.family == "xla":
        if v.row_chunk is not None:
            rc = v.row_chunk
            if rc <= 0 or r % rc or (rc != r and rc % 4):
                return "row_chunk %r invalid for R=%d" % (rc, r)
        if v.dot_impl is not None and \
                v.dot_impl not in matmul128.available_impls():
            return "dot_impl %r unavailable" % (v.dot_impl,)
        return None
    if v.family == "ggm":
        return _ggm_variant_invalid(v, n=n, batch=batch,
                                    prf_method=prf_method)
    if v.family == "keygen":
        if v.prf_group not in (None, "stacked"):
            return "prf_group %r" % (v.prf_group,)
        if v.path_reuse not in (None, "reuse"):
            return "path_reuse %r" % (v.path_reuse,)
        if v.squeeze_draws is not None and (
                not isinstance(v.squeeze_draws, int)
                or isinstance(v.squeeze_draws, bool)
                or v.squeeze_draws < 1):
            return "squeeze_draws %r" % (v.squeeze_draws,)
        return None
    if v.family != "pallas":
        return "unknown family %r" % (v.family,)
    from ..ops.pallas_sqrt import pallas_sqrt_unsupported
    reason = pallas_sqrt_unsupported(prf_method, r)
    if reason:
        return reason
    if v.tb is not None and (v.tb < 8 or v.tb % 8):
        return "tb %r not a multiple of 8" % (v.tb,)
    if v.tb is not None and v.tb > _TB_CHOICES[-1]:
        return "tb %r over v5e's scoped VMEM" % (v.tb,)
    if v.max_cells is not None and v.max_cells > _MAX_CELLS_CHOICES[-1]:
        return "max_cells %r over v5e's scoped VMEM" % (v.max_cells,)
    if v.max_cells is not None and v.max_cells < 4 * k:
        return "max_cells %r below one 4-row interleave (4*K=%d)" \
            % (v.max_cells, 4 * k)
    if v.grid_order not in (None, "bk", "kb"):
        return "grid_order %r" % (v.grid_order,)
    if v.grid_order == "kb":
        from ..ops.pallas_sqrt import PALLAS_SQRT_TB
        tb = v.tb or min(PALLAS_SQRT_TB, max(8, batch))
        if batch + (-batch) % tb > tb:
            return ("grid_order='kb' needs one key tile "
                    "(batch %d > tb %d)" % (batch, tb))
    if v.dim_semantics not in (None, "parallel", "arbitrary"):
        return "dim_semantics %r" % (v.dim_semantics,)
    if v.limbs not in (None, "low", "multi"):
        return "limbs %r" % (v.limbs,)
    if v.cw_add not in (None, "fused", "staged"):
        return "cw_add %r" % (v.cw_add,)
    return None


def _ggm_variant_invalid(v: KernelVariant, *, n: int, batch: int,
                         prf_method: int) -> str | None:
    """Validity of one GGM (log-N, radix-2) variant.  A fused/dispatch
    chunk must survive ``expand.clamp_chunk`` UNCHANGED (a clamped
    request would time a different program than the variant claims —
    the resolver surfaces that case via ``chunk_leaves_effective``, the
    search simply never proposes it); a fused ``f_levels`` must be a
    member of ``expand.f_level_candidates`` for its chunk; a subtree
    ``f_levels`` bounds both the kernel's C = N >> f_levels and the
    phase-1 frontier's live-seed bytes."""
    depth = n.bit_length() - 1
    eng = v.engine or "fused"
    if eng not in _GGM_ENGINE_IMPL:
        return "unknown ggm engine %r" % (eng,)
    if v.dot_impl is not None and \
            v.dot_impl not in matmul128.available_impls():
        return "dot_impl %r unavailable" % (v.dot_impl,)
    if eng == "pallas":
        from ..ops.pallas_level import PALLAS_MAX_C, has_subtree_core
        if not has_subtree_core(prf_method):
            return "prf %d has no Pallas plane core" % (prf_method,)
        if v.f_levels is not None:
            fl = int(v.f_levels)
            if not 1 <= fl <= depth - 3:
                return "f_levels %r outside the subtree range" % (fl,)
            if (n >> fl) > PALLAS_MAX_C:
                return ("f_levels %d leaves C=%d over the VMEM cap %d"
                        % (fl, n >> fl, PALLAS_MAX_C))
            if (1 << fl) * 16 * max(1, batch) > \
                    expand.CHUNK_SEED_BYTES_BOUND:
                return ("f_levels %d frontier over the live-seed "
                        "budget at batch %d" % (fl, batch))
        if v.tb is not None and (v.tb < 8 or v.tb % 8):
            return "tb %r not a multiple of 8" % (v.tb,)
        if v.tb is not None and v.tb > _TB_CHOICES[-1]:
            return "tb %r over v5e's scoped VMEM" % (v.tb,)
        return None
    if v.tb is not None:
        return "tb is a Pallas-engine axis"
    if v.chunk_leaves is not None:
        c = int(v.chunk_leaves)
        if c <= 0 or c & (c - 1) or c > n:
            return "chunk_leaves %r invalid for N=%d" % (c, n)
        if expand.clamp_chunk(c, n, batch) != c:
            return ("chunk_leaves %d over the live-seed budget at "
                    "batch %d" % (c, batch))
    if eng == "dispatch":
        if v.f_levels is not None:
            return ("f_levels is a fused-scan axis (the dispatch "
                    "engine groups phase 2 instead)")
        if v.dispatch_group is not None:
            g = int(v.dispatch_group)
            f = n // (v.chunk_leaves
                      or expand.choose_chunk(n, batch))
            if g < 1 or f % g:
                return ("dispatch_group %r does not divide F=%d"
                        % (g, f))
        return None
    if v.dispatch_group is not None:
        return "dispatch_group is a dispatch-engine axis"
    if v.f_levels is not None:
        c = v.chunk_leaves or expand.clamp_chunk(None, n, batch)
        if int(v.f_levels) not in expand.f_level_candidates(n, c, batch):
            return ("f_levels %r illegal for chunk %d at batch %d"
                    % (v.f_levels, c, batch))
    return None


def _field_choices(v: KernelVariant, field: str, *, n: int,
                   batch: int) -> list:
    """Legal values for one variant field at this shape (mutation and
    sampling draw from these; :func:`variant_invalid` is still the
    final word on the combination)."""
    if v.family == "ggm":
        eng = v.engine or "fused"
        if field == "chunk_leaves":
            return expand.chunk_candidates(n, batch)
        if field == "dot_impl":
            return list(matmul128.available_impls())
        if field == "dispatch_group":
            f = n // (v.chunk_leaves or expand.choose_chunk(n, batch))
            return [None] + [g for g in (1, 2, 4, 8)
                             if g <= f and f % g == 0]
        if field == "tb":
            return list(_TB_CHOICES)
        # f_levels — the level-fusion frontier axis
        depth = n.bit_length() - 1
        if eng == "pallas":
            from ..ops.pallas_level import PALLAS_MAX_C
            lo = max(1, depth - int(PALLAS_MAX_C).bit_length() + 1)
            out = [fl for fl in range(lo, max(lo, depth - 3) + 1)
                   if (1 << fl) * 16 * max(1, batch)
                   <= expand.CHUNK_SEED_BYTES_BOUND]
            return out[:4] or [None]
        c = v.chunk_leaves or expand.clamp_chunk(None, n, batch)
        return [None] + expand.f_level_candidates(n, c, batch)
    if v.family == "keygen":
        return {
            "prf_group": [None, "stacked"],
            "path_reuse": [None, "reuse"],
            "squeeze_draws": list(_SQUEEZE_CHOICES),
        }[field]
    from ..core import sqrtn
    k, r = sqrtn.default_split(n)
    if v.family == "xla":
        return {
            "row_chunk": sqrtn.sqrt_chunk_candidates(r, k, batch),
            "dot_impl": list(matmul128.available_impls()),
        }[field]
    return {
        "tb": list(_TB_CHOICES),
        "max_cells": [c for c in _MAX_CELLS_CHOICES if c >= 4 * k],
        "grid_order": ["bk", "kb"],
        "dim_semantics": ["parallel", "arbitrary"],
        "limbs": ["low", "multi"],
        "cw_add": ["fused", "staged"],
    }[field]


_XLA_FIELDS = ("row_chunk", "dot_impl")
_PALLAS_FIELDS = ("tb", "max_cells", "grid_order", "dim_semantics",
                  "limbs", "cw_add")
#: per-engine searched fields of the GGM family (engine itself is fixed
#: at sampling — a cross-engine hop is a different program family, not
#: a single-field mutation)
_GGM_FIELDS = {
    "fused": ("chunk_leaves", "f_levels", "dot_impl"),
    "dispatch": ("chunk_leaves", "dispatch_group", "dot_impl"),
    "pallas": ("f_levels", "tb"),
}
_KEYGEN_FIELDS = ("prf_group", "path_reuse", "squeeze_draws")


def _mutable_fields(v: KernelVariant) -> tuple:
    if v.family == "xla":
        return _XLA_FIELDS
    if v.family == "pallas":
        return _PALLAS_FIELDS
    if v.family == "ggm":
        return _GGM_FIELDS[v.engine or "fused"]
    return _KEYGEN_FIELDS


def mutate_variant(rng: random.Random, v: KernelVariant, *, n: int,
                   batch: int, prf_method: int,
                   tries: int = 16) -> KernelVariant | None:
    """One structural mutation: re-draw a single field from its legal
    choices, keeping the combination valid.  Deterministic under the
    caller's seeded ``rng``; None when no valid novel mutation was
    found in ``tries`` draws (a saturated neighbourhood, not an error)."""
    fields = _mutable_fields(v)
    for _ in range(tries):
        field = rng.choice(fields)
        choices = _field_choices(v, field, n=n, batch=batch)
        choices = [c for c in choices if c != getattr(v, field)]
        if not choices:
            continue
        cand = dataclasses.replace(v, **{field: rng.choice(choices)})
        if variant_invalid(cand, n=n, batch=batch,
                           prf_method=prf_method) is None:
            return cand
    return None


def sample_variant(rng: random.Random, family: str, *, n: int,
                   batch: int, prf_method: int, tries: int = 32,
                   engine: str | None = None) -> KernelVariant | None:
    """One random valid variant of ``family`` (rejection sampling over
    the per-field choices, drawn SEQUENTIALLY so dependent axes —
    ``f_levels`` after ``chunk_leaves`` — see the values already drawn).
    ``engine`` pins the GGM driver; None draws one uniformly."""
    for _ in range(tries):
        eng = engine
        if family == "ggm" and eng is None:
            eng = rng.choice(tuple(_GGM_FIELDS))
        probe = KernelVariant(family=family,
                              engine=eng if family == "ggm" else None)
        for f in _mutable_fields(probe):
            choices = _field_choices(probe, f, n=n, batch=batch)
            if choices:
                probe = dataclasses.replace(
                    probe, **{f: rng.choice(choices)})
        if variant_invalid(probe, n=n, batch=batch,
                           prf_method=prf_method) is None:
            return probe
    return None


# ----------------------------------------------------- gates & fitness


def pallas_parity_ok(v: KernelVariant, *, prf_method: int,
                     gate_n: int = 64, n_keys: int = 3,
                     entry_size: int = 5) -> bool:
    """Interpret-mode parity gate for one Pallas variant: the fused
    grid kernel under this variant's structure, run EAGERLY through the
    generic Pallas interpreter (CPU-safe), must be bit-identical to the
    scan oracle on a small [R, K] grid with distinct keys.  Small on
    purpose — the eager interpreter walks every grid cell in Python —
    but structurally complete: multiple key tiles, multiple row steps,
    a row0 offset via the tile walk, both codeword rows exercised."""
    from ..core import sqrtn
    from ..ops import pallas_sqrt
    pairs = [sqrtn.generate_sqrt_keys((i * 71 + 3) % gate_n, gate_n,
                                      b"ks%d" % i, prf_method)
             for i in range(n_keys)]
    keys = [p[0] for p in pairs] + [pairs[0][1]]
    seeds, cw1, cw2 = sqrtn.pack_sqrt_keys(keys)
    table = np.random.default_rng(gate_n).integers(
        -2 ** 31, 2 ** 31, (gate_n, entry_size),
        dtype=np.int64).astype(np.int32)
    import jax.numpy as jnp
    oracle = np.asarray(sqrtn.eval_contract_batched(
        seeds, cw1, cw2, jnp.asarray(table), prf_method=prf_method,
        kernel_impl="xla"))
    try:
        kw = dict(v.launcher_kwargs())
        # a searched tb may exceed this small gate batch — the launcher
        # pads up, so the structure under test is preserved
        out = np.asarray(pallas_sqrt.sqrt_grid_contract_pallas(
            seeds, cw1, cw2, jnp.asarray(table), prf_method=prf_method,
            row_chunk=v.row_chunk, interpret=True, **kw))
    except Exception:
        return False
    return out.shape == oracle.shape and np.array_equal(out, oracle)


def ggm_parity_ok(v: KernelVariant, *, prf_method: int,
                  gate_n: int = 256, n_keys: int = 3,
                  entry_size: int = 5) -> bool:
    """Interpret-mode parity gate for one GGM-pallas variant: the
    subtree kernel under this variant's (f_levels, tb), run EAGERLY
    through the generic Pallas interpreter (CPU-safe), must be
    bit-identical to the fused scan oracle on a small domain with
    distinct keys.  The variant's f_levels targets the REAL domain, so
    it is rescaled into the gate's subtree range — the structure under
    test (phase-1 frontier width, kernel C, key tile) is preserved."""
    from ..core import keygen as _keygen, u128
    depth = gate_n.bit_length() - 1
    keys = [_keygen.generate_keys((i * 71 + 3) % gate_n, gate_n,
                                  b"kg%d" % i, prf_method)[0]
            for i in range(n_keys)]
    cw1, cw2, last = expand.pack_keys(keys)
    table = np.random.default_rng(gate_n).integers(
        -2 ** 31, 2 ** 31, (gate_n, entry_size),
        dtype=np.int64).astype(np.int32)
    import jax.numpy as jnp
    tperm = jnp.asarray(table[u128.bit_reverse_indices(gate_n)])
    chunk = expand.clamp_chunk(None, gate_n, n_keys)
    oracle = np.asarray(expand.expand_and_contract(
        cw1, cw2, last, tperm, depth=depth, prf_method=prf_method,
        chunk_leaves=chunk))
    fl = int(v.f_levels) if v.f_levels is not None else 3
    fl = max(1, min(fl, depth - 3))
    try:
        out = np.asarray(expand._expand_contract_pallas(
            cw1, cw2, last, tperm, depth=depth, f=1 << fl,
            interpret=True, prf_method=prf_method, f_levels=fl,
            tb=v.tb))
    except Exception:
        return False
    return out.shape == oracle.shape and np.array_equal(out, oracle)


# ------------------------------------------------------------- search


def kernel_search(n: int, batch: int, *, entry_size: int = 16,
                  prf_method: int = PRF_CHACHA20, reps: int = 3,
                  generations: int = 3, population: int = 6,
                  distinct: int = 32, seed: int = 0,
                  cache: TuningCache | None = None, force: bool = False,
                  log=None) -> dict:
    """Seeded mutate/tournament search over the kernel-variant space
    for one (N, E, B, prf) shape; returns (and persists) the
    ``kvariant`` cache record.

    Seeding: the initial population always contains (a) the
    staged-descent winner from ``tune_eval`` — run first, warm-cache
    reused — (b) the static-heuristic knobs, and (c) the PR-10
    hand-tuned Pallas structure, so the searched winner can never
    regress any of them.  Each generation keeps the fastest half of the
    timed family and refills with single-field mutations of survivors.

    Fitness = best-of-``reps`` wall-clock through the REAL dispatch
    path (``DPF.eval_tpu`` with the variant pinned into the searched
    slot of the knob resolver, so the search exercises the same
    consumption path serving uses), gated by full-output equality with
    the scalar oracle.  Pallas variants race only where the kernel can
    compile (TPU); elsewhere they are interpret-parity-gated and pinned
    in the record (``pallas_pinned``) for a TPU run.
    """
    from ..api import DPF
    from ..core.u128 import next_pow2
    cache = cache if cache is not None else default_cache()
    pb = next_pow2(batch)
    key = cache_key(VARIANT_KIND, n=n, entry_size=entry_size, batch=pb,
                    prf_method=prf_method, scheme="sqrtn", radix=2)
    if not force:
        rec = cache.lookup(key)
        if rec is not None:
            return {**rec, "searched": False}

    rng = random.Random(0x5EED ^ seed ^ (n << 1) ^ batch)
    # (a) the staged-descent seed (its own equality-gated search; a
    # warm tuning cache answers without re-measuring)
    descent = tune_eval(n, batch, entry_size=entry_size,
                        prf_method=prf_method, scheme="sqrtn", radix=2,
                        reps=reps, distinct=distinct, cache=cache,
                        force=force, log=log)
    dk = descent["knobs"]
    seed_variant = KernelVariant(
        family="pallas" if dk.get("kernel_impl") == "pallas" else "xla",
        row_chunk=dk.get("row_chunk"), dot_impl=dk.get("dot_impl"))
    if seed_variant.family == "pallas":
        seed_variant = dataclasses.replace(
            pr10_default_variant(), row_chunk=dk.get("row_chunk"),
            dot_impl=dk.get("dot_impl"))
    # (b) the static heuristics as an XLA-family variant
    hk = heuristic_knobs(n, pb, prf_method=prf_method, scheme="sqrtn")
    heur_variant = KernelVariant(family="xla",
                                 row_chunk=hk.get("row_chunk"),
                                 dot_impl=hk.get("dot_impl"))

    table, keys, oracle = _workload(n, batch, entry_size, prf_method,
                                    "sqrtn", 2, distinct)
    tried = rejected = gate_escapes = 0
    timings: dict[str, float] = {}

    import jax
    time_pallas = jax.default_backend() == "tpu"

    def measure(v: KernelVariant) -> float | None:
        """Equality-gate then time one variant through the real
        dispatch path; None = rejected (counted, never timed)."""
        nonlocal tried, rejected
        tried += 1
        # every knob the variant owns stays AUTO in the config (the
        # EvalConfig defaults are explicit pins, which would outrank
        # the searched slot) — resolution must answer
        # kernel_resolved_from="searched" and run the variant
        cfg = EvalConfig(prf_method=prf_method, batch_size=batch,
                         radix=2, scheme="sqrtn", kernel_impl=None,
                         dot_impl=None, row_chunk=None)
        try:
            with cfg.applied():
                dpf = DPF(config=cfg)
                dpf.eval_init(table)
                # pin the variant into the SEARCHED slot of the knob
                # memo: resolution answers kernel_resolved_from=
                # "searched" and threads kernel_variant to the
                # launcher — the exact consumption path serving uses
                dpf._tuned_cache[dpf._pow2_domain(batch)] = {
                    "_searched": v.eval_knobs()}
                out = np.asarray(dpf.eval_tpu(keys))  # compile + warm
                kn = dpf.resolved_eval_knobs(dpf._pow2_domain(batch))
                if kn.get("kernel_resolved_from") != "searched":
                    raise AssertionError(
                        "variant pin did not resolve as searched "
                        "(got %r) — the measurement would time the "
                        "wrong program" % (kn,))
                if out.shape != oracle.shape or not np.array_equal(
                        out, oracle):
                    rejected += 1
                    if log:
                        log("  reject (oracle mismatch): %s" % v.tag())
                    return None
                best = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    np.asarray(dpf.eval_tpu(keys))
                    best = min(best, time.perf_counter() - t0)
            return best
        except AssertionError:
            raise  # a broken search harness, not a bad candidate
        except Exception as exc:
            rejected += 1
            if log:
                log("  reject (%s): %s" % (type(exc).__name__, v.tag()))
            return None

    # --- the timed tournament (XLA family; + Pallas where it compiles)
    def timed_ok(v):
        return v.family == "xla" or time_pallas

    pop: list[KernelVariant] = []
    for v in (seed_variant, heur_variant):
        if timed_ok(v) and v not in pop:
            pop.append(v)
    fam = ["xla"] + (["pallas"] if time_pallas else [])
    while len(pop) < population:
        v = sample_variant(rng, fam[len(pop) % len(fam)], n=n,
                           batch=pb, prf_method=prf_method)
        if v is None:
            break
        if v not in pop:
            pop.append(v)

    scores: dict[KernelVariant, float] = {}
    for gen in range(generations):
        for v in pop:
            if v in scores:
                continue
            bad = variant_invalid(v, n=n, batch=pb,
                                  prf_method=prf_method)
            if bad is not None:  # defensive: mutation pre-filters
                rejected += 1
                continue
            t = measure(v)
            if t is not None:
                scores[v] = t
                timings[v.tag()] = round(t, 6)
                if log:
                    log("  gen%d %-40s %.4fs" % (gen, v.tag(), t))
        ranked = sorted((s for s in scores.items() if s[0] in pop),
                        key=lambda s: s[1])
        if gen == generations - 1:
            break
        survivors = [v for v, _ in ranked[:max(2, population // 2)]]
        pop = list(survivors)
        stale = 0
        while len(pop) < population and stale < 4 * population:
            child = mutate_variant(rng, rng.choice(survivors), n=n,
                                   batch=pb, prf_method=prf_method)
            if child is None or child in pop or child in scores:
                stale += 1
                continue
            pop.append(child)

    if not scores:
        raise AssertionError(
            "kernel search timed no candidate for n=%d batch=%d prf=%s"
            % (n, batch, PRF_NAMES[prf_method]))
    winner, winner_s = min(scores.items(), key=lambda s: s[1])
    seed_s = scores.get(seed_variant)
    heur_s = scores.get(heur_variant)

    # --- the Pallas population: parity-gate every member (this is the
    # gate that makes the search meaningful off-TPU; on TPU they also
    # raced above).  Any parity failure is a correctness escape.
    pallas_pop = [pr10_default_variant()]
    from ..ops.pallas_sqrt import pallas_sqrt_unsupported
    from ..core import sqrtn as _sq
    _k, _r = _sq.default_split(n)
    if pallas_sqrt_unsupported(prf_method, _r) is None:
        while len(pallas_pop) < max(2, population // 2):
            v = (mutate_variant(rng, rng.choice(pallas_pop), n=n,
                                batch=pb, prf_method=prf_method)
                 if rng.random() < 0.5 else
                 sample_variant(rng, "pallas", n=n, batch=pb,
                                prf_method=prf_method))
            if v is not None and v not in pallas_pop:
                pallas_pop.append(v)
        gate_prf = prf_method
    else:
        # the timed prf has no Pallas plane core (DUMMY/AES) — gate the
        # structural variants with the ChaCha core so the pinned
        # population is still proven, and say so in the record
        gate_prf = PRF_CHACHA20
    pallas_parity = []
    for v in pallas_pop:
        ok = pallas_parity_ok(v, prf_method=gate_prf)
        if not ok:
            gate_escapes += 1
        pallas_parity.append({"variant": v.to_dict(), "tag": v.tag(),
                              "parity": bool(ok),
                              "timed_s": (round(scores[v], 6)
                                          if v in scores else None)})
        if log:
            log("  parity %-40s %s" % (v.tag(), "ok" if ok else "FAIL"))

    record = {
        "knobs": winner.eval_knobs(),
        "variant_tag": winner.tag(),
        "heuristic": hk,
        "pallas_pinned": pallas_parity,
        "pallas_gate_prf": PRF_NAMES[gate_prf],
        "measured": {
            "best_s": round(winner_s, 6),
            "seed_s": round(seed_s, 6) if seed_s is not None else None,
            "heuristic_s": (round(heur_s, 6)
                            if heur_s is not None else None),
            "speedup_vs_seed": (round(seed_s / winner_s, 4)
                                if seed_s else None),
            "speedup_vs_heuristic": (round(heur_s / winner_s, 4)
                                     if heur_s else None),
            "reps": reps, "generations": generations,
            "population": population, "batch": batch, "entries": n,
            "entry_size": entry_size, "prf": PRF_NAMES[prf_method],
            "scheme": "sqrtn", "radix": 2,
            "candidates_tried": tried, "rejected": rejected,
            "gate_escapes": gate_escapes,
            "pallas_timed": time_pallas,
            "timings": timings,
        },
        "fingerprint": device_fingerprint(),
        "gated": True,  # every timed candidate matched the scalar oracle
    }
    cache.store(key, record)
    return {**record, "searched": True}


def kernel_search_ggm(n: int, batch: int, *, entry_size: int = 16,
                      prf_method: int = PRF_CHACHA20, reps: int = 3,
                      generations: int = 3, population: int = 6,
                      distinct: int = 32, seed: int = 0,
                      cache: TuningCache | None = None,
                      force: bool = False, log=None) -> dict:
    """Seeded mutate/tournament search over the log-N/GGM expansion
    space for one (N, E, B, prf) shape; returns (and persists) the
    ``kvariant`` cache record under scheme="logn".

    The space: ``chunk_leaves`` x the ``f_levels`` level-fusion
    frontier x fused-vs-dispatch drive (with the dispatch engine's
    phase-2 group) x contraction ``dot_impl``, plus the subtree-kernel
    engine's (f_levels, tb) where C = N >> f_levels.  Seeding, gating,
    fitness, and the Pallas pin-don't-time rule are exactly
    :func:`kernel_search`'s: the population always contains the logn
    staged-descent winner and the static heuristics; every timed
    candidate runs through the REAL dispatch path with the variant
    pinned into the searched knob slot
    (``kernel_resolved_from="searched"`` asserted) and must match the
    scalar oracle bit-for-bit; subtree-kernel variants race only on
    TPU, elsewhere they are interpret-parity-gated
    (:func:`ggm_parity_ok`) and pinned in the record for a TPU run.
    """
    from ..api import DPF
    from ..core.u128 import next_pow2
    cache = cache if cache is not None else default_cache()
    pb = next_pow2(batch)
    key = cache_key(VARIANT_KIND, n=n, entry_size=entry_size, batch=pb,
                    prf_method=prf_method, scheme="logn", radix=2)
    if not force:
        rec = cache.lookup(key)
        if rec is not None:
            return {**rec, "searched": False}

    rng = random.Random(0x66D ^ seed ^ (n << 1) ^ batch)
    descent = tune_eval(n, batch, entry_size=entry_size,
                        prf_method=prf_method, scheme="logn", radix=2,
                        reps=reps, distinct=distinct, cache=cache,
                        force=force, log=log)
    dk = descent["knobs"]
    seed_engine = _IMPL_GGM_ENGINE.get(dk.get("kernel_impl"), "fused")
    if seed_engine == "pallas":
        # the descent's pallas chunk is the subtree kernel's own pick —
        # the variant spelling of that default is all-None
        seed_variant = KernelVariant(family="ggm", engine="pallas")
    else:
        seed_variant = KernelVariant(
            family="ggm", engine=seed_engine,
            chunk_leaves=dk.get("chunk_leaves"),
            dot_impl=dk.get("dot_impl"),
            dispatch_group=(dk.get("dispatch_group")
                            if seed_engine == "dispatch" else None))
    hk = heuristic_knobs(n, pb, prf_method=prf_method, scheme="logn")
    heur_variant = KernelVariant(family="ggm", engine="fused",
                                 chunk_leaves=hk.get("chunk_leaves"),
                                 dot_impl=hk.get("dot_impl"))

    table, keys, oracle = _workload(n, batch, entry_size, prf_method,
                                    "logn", 2, distinct)
    tried = rejected = gate_escapes = 0
    timings: dict[str, float] = {}

    import jax
    from ..ops.pallas_level import has_subtree_core
    time_pallas = (jax.default_backend() == "tpu"
                   and has_subtree_core(prf_method))

    def measure(v: KernelVariant) -> float | None:
        nonlocal tried, rejected
        tried += 1
        cfg = EvalConfig(prf_method=prf_method, batch_size=batch,
                         radix=2, scheme="logn", kernel_impl=None,
                         dot_impl=None, chunk_leaves=None,
                         dispatch_group=None)
        try:
            with cfg.applied():
                dpf = DPF(config=cfg)
                dpf.eval_init(table)
                dpf._tuned_cache[dpf._pow2_domain(batch)] = {
                    "_searched": v.eval_knobs()}
                out = np.asarray(dpf.eval_tpu(keys))  # compile + warm
                kn = dpf.resolved_eval_knobs(dpf._pow2_domain(batch))
                if kn.get("kernel_resolved_from") != "searched":
                    raise AssertionError(
                        "variant pin did not resolve as searched "
                        "(got %r) — the measurement would time the "
                        "wrong program" % (kn,))
                if out.shape != oracle.shape or not np.array_equal(
                        out, oracle):
                    rejected += 1
                    if log:
                        log("  reject (oracle mismatch): %s" % v.tag())
                    return None
                best = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    np.asarray(dpf.eval_tpu(keys))
                    best = min(best, time.perf_counter() - t0)
            return best
        except AssertionError:
            raise  # a broken search harness, not a bad candidate
        except Exception as exc:
            rejected += 1
            if log:
                log("  reject (%s): %s" % (type(exc).__name__, v.tag()))
            return None

    def timed_ok(v):
        return (v.engine or "fused") != "pallas" or time_pallas

    pop: list[KernelVariant] = []
    for v in (seed_variant, heur_variant):
        if timed_ok(v) and v not in pop:
            pop.append(v)
    engines = ["fused", "dispatch"] + (["pallas"] if time_pallas else [])
    while len(pop) < population:
        v = sample_variant(rng, "ggm", n=n, batch=pb,
                           prf_method=prf_method,
                           engine=engines[len(pop) % len(engines)])
        if v is None:
            break
        if v not in pop:
            pop.append(v)

    scores: dict[KernelVariant, float] = {}
    for gen in range(generations):
        for v in pop:
            if v in scores:
                continue
            bad = variant_invalid(v, n=n, batch=pb,
                                  prf_method=prf_method)
            if bad is not None:  # defensive: mutation pre-filters
                rejected += 1
                continue
            t = measure(v)
            if t is not None:
                scores[v] = t
                timings[v.tag()] = round(t, 6)
                if log:
                    log("  gen%d %-40s %.4fs" % (gen, v.tag(), t))
        ranked = sorted((s for s in scores.items() if s[0] in pop),
                        key=lambda s: s[1])
        if gen == generations - 1:
            break
        survivors = [v for v, _ in ranked[:max(2, population // 2)]]
        pop = list(survivors)
        stale = 0
        while len(pop) < population and stale < 4 * population:
            child = mutate_variant(rng, rng.choice(survivors), n=n,
                                   batch=pb, prf_method=prf_method)
            if child is None or child in pop or child in scores:
                stale += 1
                continue
            pop.append(child)

    if not scores:
        raise AssertionError(
            "ggm kernel search timed no candidate for n=%d batch=%d "
            "prf=%s" % (n, batch, PRF_NAMES[prf_method]))
    winner, winner_s = min(scores.items(), key=lambda s: s[1])
    seed_s = scores.get(seed_variant)
    heur_s = scores.get(heur_variant)

    # --- the subtree-kernel population: parity-gate every member (the
    # gate that makes the search meaningful off-TPU; on TPU they also
    # raced above).  Any parity failure is a correctness escape.
    gate_prf = prf_method if has_subtree_core(prf_method) else PRF_CHACHA20
    pallas_pop = [KernelVariant(family="ggm", engine="pallas")]
    while len(pallas_pop) < max(2, population // 2):
        v = (mutate_variant(rng, rng.choice(pallas_pop), n=n, batch=pb,
                            prf_method=gate_prf)
             if rng.random() < 0.5 else
             sample_variant(rng, "ggm", n=n, batch=pb,
                            prf_method=gate_prf, engine="pallas"))
        if v is not None and v not in pallas_pop:
            pallas_pop.append(v)
    pallas_parity = []
    for v in pallas_pop:
        ok = ggm_parity_ok(v, prf_method=gate_prf)
        if not ok:
            gate_escapes += 1
        pallas_parity.append({"variant": v.to_dict(), "tag": v.tag(),
                              "parity": bool(ok),
                              "timed_s": (round(scores[v], 6)
                                          if v in scores else None)})
        if log:
            log("  parity %-40s %s" % (v.tag(), "ok" if ok else "FAIL"))

    record = {
        "knobs": winner.eval_knobs(),
        "variant_tag": winner.tag(),
        "heuristic": hk,
        "pallas_pinned": pallas_parity,
        "pallas_gate_prf": PRF_NAMES[gate_prf],
        "measured": {
            "best_s": round(winner_s, 6),
            "seed_s": round(seed_s, 6) if seed_s is not None else None,
            "heuristic_s": (round(heur_s, 6)
                            if heur_s is not None else None),
            "speedup_vs_seed": (round(seed_s / winner_s, 4)
                                if seed_s else None),
            "speedup_vs_heuristic": (round(heur_s / winner_s, 4)
                                     if heur_s else None),
            "reps": reps, "generations": generations,
            "population": population, "batch": batch, "entries": n,
            "entry_size": entry_size, "prf": PRF_NAMES[prf_method],
            "scheme": "logn", "radix": 2,
            "candidates_tried": tried, "rejected": rejected,
            "gate_escapes": gate_escapes,
            "pallas_timed": time_pallas,
            "timings": timings,
        },
        "fingerprint": device_fingerprint(),
        "gated": True,  # every timed candidate matched the scalar oracle
    }
    cache.store(key, record)
    return {**record, "searched": True}


def keygen_search(n: int, batch: int, *,
                  prf_method: int = PRF_CHACHA20, scheme: str = "logn",
                  radix: int = 2, reps: int = 3, generations: int = 3,
                  population: int = 6, seed: int = 0,
                  cache: TuningCache | None = None,
                  force: bool = False, log=None) -> dict:
    """Seeded mutate/tournament search over the batched-keygen space
    for one (N, B, prf, construction) shape; returns (and persists) the
    ``kvariant`` cache record under the ``entry_size=0`` sentinel.

    The space: SHAKE squeeze batching (``squeeze_draws``) x vectorized
    ``prf_v`` limb-call grouping (``prf_group``) x target-path seed
    reuse (``path_reuse``) — every knob a bit-identical reformulation
    by PRF row-wise purity / DRBG stream identity.  Fitness is keys/s;
    the gate is the strongest one available: every TIMED candidate's
    output must equal the scalar generator oracle's serialized wire
    rows bit-for-bit, per key, BOTH servers.  The all-None baseline
    (the PR-4 vectorized path) is always in the population, so the
    winner can never regress it.  No Pallas leg exists here
    (``pallas_pinned`` is empty, ``pallas_timed`` false): keygen is a
    host-side numpy pipeline.
    """
    from ..core import keygen as _kg, radix4 as _r4, sqrtn as _sq
    from ..core.u128 import next_pow2
    cache = cache if cache is not None else default_cache()
    pb = next_pow2(batch)
    key = cache_key(VARIANT_KIND, n=n, entry_size=0, batch=pb,
                    prf_method=prf_method, scheme=scheme, radix=radix)
    if not force:
        rec = cache.lookup(key)
        if rec is not None:
            return {**rec, "searched": False}

    rng = random.Random(0x4E7 ^ seed ^ (n << 1) ^ batch)
    alphas = np.array([(i * 0x9E3779B1) % n for i in range(batch)],
                      dtype=np.int64)
    seeds = [b"kgs-%04d-" % i + bytes(7) for i in range(batch)]
    if scheme == "sqrtn":
        construction = "sqrtn.r2"
        scalar = [_sq.generate_sqrt_keys(int(a), n, sd, prf_method)
                  for a, sd in zip(alphas, seeds)]

        def gen(kn):
            return _sq.gen_sqrt_batched(alphas, n, seeds,
                                        prf_method=prf_method, knobs=kn)
    elif radix == 4:
        construction = "logn.r4"
        scalar = [_r4.generate_keys_r4(int(a), n, sd, prf_method)
                  for a, sd in zip(alphas, seeds)]

        def gen(kn):
            return _r4.gen_batched_r4(alphas, n, seeds,
                                      prf_method=prf_method, knobs=kn)
    else:
        construction = "logn.r2"
        scalar = [_kg.generate_keys(int(a), n, sd, prf_method)
                  for a, sd in zip(alphas, seeds)]

        def gen(kn):
            return _kg.gen_batched(alphas, n, seeds,
                                   prf_method=prf_method, knobs=kn)
    oracle = (np.stack([k[0].serialize() for k in scalar]),
              np.stack([k[1].serialize() for k in scalar]))

    tried = rejected = gate_escapes = 0
    timings: dict[str, float] = {}

    def measure(v: KernelVariant) -> float | None:
        nonlocal tried, rejected
        tried += 1
        kn = v.keygen_knobs() or None
        try:
            wa, wb = gen(kn)
            if not (np.array_equal(wa, oracle[0])
                    and np.array_equal(wb, oracle[1])):
                rejected += 1
                if log:
                    log("  reject (wire mismatch): %s" % v.tag())
                return None
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                gen(kn)
                best = min(best, time.perf_counter() - t0)
            return best
        except Exception as exc:
            rejected += 1
            if log:
                log("  reject (%s): %s" % (type(exc).__name__, v.tag()))
            return None

    baseline = KernelVariant(family="keygen")  # PR-4 behavior, all-None
    pop = [baseline]
    while len(pop) < population:
        v = sample_variant(rng, "keygen", n=n, batch=pb,
                           prf_method=prf_method)
        if v is None:
            break
        if v not in pop:
            pop.append(v)

    scores: dict[KernelVariant, float] = {}
    for gen_i in range(generations):
        for v in pop:
            if v in scores:
                continue
            bad = variant_invalid(v, n=n, batch=pb,
                                  prf_method=prf_method)
            if bad is not None:
                rejected += 1
                continue
            t = measure(v)
            if t is not None:
                scores[v] = t
                timings[v.tag()] = round(t, 6)
                if log:
                    log("  gen%d %-32s %.4fs (%d keys/s)"
                        % (gen_i, v.tag(), t, int(batch / t)))
        ranked = sorted((s for s in scores.items() if s[0] in pop),
                        key=lambda s: s[1])
        if gen_i == generations - 1:
            break
        survivors = [v for v, _ in ranked[:max(2, population // 2)]]
        pop = list(survivors)
        stale = 0
        while len(pop) < population and stale < 4 * population:
            child = mutate_variant(rng, rng.choice(survivors), n=n,
                                   batch=pb, prf_method=prf_method)
            if child is None or child in pop or child in scores:
                stale += 1
                continue
            pop.append(child)

    if baseline not in scores:
        raise AssertionError(
            "keygen search could not time the PR-4 baseline for n=%d "
            "batch=%d %s — nothing to compare against" % (n, batch,
                                                          construction))
    winner, winner_s = min(scores.items(), key=lambda s: s[1])
    base_s = scores[baseline]

    record = {
        "knobs": {"keygen_knobs": winner.keygen_knobs(),
                  "kernel_variant": winner.to_dict()},
        "variant_tag": winner.tag(),
        "heuristic": {},  # no keygen heuristics exist — None IS default
        "pallas_pinned": [],
        "pallas_gate_prf": None,
        "measured": {
            "best_s": round(winner_s, 6),
            "seed_s": round(base_s, 6),
            "heuristic_s": None,
            "speedup_vs_seed": round(base_s / winner_s, 4),
            "speedup_vs_heuristic": None,
            "keys_per_s": int(batch / winner_s),
            "baseline_keys_per_s": int(batch / base_s),
            "construction": construction,
            "reps": reps, "generations": generations,
            "population": population, "batch": batch, "entries": n,
            "entry_size": 0, "prf": PRF_NAMES[prf_method],
            "scheme": scheme, "radix": radix,
            "candidates_tried": tried, "rejected": rejected,
            "gate_escapes": gate_escapes,
            "pallas_timed": False,
            "timings": timings,
        },
        "fingerprint": device_fingerprint(),
        "gated": True,  # every timed candidate matched the wire oracle
    }
    cache.store(key, record)
    return {**record, "searched": True}


# --------------------------------------------------------------- sweep


#: --family spellings -> the per-shape search each runs
_SWEEP_FAMILIES = ("sqrtn", "logn", "keygen")


def _sweep_families(family: str) -> tuple:
    """Parse the ``--family`` flag: one of sqrtn|logn|keygen|all or a
    comma list; order preserved, duplicates dropped."""
    fams = (_SWEEP_FAMILIES if family == "all"
            else tuple(f.strip() for f in family.split(",") if f.strip()))
    seen, out = set(), []
    for f in fams:
        if f not in _SWEEP_FAMILIES:
            raise ValueError(
                "unknown kernel-search family %r (want %s or 'all')"
                % (f, "|".join(_SWEEP_FAMILIES)))
        if f not in seen:
            seen.add(f)
            out.append(f)
    return tuple(out)


def kernel_search_sweep(shapes=None, *, prf_method: int = PRF_CHACHA20,
                        entry_size: int = 16, reps: int = 3,
                        generations: int = 3, population: int = 6,
                        family: str = "sqrtn",
                        force: bool = False, dryrun: bool = False,
                        cache: TuningCache | None = None,
                        out: str | None = None,
                        quiet: bool = False) -> dict:
    """``benchmark.py --autotune-kernel``: run the per-family searches
    (:func:`kernel_search` for sqrtn, :func:`kernel_search_ggm` for
    logn, :func:`keygen_search` for keygen) per (N, B) point and emit
    one self-describing JSON record (committed as
    ``BENCH_KSEARCH2_r18.json``; the sqrt-N-only PR-15 record stays as
    ``BENCH_KSEARCH_r15.json``).  ``family`` is sqrtn|logn|keygen|all
    or a comma list; the default keeps the PR-15 call shape.
    ``--dryrun`` shrinks the shapes and the search budget to a
    seconds-long CI smoke with the same record shape (and the same
    invariants: 0 rejections, 0 gate escapes, a persisted family-tagged
    winner per family)."""
    from .search import DEFAULT_SWEEP
    compcache.enable()
    cache = cache if cache is not None else default_cache()
    log = None if quiet else (lambda m: print(m, flush=True))
    families = _sweep_families(family)
    if shapes is None:
        shapes = ((256, 32),) if dryrun else DEFAULT_SWEEP
    if dryrun:
        reps, generations, population = 1, 2, 4
    points = []
    for fam in families:
        for n, batch in shapes:
            if log:
                log("kernel search [%s] n=%d batch=%d prf=%s ..."
                    % (fam, n, batch, PRF_NAMES[prf_method]))
            if fam == "sqrtn":
                rec = kernel_search(
                    n, batch, entry_size=entry_size,
                    prf_method=prf_method, reps=reps,
                    generations=generations, population=population,
                    distinct=8 if dryrun else 32, cache=cache,
                    force=force, log=log)
            elif fam == "logn":
                rec = kernel_search_ggm(
                    n, batch, entry_size=entry_size,
                    prf_method=prf_method, reps=reps,
                    generations=generations, population=population,
                    distinct=8 if dryrun else 32, cache=cache,
                    force=force, log=log)
            else:
                rec = keygen_search(
                    n, batch, prf_method=prf_method, reps=reps,
                    generations=generations, population=population,
                    cache=cache, force=force, log=log)
            m = rec["measured"]
            pt = {
                "family": fam,
                "entries": n, "batch": batch,
                "winner": rec["variant_tag"],
                "winner_knobs": rec["knobs"],
                "winner_s": m["best_s"], "seed_s": m["seed_s"],
                "heuristic_s": m["heuristic_s"],
                "speedup_vs_seed": m["speedup_vs_seed"],
                "speedup_vs_heuristic": m["speedup_vs_heuristic"],
                "winner_qps": int(batch / m["best_s"]),
                "candidates_tried": m["candidates_tried"],
                "rejected": m["rejected"],
                "gate_escapes": m["gate_escapes"],
                "pallas_timed": m["pallas_timed"],
                "pallas_pinned": rec["pallas_pinned"],
                "pallas_all_parity": all(p["parity"]
                                         for p in rec["pallas_pinned"]),
                "from_cache": not rec["searched"],
            }
            if fam == "keygen":
                pt["winner_keys_per_s"] = m["keys_per_s"]
                pt["baseline_keys_per_s"] = m["baseline_keys_per_s"]
                pt["construction"] = m["construction"]
            points.append(pt)
    record = {
        "metric": "generative kernel-variant search (seeded mutate/"
                  "tournament, equality-gated, best-of-%d reps; Pallas "
                  "family interpret-parity-gated and pinned)" % reps,
        "fingerprint": device_fingerprint(),
        "prf": PRF_NAMES[prf_method],
        "families": list(families),
        "dryrun": dryrun,
        "points": points,
        "tuning_cache": cache.path,
        "compilation_cache": compcache.enabled_dir(),
        "cache_counters": CACHE_COUNTERS.as_dict(),
        # checked: every timed candidate passed its oracle gate AND
        # every pinned Pallas variant passed interpret parity
        "checked": (all(p["gate_escapes"] == 0 for p in points)
                    and all(p["pallas_all_parity"] for p in points)),
    }
    if "keygen" in families:
        # the keygen-throughput section of the bench record: keys/s per
        # construction and shape, winner vs the PR-4 baseline
        record["keygen_throughput"] = [
            {"construction": p["construction"], "entries": p["entries"],
             "batch": p["batch"],
             "baseline_keys_per_s": p["baseline_keys_per_s"],
             "winner_keys_per_s": p["winner_keys_per_s"],
             "speedup": p["speedup_vs_seed"]}
            for p in points if p["family"] == "keygen"]
    if not quiet:
        print(json.dumps(record), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return record
