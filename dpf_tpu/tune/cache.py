"""Persistent JSON tuning cache: search once per shape per machine.

One small file (default ``~/.cache/dpf_tpu/tuning.json``, override with
``DPF_TPU_TUNE_CACHE=<path>``, disable with ``DPF_TPU_TUNE_CACHE=0``)
maps ``fingerprint.cache_key`` strings to tuned-knob records:

.. code-block:: json

    {"version": 1,
     "entries": {
       "eval|cpu/cpu/x1/jax0.9.0+...|n16384.e16.b512.prf0.logn.r2": {
         "knobs": {"chunk_leaves": 8192, "dot_impl": "i32",
                   "kernel_impl": "xla", "dispatch_group": null,
                   "aes_impl": "gather"},
         "measured": {"best_s": 0.031, "heuristic_s": 0.035,
                      "speedup": 1.13, "reps": 3},
         "tuned_at": "2026-08-04T.."}}}

Every lookup moves the process-wide
``utils.profiling.CACHE_COUNTERS.tuning_{hits,misses}`` counters, so a
warm second process can *prove* it skipped the search.  Writes are
atomic (tmp file + rename) and merge-on-save: concurrent tuners lose at
worst their own last write, never the whole file.
"""

from __future__ import annotations

import datetime
import json
import os
import tempfile

from ..utils.profiling import CACHE_COUNTERS, note_swallowed
from .fingerprint import cache_key

_ENV = "DPF_TPU_TUNE_CACHE"
_OFF = ("0", "off", "none", "disabled")
VERSION = 1


def default_path() -> str | None:
    """Resolved cache file path: unset -> ~/.cache/dpf_tpu/tuning.json,
    "0"/"off"/"none"/"disabled" -> None (disabled), else that path."""
    v = os.environ.get(_ENV)
    if v is not None:
        return None if v.strip().lower() in _OFF or not v.strip() else v
    return os.path.join(os.path.expanduser("~"), ".cache", "dpf_tpu",
                        "tuning.json")


class TuningCache:
    """Dict-of-records view over the JSON file (loaded once per
    instance).  ``path=None`` means ``default_path()``, which itself can
    be None (cache disabled via env) — then the cache is in-memory only
    and every lookup on a fresh process is a clean miss."""

    def __init__(self, path: str | None = None):
        self.path = path if path is not None else default_path()
        self.entries: dict = {}
        self.load_error: str | None = None
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    data = json.load(f)
                if data.get("version") == VERSION:
                    self.entries = dict(data.get("entries", {}))
            except (OSError, ValueError) as e:
                # corrupt cache = cold cache (tuning degrades to the
                # heuristics), but the cause stays visible: load_error
                # for callers, the swallowed-error registry + one-shot
                # warning for operators
                self.entries = {}
                self.load_error = "%s: %s" % (type(e).__name__, e)
                note_swallowed("tune.cache.load", e)

    # ------------------------------------------------------------ lookups

    def lookup(self, key: str) -> dict | None:
        rec = self.entries.get(key)
        if rec is None:
            CACHE_COUNTERS.tuning_misses += 1
        else:
            CACHE_COUNTERS.tuning_hits += 1
        return rec

    def lookup_knobs(self, kind: str, *, nearest_batch: bool = False,
                     **shape) -> dict | None:
        """The tuned knob dict for one shape, or None.

        With ``nearest_batch=True`` an exact-batch miss falls back to
        the same-shape entry whose batch is closest (largest tuned batch
        <= the requested one, else the smallest above): the engine's
        smaller buckets reuse the cap-size tuning rather than each
        demanding their own search.  One logical lookup moves exactly
        one counter, whichever probe answered.
        """
        rec = self.entries.get(cache_key(kind, **shape))
        if rec is None and nearest_batch:
            want = shape["batch"]
            below, above = None, None
            for b, r in self._batch_variants(kind, **shape):
                if b <= want and (below is None or b > below[0]):
                    below = (b, r)
                if b > want and (above is None or b < above[0]):
                    above = (b, r)
            hit = below or above
            rec = hit[1] if hit else None
        if rec is None:
            CACHE_COUNTERS.tuning_misses += 1
            return None
        CACHE_COUNTERS.tuning_hits += 1
        return rec.get("knobs")

    def _batch_variants(self, kind: str, **shape):
        for b in (1 << i for i in range(21)):
            if b == shape["batch"]:
                continue
            rec = self.entries.get(
                cache_key(kind, **{**shape, "batch": b}))
            if rec is not None:
                yield b, rec

    # ------------------------------------------------------------- stores

    def store(self, key: str, record: dict) -> None:
        record = dict(record)
        record.setdefault(
            "tuned_at",
            datetime.datetime.now(datetime.timezone.utc).isoformat(
                timespec="seconds"))
        self.entries[key] = record
        CACHE_COUNTERS.tuning_stores += 1
        self._save()

    def _save(self) -> None:
        if not self.path:
            return
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        merged = dict(self.entries)
        try:  # merge-on-save: keep entries another process added meanwhile
            with open(self.path) as f:
                disk = json.load(f)
            if disk.get("version") == VERSION:
                merged = {**disk.get("entries", {}), **self.entries}
        except (OSError, ValueError):
            pass
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(self.path) or ".", suffix=".tuning")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": VERSION, "entries": merged}, f,
                          indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


_DEFAULT: TuningCache | None = None


def default_cache(refresh: bool = False) -> TuningCache:
    """The process-wide cache over ``default_path()`` (re-created when
    the env var changes the path, or on ``refresh=True``)."""
    global _DEFAULT
    path = default_path()
    if refresh or _DEFAULT is None or _DEFAULT.path != path:
        _DEFAULT = TuningCache(path)
    return _DEFAULT


def lookup_eval_knobs(*, n: int, entry_size: int, batch: int,
                      prf_method: int, scheme: str = "logn",
                      radix: int = 2) -> dict | None:
    """Convenience for the dispatch paths (api.DPF / ShardedDPFServer):
    tuned fused-eval knobs for this shape on this machine, nearest-batch
    fallback included.  Never raises — an unreadable cache is a miss."""
    try:
        return default_cache().lookup_knobs(
            "eval", nearest_batch=True, n=n, entry_size=entry_size,
            batch=batch, prf_method=prf_method, scheme=scheme, radix=radix)
    except Exception as e:  # pragma: no cover — never break serving
        note_swallowed("tune.cache.lookup_eval_knobs", e)
        return None


def lookup_mesh_knobs(*, n: int, entry_size: int, batch: int,
                      prf_method: int, mesh: str, scheme: str = "logn",
                      radix: int = 2) -> dict | None:
    """Tuned MESH-path knobs (per-shard chunk_leaves/row_chunk, psum
    granularity) for this shape on this machine AND this mesh split
    (``mesh`` = ``fingerprint.mesh_tag``, e.g. "2x4"); populated by
    ``benchmark.py --multichip`` (``tune.mesh_tune``).  Nearest-batch
    fallback like the single-device lookup.  Never raises."""
    try:
        return default_cache().lookup_knobs(
            "mesh", nearest_batch=True, n=n, entry_size=entry_size,
            batch=batch, prf_method=prf_method, scheme=scheme,
            radix=radix, mesh=mesh)
    except Exception as e:  # pragma: no cover — never break serving
        note_swallowed("tune.cache.lookup_mesh_knobs", e)
        return None


def lookup_kernel_variant(*, n: int, entry_size: int, batch: int,
                          prf_method: int, scheme: str = "sqrtn",
                          radix: int = 2) -> dict | None:
    """The searched kernel-variant knobs for this shape on this machine
    (``{"kernel_impl": ..., "kernel_variant": {...}, ...}``), recorded
    by ``benchmark.py --autotune-kernel`` (``tune.kernel_search``) under
    the ``kvariant`` entry kind — a NEW kind, so pre-variant
    ``tuning.json`` files have no such entries and this lookup is simply
    a miss on them.  ``scheme``/``radix`` select the searched family's
    construction (sqrt-N entries under scheme="sqrtn", GGM/log-N entries
    under scheme="logn" with their radix) — the defaults preserve the
    pre-family call shape.  Nearest-batch fallback like the eval-knob
    lookup.  Never raises."""
    try:
        return default_cache().lookup_knobs(
            "kvariant", nearest_batch=True, n=n, entry_size=entry_size,
            batch=batch, prf_method=prf_method, scheme=scheme,
            radix=radix)
    except Exception as e:  # pragma: no cover — never break serving
        note_swallowed("tune.cache.lookup_kernel_variant", e)
        return None


def lookup_keygen_variant(*, n: int, batch: int, prf_method: int,
                          scheme: str = "logn",
                          radix: int = 2) -> dict | None:
    """The searched batched-keygen knobs for this shape on this machine
    (``{"keygen_knobs": {...}, "kernel_variant": {...}}``), recorded by
    ``benchmark.py --autotune-kernel --family=keygen``
    (``tune.kernel_search.keygen_search``).  Keygen cost is independent
    of the table entry size, so these entries are keyed with the
    ``entry_size=0`` sentinel — disjoint from the eval-side kvariant
    entries at the same (n, batch).  Never raises."""
    try:
        return default_cache().lookup_knobs(
            "kvariant", nearest_batch=True, n=n, entry_size=0,
            batch=batch, prf_method=prf_method, scheme=scheme,
            radix=radix)
    except Exception as e:  # pragma: no cover — never break serving
        note_swallowed("tune.cache.lookup_keygen_variant", e)
        return None


def lookup_scheme(*, n: int, entry_size: int, batch: int,
                  prf_method: int) -> dict | None:
    """The measured winning construction for this shape on this machine
    (``{"scheme": ..., "radix": ..., "construction": ...}``), recorded
    by ``benchmark.py --autotune-scheme`` (``search.scheme_sweep``);
    nearest-batch fallback like the eval-knob lookup.  Never raises."""
    try:
        return default_cache().lookup_knobs(
            "scheme", nearest_batch=True, n=n, entry_size=entry_size,
            batch=batch, prf_method=prf_method, scheme="any", radix=0)
    except Exception as e:  # pragma: no cover — never break serving
        note_swallowed("tune.cache.lookup_scheme", e)
        return None
