"""User-facing DPF API — drop-in compatible with the reference's ``dpf.DPF``.

Mirrors the surface of the reference Python API (``dpf.py:35-137`` +
``dpf_wrapper.cu:188-204``): ``gen``/``eval_init``/``eval_gpu``/``eval_cpu``/
``eval_free``, constants ``ENTRY_SIZE``/``BATCH_SIZE``/``PRF_*``, 524-int32
(2096 B) keys — but the server eval path is a jitted JAX program on TPU
(``eval_tpu``; ``eval_gpu`` is kept as an alias so reference scripts run
unmodified).

Tables are accepted as torch tensors (CPU), NumPy arrays, or anything
array-like; results come back as torch tensors when torch supplied the
inputs, NumPy arrays otherwise.
"""

from __future__ import annotations

import os

import numpy as np

from .core import evalref, expand, keygen
from .obs.tracer import annotate, span
from .utils.config import check_construction
from .core.prf_ref import (PRF_AES128, PRF_CHACHA20, PRF_CHACHA20_BLK,
                           PRF_DUMMY, PRF_NAMES, PRF_SALSA20,
                           PRF_SALSA20_BLK)


def _to_numpy(x, dtype=None):
    if hasattr(x, "detach"):  # torch tensor
        x = x.detach().cpu().numpy()
    arr = np.asarray(x)
    if dtype is not None and arr.dtype != dtype:
        arr = arr.astype(dtype)
    return arr


def _maybe_torch(arr, like_torch: bool):
    if like_torch:
        try:
            import torch
            arr = np.ascontiguousarray(arr)
            if not arr.flags.writeable:  # e.g. a view of a JAX array
                arr = arr.copy()
            return torch.from_numpy(arr)
        except ImportError:
            pass
    return arr


def _is_torch(x) -> bool:
    return hasattr(x, "detach")


def _native_gen(k, n, seed, prf_method):
    """Native keygen fast path (byte-identical to the Python DRBG)."""
    try:
        from . import native
        return native.gen(k, n, seed, prf_method)
    except Exception:
        return None


def gen_batched_binary(alphas, n, seeds, prf_method: int, knobs=None):
    """Fastest available batched BINARY keygen: the native C++ per-key
    generator when the extension is built (byte-identical to the Python
    DRBG construction, ~an order of magnitude faster per key than the
    vectorized numpy path at small depths), else
    ``keygen.gen_batched``.  Returns two [B, 524] int32 arrays either
    way; shared by ``DPF.gen_batch`` and the batch-PIR client.

    ``knobs``: searched keygen-variant knobs (``tune.kernel_search``),
    consumed only by the numpy path — the native loop keeps precedence
    (it has no such knobs and is already the per-key fast path)."""
    # same argument validation as the numpy path (short seed lists and
    # out-of-range alphas must not reach the native loop)
    alphas, seeds = keygen._check_batch_args(alphas, n, seeds)
    try:
        from . import native
        have_native = native.available()
    except Exception:
        have_native = False
    if have_native:
        try:  # bytes(sd): ctypes rejects the bytearray/memoryview seed
            #  types the validator accepts; any native failure falls
            #  back to the numpy path (same contract as _native_gen)
            outs = [native.gen(int(a), n, bytes(sd), prf_method)
                    for a, sd in zip(alphas, seeds)]
        except Exception:
            outs = [None]
        if all(o is not None for o in outs):
            return (np.stack([a for a, _ in outs]),
                    np.stack([b for _, b in outs]))
    return keygen.gen_batched(alphas, n, seeds, prf_method=prf_method,
                              knobs=knobs)


def _native_expand_batch(keys, prf_method):
    """Native full-expansion fast path; None to fall back to NumPy."""
    try:
        from . import native
        if not native.available():
            return None
        return np.stack([native.eval_expand(_to_numpy(k, np.int32),
                                            prf_method) for k in keys])
    except Exception:
        return None


class DPF(object):
    """Two-server DPF with TPU-accelerated server-side evaluation."""

    PRF_DUMMY = PRF_DUMMY
    PRF_SALSA20 = PRF_SALSA20
    PRF_CHACHA20 = PRF_CHACHA20
    PRF_AES128 = PRF_AES128
    # block-PRG ("wide") variants: one 512-bit stream-cipher block feeds
    # four GGM children (core/prf_ref.py::prf_salsa20_12_blk) — same
    # protocol, NOT wire-compatible with the reference's per-child PRFs
    PRF_SALSA20_BLK = PRF_SALSA20_BLK
    PRF_CHACHA20_BLK = PRF_CHACHA20_BLK

    ENTRY_SIZE = 16       # int32 words per entry (reference parity)
    BATCH_SIZE = 512      # max keys per device dispatch (reference parity)
    MIN_ENTRIES = 128

    DEFAULT_PRF = PRF_AES128

    def __init__(self, prf=None, strict=True, config=None, scheme=None,
                 entry_size=None):
        """config: optional utils.config.EvalConfig consolidating the
        runtime knobs (prf_method, batch_size, chunk_leaves, dot_impl,
        aes_impl, round_unroll) — the replacement for the reference's
        compile-time -D flag tiers.

        scheme: construction selector ("logn"/"sqrtn"/"auto") as a
        direct argument, so scripts don't need a full EvalConfig for
        it.  It wins over a ``config.scheme`` left at the "logn"
        default (a frozen dataclass can't tell default from explicit,
        and knob-only configs must stay combinable); a config pinned to
        a different non-default construction raises.

        scheme="auto" defers the construction choice to first use (gen
        or eval_init): the measured per-shape winner from the tuning
        cache (``tune.lookup_scheme``, recorded by ``benchmark.py
        --autotune-scheme``) wins, falling back to the cold-cache
        heuristic (``tune.search.heuristic_scheme``).  Resolution is
        sticky — once keys are minted or a table uploaded the
        construction is pinned (``scheme_resolved_from`` says which
        path answered).

        entry_size: the table width the scheme-cache lookup is keyed
        on.  Only meaningful with scheme="auto" on a keygen-only
        instance (no ``eval_init``): the server resolves with its real
        table width, so a client minting keys for an E!=16 table MUST
        pass the same width here or the two sides can resolve
        different constructions from the same cache."""
        self._config = config
        self.radix = 2
        self.scheme = "logn"
        if config is not None:
            if prf is None:
                prf = config.prf_method
            self.BATCH_SIZE = config.batch_size
            self.radix = getattr(config, "radix", 2)
            self.scheme = getattr(config, "scheme", "logn")
        if scheme is not None:
            if (config is not None and self.scheme != "logn"
                    and scheme != self.scheme):
                raise ValueError("scheme=%r conflicts with config.scheme=%r"
                                 % (scheme, self.scheme))
            self.scheme = scheme
        # the ONE validation point for the construction selectors — the
        # config and direct-argument spellings both land here
        check_construction(self.scheme, self.radix)
        if self.scheme == "auto" and self.radix == 4:
            raise ValueError(
                "scheme='auto' resolves the whole construction (scheme AND "
                "radix) from the tuning cache; leave radix at 2")
        if entry_size is not None and self.scheme != "auto":
            raise ValueError(
                "entry_size only parameterizes scheme='auto' resolution "
                "(the table's own width governs everything else)")
        self._auto_entry_size = entry_size
        self.scheme_resolved_from = None  # "cache"/"heuristic" once auto
        #                                   resolution has run
        self.prf_method = self.DEFAULT_PRF if prf is None else prf
        self.prf_method_string = PRF_NAMES[self.prf_method]
        self.strict = strict          # enforce reference shape limits
        self._tuned_cache = {}        # batch -> tuning-cache knob dict
        # (n, pow2 batch) -> searched keygen knobs or None; its own memo
        # because gen_batch runs before any eval_init and keys on the
        # GEN domain, not the table shape
        self._keygen_knobs_cache = {}
        self.table = None             # original table (numpy int32)
        self.table_device = None      # permuted table on device (jnp)
        self._kernel_tables = {}      # Pallas digit planes per layout
        self.table_num_entries = None
        self.table_effective_entry_size = None
        self._torch_io = False
        self.buffers = None           # reference-API compat handle
        # optional time.monotonic() soft deadline for
        # kernel_impl="dispatch": checked between per-level programs
        # (never interrupts a compile)
        self.dispatch_deadline = None

    # ------------------------------------------------------------------ gen

    @staticmethod
    def _pow2_domain(n: int) -> int:
        from .core.u128 import next_pow2
        return next_pow2(n)

    def _check_gen_domain(self, k, n: int) -> int:
        """The one domain rule for key generation, shared by the scalar
        and batched paths (`k` is the largest requested index): index in
        range, then the strict/auto-pad power-of-two policy.  Returns
        the (possibly padded) domain."""
        if k >= n:
            raise ValueError(
                "k (%d), the selected element, must be less than n (%d), "
                "the number of entries in the table" % (k, n))
        if n & (n - 1) != 0:
            if self.strict:
                raise ValueError(
                    "Table num entries (%d) must be a power of two "
                    "(pass strict=False to auto-pad)" % n)
            n = self._pow2_domain(n)
        return n

    def _ensure_scheme(self, n: int, entry_size: int | None = None):
        """Resolve ``scheme="auto"`` into a concrete construction for
        domain ``n``: the scheme-level tuning cache answers first
        (``tune.lookup_scheme`` — the winner ``benchmark.py
        --autotune-scheme`` measured for this shape on this machine),
        else the cold-cache heuristic.  Sticky: the first use (gen or
        eval_init) pins the construction — keys already minted must
        stay decodable by this instance."""
        if self.scheme != "auto":
            return
        from .tune.cache import lookup_scheme
        rec = lookup_scheme(
            n=n,
            entry_size=(entry_size or self._auto_entry_size
                        or self.ENTRY_SIZE),
            batch=self.BATCH_SIZE, prf_method=self.prf_method)
        if rec and rec.get("scheme") in ("logn", "sqrtn"):
            self.scheme_resolved_from = "cache"
        else:
            from .tune.search import heuristic_scheme
            rec = heuristic_scheme(n)
            self.scheme_resolved_from = "heuristic"
        self.scheme = rec["scheme"]
        self.radix = int(rec.get("radix") or 2)

    def gen(self, k, n, seed: bytes | None = None):
        """Generate the two servers' keys for secret index k in [0, n).

        With strict=False, non-power-of-two n is allowed (a reference TODO,
        ``dpf.py:24``): keys are generated over the next power-of-two
        domain, matching eval_init's zero-padding of the table.

        ``k`` may also be a LIST (or 1-D array) of indices: the batch
        routes through the vectorized generators (``gen_batch``) and two
        [B, words] key tensors come back, row i bit-identical to the
        scalar call for ``k[i]``.
        """
        if isinstance(k, (list, tuple, np.ndarray)) and np.ndim(k) >= 1:
            return self.gen_batch(k, n, seeds=seed)
        n = self._check_gen_domain(k, n)
        if seed is None:
            seed = os.urandom(128)
        self._ensure_scheme(n)
        if self.scheme == "sqrtn":
            from .core import sqrtn
            k0, k1 = sqrtn.generate_sqrt_keys(k, n, seed, self.prf_method)
            s0, s1 = k0.serialize(), k1.serialize()
            return _maybe_torch(s0, True), _maybe_torch(s1, True)
        if self.radix == 4:
            from .core import radix4
            k0, k1 = radix4.generate_keys_r4(k, n, seed, self.prf_method)
            s0, s1 = k0.serialize(), k1.serialize()
            return _maybe_torch(s0, True), _maybe_torch(s1, True)
        native_keys = _native_gen(k, n, seed, self.prf_method)
        if native_keys is not None:
            s0, s1 = native_keys
        else:
            k0, k1 = keygen.generate_keys(k, n, seed, self.prf_method)
            s0, s1 = k0.serialize(), k1.serialize()
        return _maybe_torch(s0, True), _maybe_torch(s1, True)

    def gen_batch(self, indices, n, seeds=None):
        """Batched keygen: B keys over one domain ``n`` in a few
        vectorized host calls (``keygen.gen_batched`` /
        ``radix4.gen_batched_r4`` / ``sqrtn.gen_sqrt_batched``) instead
        of a per-index ``gen`` loop — the client-side lever of the
        batch-PIR hot path (one key per bin, hundreds of bins).

        ``seeds``: optional list of per-key DRBG seeds (None = fresh
        ``os.urandom`` per key).  Returns two [B, words] int32 key
        tensors; row i is bit-identical to
        ``gen(indices[i], n, seed=seeds[i])`` (the scalar generator is
        the fuzz oracle, tests/test_api.py)."""
        import time as _time
        indices = np.asarray(indices, dtype=np.int64).reshape(-1)
        n = self._check_gen_domain(
            int(indices.max()) if indices.size else 0, n)
        self._ensure_scheme(n)
        knobs = self._resolved_keygen_knobs(n, indices.size)
        t0 = _time.perf_counter()
        if self.scheme == "sqrtn":
            from .core import sqrtn
            construction = "sqrtn.r2"
            wa, wb = sqrtn.gen_sqrt_batched(indices, n, seeds,
                                            prf_method=self.prf_method,
                                            knobs=knobs)
        elif self.radix == 4:
            from .core import radix4
            construction = "logn.r4"
            wa, wb = radix4.gen_batched_r4(indices, n, seeds,
                                           prf_method=self.prf_method,
                                           knobs=knobs)
        else:
            construction = "logn.r2"
            wa, wb = gen_batched_binary(indices, n, seeds,
                                        self.prf_method, knobs=knobs)
        try:  # observability must never break keygen
            from .obs.metrics import observe_keygen
            observe_keygen(construction, indices.size,
                           _time.perf_counter() - t0)
        except Exception as e:  # pragma: no cover
            from .utils.profiling import note_swallowed
            note_swallowed("api.keygen_metrics", e)
        return _maybe_torch(wa, True), _maybe_torch(wb, True)

    def _resolved_keygen_knobs(self, n: int, batch: int) -> dict | None:
        """Searched batched-keygen knobs for this (scheme, radix, n,
        batch), or None (the PR-4 baseline).  Same precedence family as
        ``resolved_eval_knobs``: there are no EvalConfig keygen fields,
        so the searched ``kvariant`` entry (``tune.kernel_search``
        "keygen" family, ``lookup_keygen_variant``) is the only rung
        above the baseline.  Memoized per (n, pow2 batch) so the hot
        batch-PIR client path pays one cache lookup per shape, and
        guarded on the variant family so a GGM/sqrt-N entry can never
        ride a keygen call."""
        from .core.u128 import next_pow2
        key = (n, next_pow2(max(1, batch)))
        memo = self._keygen_knobs_cache
        if key not in memo:
            from .tune.cache import lookup_keygen_variant
            rec = lookup_keygen_variant(
                n=n, batch=key[1], prf_method=self.prf_method,
                scheme=self.scheme, radix=self.radix) or {}
            fam = (rec.get("kernel_variant") or {}).get("family")
            kk = rec.get("keygen_knobs")
            memo[key] = dict(kk) if (kk and fam == "keygen") else None
        return memo[key]

    # ----------------------------------------------------------- eval_init

    def eval_init(self, table):
        """Upload a [N, E] integer table; pre-permutes rows for BFS order.

        With strict=False, non-power-of-two N is zero-padded to the next
        power of two (matching gen's domain rounding)."""
        self._torch_io = _is_torch(table)
        tbl = _to_numpy(table, np.int32)
        if tbl.ndim != 2:
            raise ValueError("table must be 2D [entries, entry_size]")
        n, e = tbl.shape
        if n < self.MIN_ENTRIES:
            raise ValueError(
                "Table (%d) must have at least %d elements"
                % (n, self.MIN_ENTRIES))
        if n & (n - 1) != 0:
            if self.strict:
                raise ValueError(
                    "Table num entries (%d) must be a power of two "
                    "(pass strict=False to auto-pad)" % n)
            n_pad = self._pow2_domain(n)
            padded = np.zeros((n_pad, e), np.int32)
            padded[:n] = tbl
            tbl, n = padded, n_pad
        if self.strict and e > self.ENTRY_SIZE:
            raise ValueError(
                "Table entry dimension (%d) must be <= %d "
                "(pass strict=False to lift)" % (e, self.ENTRY_SIZE))

        import jax.numpy as jnp
        self._ensure_scheme(n, e)
        self.table = tbl
        self.table_num_entries = n
        self.table_effective_entry_size = e
        self._tuned_cache = {}  # shape changed — re-resolve per batch
        self._kernel_tables = {}
        if self.scheme == "sqrtn":
            # the sqrt-N grid emits natural order — no permutation
            self.table_device = jnp.asarray(tbl)
        elif self.radix == 4:
            from .core import radix4
            perm = radix4.mixed_reverse_indices(radix4.arities(n))
            self.table_device = jnp.asarray(np.ascontiguousarray(tbl[perm]))
        else:
            self.table_device = jnp.asarray(expand.permute_table(tbl))
        self.buffers = (self.table_device,)
        return self.buffers

    # ------------------------------------------------------------ eval_tpu

    def eval_tpu(self, keys):
        """Batched server evaluation on the accelerator.

        keys: list of serialized key tensors ([524] int32 each).
        Returns [len(keys), entry_size] int32 shares.
        """
        if self.table_device is None:
            raise RuntimeError("Must call `eval_init` before `eval_tpu`")
        from .tune import compcache
        compcache.enable()
        eff = len(keys)
        if eff == 0:
            raise ValueError("empty key batch")
        with span("eval_tpu", batch=eff):
            results = []
            for i in range(0, eff, self.BATCH_SIZE):
                cur = keys[i:i + self.BATCH_SIZE]
                n_real = len(cur)
                # pad to the next power of two (bounded compile-cache
                # churn, reference pads to a fixed 512: dpf.py:123-126)
                cur = cur + [cur[-1]] * (self._pow2_domain(n_real) - n_real)
                # trim per chunk: with a non-power-of-two BATCH_SIZE, pad
                # rows would otherwise land mid-output
                results.append(self._eval_batch(cur)[:n_real])
            out = np.concatenate(results)[:, :self.table_effective_entry_size]
        return _maybe_torch(out, self._torch_io)

    # Reference scripts call eval_gpu; on this framework that IS the TPU.
    eval_gpu = eval_tpu

    def _pack_batch(self, keys):
        """Decode + validate a key batch -> (packed arrays, n, torch-ness
        of the inputs).  Uses the vectorized batched codec
        (``keygen.decode_keys_batched``) — one stacked buffer, O(1)
        Python decode ops — instead of the per-key scalar loop."""
        if not len(keys):
            raise ValueError("empty key batch")
        torch_io = any(_is_torch(k) for k in keys)
        pk = keygen.decode_keys_batched(keys)
        return (pk.cw1, pk.cw2, pk.last), pk.n, torch_io

    def eval_one_hot(self, keys):
        """Accelerated full one-hot expansion (a reference TODO,
        ``dpf.py:30``): [len(keys), N] int32 shares in natural index order,
        no table involved.  Memory is O(batch x N) — for large N prefer
        eval_tpu (fused) or eval_points (sparse)."""
        if self.scheme == "sqrtn":
            import jax.numpy as jnp

            from .core import sqrtn
            torch_io = any(_is_torch(k) for k in keys)
            sk = self._sqrt_batch(keys)
            out = np.stack([np.asarray(sqrtn.eval_grid(
                k, self.prf_method, jnp)) for k in sk])
            return _maybe_torch(out, torch_io)
        if self.radix == 4:
            import jax.numpy as jnp

            from .core import radix4
            torch_io = any(_is_torch(k) for k in keys)
            mk = self._mixed_batch(keys)
            cw1, cw2, last = radix4.pack_mixed_keys(mk)
            out = radix4.expand_leaves_mixed(
                jnp.asarray(cw1), jnp.asarray(cw2), jnp.asarray(last),
                n=mk[0].n, prf_method=self.prf_method)
            return _maybe_torch(np.asarray(out), torch_io)
        (cw1, cw2, last), n, torch_io = self._pack_batch(keys)
        out = expand.expand_leaves(cw1, cw2, last,
                                   depth=n.bit_length() - 1,
                                   prf_method=self.prf_method)
        return _maybe_torch(np.asarray(out), torch_io)

    def eval_points(self, keys, indices):
        """Sparse evaluation: each key at the given indices only.

        The "naive strategy" surface (reference ``dpf_gpu/dpf/dpf_naive.cu``):
        O(Q log N) PRF calls per key instead of O(N) — useful for spot
        checks or when only a few positions are needed.  Returns
        [len(keys), len(indices)] int32 one-hot shares (low 32 bits),
        independent of any table.
        """
        if self.scheme == "sqrtn":
            from .core import sqrtn
            torch_io = any(_is_torch(k) for k in keys)
            sk = self._sqrt_batch(keys)
            idx = np.asarray(indices, dtype=np.int64)
            if idx.ndim != 1 or (idx >= sk[0].n).any() or (idx < 0).any():
                raise ValueError("indices must be 1D and < n=%d" % sk[0].n)
            out = sqrtn.eval_points_sqrt(sk, idx, self.prf_method)
            return _maybe_torch(out, torch_io)
        if self.radix == 4:
            from .core import radix4
            torch_io = any(_is_torch(k) for k in keys)
            mk = self._mixed_batch(keys)
            idx = np.asarray(indices, dtype=np.uint64)
            if idx.ndim != 1 or (idx >= mk[0].n).any():
                raise ValueError("indices must be 1D and < n=%d" % mk[0].n)
            cw1, cw2, last = radix4.pack_mixed_keys(mk)
            out = radix4.eval_points_mixed(
                cw1, cw2, last, idx.astype(np.uint32), n=mk[0].n,
                prf_method=self.prf_method)
            return _maybe_torch(np.asarray(out), torch_io)
        (cw1, cw2, last), n, torch_io = self._pack_batch(keys)
        idx = np.asarray(indices, dtype=np.uint64)
        if idx.ndim != 1 or (idx >= n).any():
            raise ValueError("indices must be 1D and < n=%d" % n)
        out = expand.eval_points(cw1, cw2, last, idx.astype(np.uint32),
                                 depth=n.bit_length() - 1,
                                 prf_method=self.prf_method)
        return _maybe_torch(np.asarray(out), torch_io)

    def _sqrt_batch(self, keys):
        """Deserialize + validate a sqrt-N key batch (uniform split)."""
        from .core import sqrtn
        if not keys:
            raise ValueError("empty key batch")
        sk = [sqrtn.deserialize_sqrt_key(_to_numpy(k, np.int32))
              for k in keys]
        for k in sk:
            if (k.n, k.n_keys) != (sk[0].n, sk[0].n_keys):
                raise ValueError("keys for mixed sqrt-N splits")
        return sk

    def _eval_batch(self, keys) -> np.ndarray:
        # the spans live here, not in the two methods: the serving engine
        # calls those under its own ``pack`` and ``dispatch`` spans
        with span("eval_tpu.decode", batch=len(keys)):
            pk = self._decode_batch(keys)
        with span("eval_tpu.dispatch"):
            dev = self._dispatch_packed(pk)
        with span("eval_tpu.fetch"):   # the device's wait and the copy back
            return np.asarray(dev)

    def _decode_batch(self, keys):
        """Vectorized ingest: wire keys -> packed batch, validated
        against the initialized table (shared with the serving engine).
        Returns ``keygen.PackedKeys`` for the logn schemes,
        ``sqrtn.PackedSqrtKeys`` for scheme='sqrtn' — both via the
        batched codec (one stacked buffer, O(1) Python decode ops)."""
        if self.scheme == "sqrtn":
            from .core import sqrtn
            pk = sqrtn.decode_sqrt_keys_batched(keys)
        elif self.radix == 4:
            from .core import radix4
            pk = radix4.decode_mixed_keys_batched(keys)
        else:
            pk = keygen.decode_keys_batched(keys)
        n = self.table_num_entries
        if n is not None and pk.n != n:
            raise ValueError(
                "key generated for n=%d but table has n=%d" % (pk.n, n))
        return pk

    def resolved_eval_knobs(self, batch: int) -> dict:
        """Concrete program knobs for one dispatch batch size.

        Per-knob precedence: an explicit ``EvalConfig`` field wins; a
        field left at its auto state (``None``/``"auto"``) takes the
        tuned value from the persistent tuning cache
        (``tune/cache.py`` — keyed by device fingerprint x
        (N, E, B, prf, scheme, radix), nearest-batch fallback, populated
        by ``benchmark.py --autotune``); static heuristics
        (``expand.choose_chunk`` et al.) fill the rest.  The tuning
        lookup is cached per batch size (invalidated by ``eval_init``),
        but the process-global fallbacks (``matmul128.default_impl``,
        the AES pair impl, ``ROUND_UNROLL``) are re-read every call so
        ``set_dot_impl``/``apply_globals`` stay live between dispatches.

        scheme='sqrtn' resolves its own knob space (``dot_impl``,
        ``row_chunk``, ``kernel_impl``) under the same precedence with
        one extra rung: a SEARCHED kernel variant (``tune/
        kernel_search.py``'s ``kvariant`` cache entries) outranks the
        staged-descent knobs — provenance ``kernel_resolved_from`` is
        "config" | "searched" | "tuned" | "heuristic" | "degraded"
        (the last when a resolved "pallas" has no Pallas/TPU here and
        the xla scan answers instead).  A searched resolution carries
        the serialized variant under ``kernel_variant``; a "pallas"
        resolution also reports ``row_chunk_effective`` — the chunk the
        grid kernel will actually run after its VMEM cell cap, with a
        halved request counted at ``api.sqrt_row_chunk_halved``.
        ``row_chunk`` may come back None — the dispatch path resolves
        it against the decoded batch's key split
        (``sqrtn.clamp_row_chunk``).
        """
        from .core import prf as _prf
        from .ops import matmul128
        from .utils.config import is_auto
        cfg = self._config
        n = self.table_num_entries
        if n is None:
            raise RuntimeError("Must call `eval_init` before resolving")
        tuned = self._tuned_cache.get(batch)
        if tuned is None:
            if self.scheme == "sqrtn":
                auto_fields = ((cfg.row_chunk, cfg.dot_impl,
                                cfg.kernel_impl)
                               if cfg is not None else (None,))
            else:
                auto_fields = ((cfg.chunk_leaves, cfg.dot_impl,
                                cfg.kernel_impl, cfg.aes_impl,
                                cfg.dispatch_group)
                               if cfg is not None else (None,))
            if any(is_auto(v) for v in auto_fields):
                from .tune.cache import lookup_eval_knobs
                tuned = lookup_eval_knobs(
                    n=n, entry_size=self.table_effective_entry_size,
                    batch=batch, prf_method=self.prf_method,
                    scheme=self.scheme, radix=self.radix) or {}
                # searched kernel variants (tune/kernel_search.py)
                # live under their own "kvariant" entry kind and
                # ride in the memo's reserved "_searched" slot —
                # a tuner's measurement pin (a bare knob dict)
                # never carries one, so a pinned candidate is
                # timed as itself, not hijacked by a prior search.
                # The kvariant key carries (scheme, radix), so sqrt-N
                # and GGM entries never answer each other's lookups.
                from .tune.cache import lookup_kernel_variant
                searched = lookup_kernel_variant(
                    n=n, entry_size=self.table_effective_entry_size,
                    batch=batch, prf_method=self.prf_method,
                    scheme=self.scheme, radix=self.radix)
                if searched:
                    tuned = {**tuned, "_searched": searched}
            else:
                tuned = {}
            self._tuned_cache[batch] = tuned

        def pick(field, fallback):
            explicit = getattr(cfg, field) if cfg is not None else None
            if not is_auto(explicit):
                return explicit
            v = tuned.get(field)
            return v if v is not None else fallback

        if self.scheme == "sqrtn":
            # row_chunk's heuristic needs the key split (K, R), which
            # only the decoded batch knows — a None here is resolved at
            # dispatch by sqrtn.clamp_row_chunk, which also re-checks
            # tuned values against the live-slab budget.  kernel_impl
            # resolves with provenance: an unavailable Pallas host
            # degrades a tuned/pinned "pallas" to the xla scan instead
            # of raising (kernel_resolved_from="degraded", counted via
            # note_swallowed) so a tuning cache written on a TPU stays
            # usable on this machine
            searched = tuned.get("_searched") or {}
            if (searched.get("kernel_variant") or {}).get("family") in (
                    "ggm", "keygen"):
                # defense in depth: the kvariant key discipline already
                # separates the families, but a GGM/keygen entry must
                # never ride a sqrt-N dispatch even if hand-planted
                searched = {}
            explicit_k = cfg.kernel_impl if cfg is not None else None
            if not is_auto(explicit_k):
                kernel, kernel_from = explicit_k, "config"
            elif searched.get("kernel_impl") is not None:
                # a searched kernel variant (tune/kernel_search.py)
                # outranks the staged-descent knobs: it was seeded FROM
                # them and equality-gated, so it is never a regression
                kernel, kernel_from = searched["kernel_impl"], "searched"
            elif tuned.get("kernel_impl") is not None:
                kernel, kernel_from = tuned["kernel_impl"], "tuned"
            else:
                kernel, kernel_from = "xla", "heuristic"
            variant = (searched.get("kernel_variant")
                       if kernel_from == "searched" else None)
            if kernel == "pallas":
                from .utils.compat import has_pallas_sqrt_kernel
                if not has_pallas_sqrt_kernel():
                    from .utils.profiling import note_swallowed
                    note_swallowed(
                        "api.sqrt_kernel_unavailable",
                        RuntimeError(
                            "kernel_impl='pallas' (from %s) but Pallas/"
                            "TPU is unavailable here" % kernel_from))
                    kernel, kernel_from, variant = "xla", "degraded", None
            if kernel_from == "searched":
                # the searched (row_chunk, dot_impl) were gated with
                # THEIR kernel; a tuned row_chunk never mixes in
                row_chunk = (cfg.row_chunk
                             if cfg is not None
                             and not is_auto(cfg.row_chunk)
                             else searched.get("row_chunk"))
            else:
                row_chunk = pick("row_chunk", None)
                if (row_chunk is not None
                        and (cfg is None or is_auto(cfg.row_chunk))
                        and tuned.get("kernel_impl", "xla") != kernel):
                    # the tuner gated (row_chunk, kernel) together — a
                    # tuned row_chunk rides only with ITS kernel (the
                    # logn chunk_leaves rule); the winning kernel falls
                    # back to its own heuristic/VMEM clamp at dispatch
                    row_chunk = None
            if kernel_from == "searched" and (
                    cfg is None or is_auto(cfg.dot_impl)):
                dot = searched.get("dot_impl") or matmul128.default_impl()
            else:
                dot = pick("dot_impl", matmul128.default_impl())
            out = {
                "dot_impl": dot,
                "row_chunk": row_chunk,
                "kernel_impl": kernel,
                "kernel_resolved_from": kernel_from,
            }
            # extra provenance keys appear ONLY for searched/pallas
            # resolutions, so pre-variant cache entries resolve to the
            # exact pre-variant dict
            if variant is not None:
                out["kernel_variant"] = variant
            if kernel == "pallas":
                # the effective row chunk the grid kernel will RUN
                # (the VMEM cell cap halves over-large requests —
                # ops/pallas_sqrt.pallas_sqrt_row_chunk); surfacing it
                # here means the cache entry's claim and the kernel's
                # reality can no longer silently diverge
                from .core import sqrtn as _sqrtn
                from .ops.pallas_sqrt import pallas_sqrt_row_chunk
                _k, _r = _sqrtn.default_split(n)
                eff = pallas_sqrt_row_chunk(
                    _r, _k, row_chunk,
                    (variant or {}).get("max_cells"))
                out["row_chunk_effective"] = eff
                if row_chunk is not None and eff != row_chunk:
                    from .utils.profiling import note_swallowed
                    note_swallowed(
                        "api.sqrt_row_chunk_halved",
                        RuntimeError(
                            "requested sqrt row_chunk %d (from %s) "
                            "halved to %d by the VMEM cell cap"
                            % (row_chunk, kernel_from, eff)))
            return out

        # ---- logn (GGM) resolution.  A searched "ggm"-family kernel
        # variant (tune/kernel_search.py) outranks the staged-descent
        # knobs exactly like the sqrt-N branch; any other family in the
        # slot (pre-family sqrt-N entries, keygen variants) never rides
        # a logn dispatch — the kvariant key discipline already keeps
        # them out, this guard is the defense in depth the
        # backward-compat tests pin.
        searched = tuned.get("_searched") or {}
        variant = searched.get("kernel_variant") or {}
        if variant.get("family") != "ggm":
            searched, variant = {}, {}
        explicit_k = cfg.kernel_impl if cfg is not None else None
        if not is_auto(explicit_k):
            kernel_impl, kernel_from = explicit_k, "config"
        elif searched.get("kernel_impl") is not None:
            kernel_impl, kernel_from = searched["kernel_impl"], "searched"
        elif tuned.get("kernel_impl") is not None:
            kernel_impl, kernel_from = tuned["kernel_impl"], "tuned"
        else:
            kernel_impl, kernel_from = self._heuristic_kernel(), "heuristic"
        if kernel_from != "searched":
            searched, variant = {}, {}
        if kernel_impl == "pallas" and kernel_from in ("searched",
                                                       "tuned"):
            # a cache written where the subtree kernel compiles must
            # stay usable here: degrade to the xla scan instead of
            # raising (an EXPLICIT config "pallas" still passes through
            # and fails loudly at dispatch)
            from .utils.compat import has_pallas_sqrt_kernel
            if not has_pallas_sqrt_kernel():
                from .utils.profiling import note_swallowed
                note_swallowed(
                    "api.ggm_kernel_unavailable",
                    RuntimeError(
                        "kernel_impl='pallas' (from %s) but Pallas/TPU "
                        "is unavailable here" % kernel_from))
                kernel_impl, kernel_from = "xla", "degraded"
                searched, variant = {}, {}
        depth = n.bit_length() - 1
        f_levels = searched.get("f_levels")
        chunk_req = chunk_from = None
        if cfg is not None and cfg.chunk_leaves:
            chunk_req, chunk_from = int(cfg.chunk_leaves), "config"
            chunk = min(chunk_req, n)
        elif searched.get("chunk_leaves"):
            # the searched (chunk, f_levels, dot) were gated with THEIR
            # kernel; the live-seed budget is still re-checked (the
            # nearest-batch fallback can pair a small-batch chunk with
            # a bigger batch)
            chunk_req, chunk_from = int(searched["chunk_leaves"]), \
                "searched"
            chunk = expand.clamp_chunk(chunk_req, n, batch)
        elif (tuned.get("chunk_leaves")
                and tuned.get("kernel_impl", "xla") == kernel_impl):
            # the tuner gated (chunk, kernel) together — a tuned chunk
            # rides only with ITS kernel (an entry naming none was timed
            # on the xla scan; a kernel that differs, e.g. pallas with
            # its VMEM-bounded tile chunk, falls through to that
            # kernel's own heuristic) and is
            # re-checked against the live-seed budget (nearest-batch
            # fallback can pair a small-batch chunk with a bigger batch)
            chunk_req, chunk_from = int(tuned["chunk_leaves"]), "tuned"
            chunk = expand.clamp_chunk(chunk_req, n, batch)
        elif (kernel_impl == "pallas" and self.radix == 2
                and self.prf_method != PRF_AES128):
            # subtree-kernel chunk is bounded by per-tile VMEM state;
            # the AES plane-level kernel uses the standard memory bound.
            # A searched f_levels IS the chunk here (C = N >> f_levels)
            from .ops.pallas_level import pallas_chunk_leaves
            chunk = ((n >> int(f_levels)) if f_levels
                     else pallas_chunk_leaves(n))
        else:
            chunk = expand.clamp_chunk(None, n, batch)
        clamped = chunk_req is not None and chunk != chunk_req
        if clamped:
            # satellite of the sqrt-N row_chunk_effective move: a
            # silently-clamped request is surfaced, never swallowed
            from .utils.profiling import note_swallowed
            note_swallowed(
                "api.chunk_leaves_clamped",
                RuntimeError(
                    "requested chunk_leaves %d (from %s) clamped to %d "
                    "by the live-seed budget" % (chunk_req, chunk_from,
                                                 chunk)))
        if f_levels is not None and self.radix == 2:
            # a clamped/overridden chunk can invalidate the searched
            # phase split (f_levels must cover at least log2(N/C))
            base = depth - int(chunk).bit_length() + 1
            if not base <= int(f_levels) <= depth:
                f_levels = None
        if cfg is not None and cfg.round_unroll is not None:
            round_unroll = cfg.round_unroll
        elif "round_unroll" in tuned:  # the tuner's measurement pin
            round_unroll = tuned["round_unroll"]
        else:
            round_unroll = _prf.ROUND_UNROLL
        if kernel_from == "searched" and (cfg is None
                                          or is_auto(cfg.dot_impl)):
            dot = searched.get("dot_impl") or matmul128.default_impl()
        else:
            dot = pick("dot_impl", matmul128.default_impl())
        if kernel_from == "searched" and (
                cfg is None or is_auto(cfg.dispatch_group)):
            group = searched.get("dispatch_group")
        else:
            group = pick("dispatch_group", None)
        out = {
            "chunk_leaves": chunk,
            "dot_impl": dot,
            "aes_impl": pick("aes_impl", _prf._aes_pair_impl()),
            "round_unroll": round_unroll,
            "kernel_impl": kernel_impl,
            "dispatch_group": group,
            "kernel_resolved_from": kernel_from,
            "f_levels": f_levels,
        }
        if variant:
            out["kernel_variant"] = variant
        if clamped:
            out["chunk_leaves_effective"] = chunk
        return out

    def _heuristic_kernel(self) -> str:
        """The logn kernel when no config, tuned or searched entry names
        one (``ops.pallas_level.heuristic_kernel``, the mesh's rule
        too)."""
        from .ops.pallas_level import heuristic_kernel
        return heuristic_kernel(self.prf_method, self.radix)

    def _kernel_table(self, key, build):
        """The device table in a Pallas kernel's [4, N, E] int8 digit
        form, built by ``build(table_device)`` once per kernel layout
        ``key`` and kept until the next ``eval_init``/``eval_free``.  A
        call then holds no table-sized temporaries; the resident cost is
        one more table's bytes."""
        if key not in self._kernel_tables:
            self._kernel_tables[key] = build(self.table_device)
        return self._kernel_tables[key]

    def _dispatch_packed(self, pk: keygen.PackedKeys):
        """Dispatch one packed batch to the device and return the device
        array WITHOUT forcing a host sync: JAX async dispatch lets the
        caller (the serving engine) pack the next batch while this one
        runs.  Blocking callers wrap the result in ``np.asarray``."""
        if self.table_device is None:
            raise RuntimeError("Must call `eval_init` before dispatch")
        if self.scheme == "sqrtn":
            return self._dispatch_packed_sqrt(pk)
        if self.radix == 4:
            return self._dispatch_packed_r4(pk)
        cw1, cw2, last = pk.cw1, pk.cw2, pk.last
        n = self.table_num_entries
        depth = n.bit_length() - 1
        k = self.resolved_eval_knobs(pk.batch)
        annotate(kernel=k["kernel_impl"])
        chunk = k["chunk_leaves"]
        if n % chunk:
            raise ValueError(
                "chunk_leaves (%d) must divide table size %d" % (chunk, n))
        if k["kernel_impl"] == "dispatch":
            return expand.eval_dispatch(
                cw1, cw2, last, self.table_device, depth=depth,
                prf_method=self.prf_method, chunk_leaves=chunk,
                group=k["dispatch_group"],
                dot_impl=k["dot_impl"], aes_impl=k["aes_impl"],
                round_unroll=k["round_unroll"],
                deadline=self.dispatch_deadline)
        table = self.table_device
        if k["kernel_impl"] == "pallas" and self.prf_method != PRF_AES128:
            from .ops.pallas_level import subtree_digits, tiled_levels
            fl = k.get("f_levels") or depth - chunk.bit_length() + 1
            sched = (2,) * (depth - fl)
            annotate(tiled_levels=tiled_levels(sched))
            table = self._kernel_table(
                ("subtree", fl),
                lambda t: subtree_digits(t, 1 << fl, sched))
        return expand.expand_and_contract(
            cw1, cw2, last, table, depth=depth,
            prf_method=self.prf_method, chunk_leaves=chunk,
            dot_impl=k["dot_impl"], aes_impl=k["aes_impl"],
            round_unroll=k["round_unroll"], kernel_impl=k["kernel_impl"],
            f_levels=k.get("f_levels"),
            pallas_tb=(k.get("kernel_variant") or {}).get("tb"))

    def _dispatch_packed_sqrt(self, pk):
        """Sqrt-N device dispatch: row-chunked fused PRF-grid evaluation
        (``sqrtn.eval_contract_batched``), async like the logn paths.
        Shares the tuned-knob resolution; a TUNED row_chunk is hardened
        against THIS batch's key split and the live-slab budget
        (``sqrtn.clamp_row_chunk`` — tuned entries key on the table
        shape, not the split), while an EXPLICIT ``EvalConfig.row_chunk``
        passes straight through so an invalid pin raises rather than
        silently measuring the heuristic (the logn chunk_leaves rule).

        ``kernel_impl`` comes resolved (with availability degradation)
        from ``resolved_eval_knobs``; what remains here is the
        SHAPE-level gate only the decoded batch can answer — the grid
        kernel needs a supported prf core and, for the block-PRG ids,
        R % 4 == 0 (``pallas_sqrt.pallas_sqrt_unsupported``).  An
        unsupported shape degrades to the xla scan with the same
        note_swallowed provenance rather than raising."""
        from .core import sqrtn
        from .utils.config import is_auto
        kn = self.resolved_eval_knobs(pk.batch)
        explicit = (self._config.row_chunk if self._config is not None
                    else None)
        if not is_auto(explicit):
            rc = int(explicit)
        else:
            rc = sqrtn.clamp_row_chunk(kn["row_chunk"], pk.n_codewords,
                                       pk.n_keys, pk.batch)
        kernel = kn.get("kernel_impl", "xla")
        if kernel == "pallas":
            from .ops.pallas_sqrt import pallas_sqrt_unsupported
            reason = pallas_sqrt_unsupported(self.prf_method,
                                             pk.n_codewords)
            if reason is not None:
                from .utils.profiling import note_swallowed
                note_swallowed("api.sqrt_kernel_unsupported",
                               ValueError(reason))
                kernel = "xla"
        annotate(kernel=kernel)
        table = self.table_device
        if kernel == "pallas":
            from .ops.pallas_level import table_digits
            table = self._kernel_table(("sqrt",), table_digits)
        return sqrtn.eval_contract_batched(
            pk.seeds, pk.cw1, pk.cw2, table,
            prf_method=self.prf_method, dot_impl=kn["dot_impl"],
            row_chunk=rc, kernel_impl=kernel,
            kernel_variant=kn.get("kernel_variant"))

    def _mixed_batch(self, keys):
        """Deserialize + validate a radix-4 key batch (uniform n)."""
        from .core import radix4
        if not keys:
            raise ValueError("empty key batch")
        mk = [radix4.deserialize_mixed_key(k) for k in keys]
        for k in mk:
            if k.n != mk[0].n:
                raise ValueError("keys for mixed table sizes")
        return mk

    def _dispatch_packed_r4(self, pk: keygen.PackedKeys):
        """Radix-4 device dispatch (core/radix4.py engines), async like
        ``_dispatch_packed``.  Shares the tuned-knob resolution."""
        from .core import radix4
        cw1, cw2, last = pk.cw1, pk.cw2, pk.last
        n = self.table_num_entries
        k = self.resolved_eval_knobs(pk.batch)
        annotate(kernel=k["kernel_impl"])
        if k["kernel_impl"] == "pallas":
            table = self.table_device
            if self.prf_method != PRF_AES128:
                table = self._kernel_table(
                    ("mixed",), lambda t: radix4.mixed_digits(t, n))
            out = radix4.expand_and_contract_mixed_pallas(
                cw1, cw2, last, table, n=n,
                prf_method=self.prf_method, aes_impl=k["aes_impl"],
                dot_impl=k["dot_impl"])
        elif k["kernel_impl"] == "dispatch":
            out = radix4.eval_dispatch_mixed(
                cw1, cw2, last, self.table_device, n=n,
                prf_method=self.prf_method, chunk_leaves=k["chunk_leaves"],
                group=k["dispatch_group"],
                dot_impl=k["dot_impl"], aes_impl=k["aes_impl"],
                round_unroll=k["round_unroll"],
                deadline=self.dispatch_deadline)
        else:
            out = radix4.expand_and_contract_mixed(
                cw1, cw2, last, self.table_device, n=n,
                prf_method=self.prf_method, chunk_leaves=k["chunk_leaves"],
                dot_impl=k["dot_impl"], aes_impl=k["aes_impl"],
                round_unroll=k["round_unroll"],
                f_levels=k.get("f_levels"))
        return out

    # ------------------------------------------------------------ eval_cpu

    def eval_cpu(self, keys, one_hot_only=False):
        """Host reference evaluation (native C++ when available, else
        vectorized NumPy breadth-first)."""
        torch_io = any(_is_torch(k) for k in keys)
        if self.scheme == "sqrtn":
            from .core import sqrtn
            sk = self._sqrt_batch(keys)
            hots = np.stack([sqrtn.eval_grid(k, self.prf_method)
                             for k in sk])
        elif self.radix == 4:
            from .core import radix4
            mk = self._mixed_batch(keys)
            cw1, cw2, last = radix4.pack_mixed_keys(mk)
            hots = np.asarray(radix4.expand_leaves_mixed(
                cw1, cw2, last, n=mk[0].n, prf_method=self.prf_method))
        else:
            hots = self._binary_one_hots(keys)
        if one_hot_only:
            return _maybe_torch(hots, torch_io)
        if self.table is None:
            raise RuntimeError(
                "Must call `eval_init` before `eval_cpu` with "
                "one_hot_only=False")
        # exact wrapping mod-2^32 matmul on host
        prod = hots.astype(np.uint32) @ self.table.view(np.uint32)
        return _maybe_torch(prod.view(np.int32), torch_io or self._torch_io)

    def _binary_one_hots(self, keys):
        from .core import radix4
        for k in keys:  # marker check BEFORE the native fast path, which
            #             would otherwise misparse mixed-radix layouts
            if radix4.is_mixed_key(_to_numpy(k, np.int32)):
                raise ValueError(
                    "mixed-radix key — use DPF(config=EvalConfig(radix=4))")
        hots = _native_expand_batch(keys, self.prf_method)
        if hots is None:
            flat = [keygen.deserialize_key(k) for k in keys]
            hots = np.stack([evalref.eval_one_hot_i32(fk, self.prf_method)
                             for fk in flat])  # [B, N] int32
        return hots

    # ------------------------------------------------------- serving_engine

    def serving_engine(self, **kwargs):
        """Construct a throughput-oriented ``ServingEngine`` over this
        DPF's initialized table (``serve/engine.py``): vectorized key
        ingest, double-buffered async dispatch, shape-bucketed batching.

        kwargs forward to ``ServingEngine`` (``max_in_flight``,
        ``buckets``, ``warmup``).  Requires a prior ``eval_init``.
        """
        from .serve import ServingEngine
        return ServingEngine(self, **kwargs)

    # --------------------------------------------------------- mesh scale-out

    def sharded_server(self, mesh=None, **kwargs):
        """Mesh scale-out counterpart of ``serving_engine``: a
        ``parallel.sharded.ShardedDPFServer`` over this DPF's table with
        the same construction, PRF, and batch cap — the one-liner from a
        single-device deployment to the mesh path (docs/SHARDING.md).

        Requires a prior ``eval_init`` (which also resolves
        ``scheme="auto"``, so the mesh server inherits the concrete
        construction and keys already minted stay servable).  ``mesh``:
        a ``parallel.sharded.make_mesh`` mesh (None = one over all
        devices); kwargs forward to ``ShardedDPFServer`` (the explicit
        knob pins ``chunk_leaves``/``row_chunk``/``psum_group``/
        ``dot_impl``)."""
        if self.table is None:
            raise RuntimeError(
                "Must call `eval_init` before `sharded_server`")
        from .parallel.sharded import ShardedDPFServer
        return ShardedDPFServer(
            self.table, mesh, prf_method=self.prf_method,
            batch_size=self.BATCH_SIZE, radix=self.radix,
            scheme=self.scheme, **kwargs)

    # ------------------------------------------------------------ eval_free

    def eval_free(self, buffers=None):
        self.table_device = None
        self._kernel_tables = {}
        self.buffers = None

    def __repr__(self):
        if self.table_device is None:
            return ("DPF(_uninitialized_, prf_method=%s)"
                    % self.prf_method_string)
        return ("DPF(entries=%d, entry_size=%d, prf_method=%s)"
                % (self.table_num_entries, self.table_effective_entry_size,
                   self.prf_method_string))
