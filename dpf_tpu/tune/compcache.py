"""JAX persistent compilation cache wiring (+ hit/miss counters).

Tuned programs are worthless if every process pays the XLA compile
again — cold-start warmup is real serving latency.  ``enable()`` turns
JAX's persistent compilation cache on with the entry-size/compile-time
floors removed, so *every* executable serializes; a second process then
deserializes instead of recompiling.

There is one way to place the cache: ``JAX_COMPILATION_CACHE_DIR``.
When it is set, JAX itself reads it and nothing here sets another
directory.  Otherwise the cache goes to the fixed path
``<repo>/.jax_compile_cache`` (ignored by git) — fixed, because the
path is part of what makes a later process find the entries.
``DPF_TPU_COMPILE_CACHE=0`` turns the cache off (the test suite does).

``DPF.eval_tpu``, the serving engine, the tuners, ``bench.py`` and
``chip_smoke.py`` call ``enable()``.  A ``jax.monitoring`` listener
mirrors the ``/jax/compilation_cache/{cache_hits,cache_misses}`` events
into ``utils.profiling.CACHE_COUNTERS.compile_{hits,misses}`` (plus
``compile_time_saved_s``), giving tests and benchmark records a
process-local view of recompiles skipped.
"""

from __future__ import annotations

import os

from ..utils.profiling import CACHE_COUNTERS

_ENV = "DPF_TPU_COMPILE_CACHE"
_JAX_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

_ENABLED_DIR: str | None = None
_LISTENING = False


def default_dir() -> str | None:
    """The directory ``enable()`` will use: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``REPO_DIR``; None when ``DPF_TPU_COMPILE_CACHE=0``."""
    off = os.environ.get(_ENV)
    if off is not None:
        if off.strip().lower() not in ("0", "off"):
            raise ValueError(
                "%s only takes 0 (cache off); place the cache with %s"
                % (_ENV, _JAX_ENV))
        return None
    return os.environ.get(_JAX_ENV) or REPO_DIR


def _listener(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        CACHE_COUNTERS.compile_hits += 1
    elif event == "/jax/compilation_cache/cache_misses":
        CACHE_COUNTERS.compile_misses += 1


def _duration_listener(event: str, duration: float, **kw) -> None:
    if event == "/jax/compilation_cache/compile_time_saved_sec":
        CACHE_COUNTERS.compile_time_saved_s += float(duration)


def _install_listeners() -> None:
    global _LISTENING
    if _LISTENING:
        return
    from jax import monitoring
    monitoring.register_event_listener(_listener)
    monitoring.register_event_duration_secs_listener(_duration_listener)
    _LISTENING = True


def enable() -> str | None:
    """Turn the persistent compilation cache on; returns the directory
    in use (None when off).  Idempotent; safe to call after backend
    init — only compiles *after* the call get cached."""
    global _ENABLED_DIR
    d = default_dir()
    if d is None or _ENABLED_DIR == d:
        return d
    import jax
    os.makedirs(d, exist_ok=True)
    if not os.environ.get(_JAX_ENV):
        jax.config.update("jax_compilation_cache_dir", d)
    # cache everything: the default floors (1 s compile, 0-byte entry)
    # skip exactly the small per-level programs the dispatch kernel and
    # the bucket ladder produce in bulk
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _install_listeners()
    _ENABLED_DIR = d
    return d


def enabled_dir() -> str | None:
    """The directory ``enable()`` last configured, or None."""
    return _ENABLED_DIR
