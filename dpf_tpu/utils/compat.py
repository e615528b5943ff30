"""Backend probes: can THIS process run the Pallas TPU kernels, and
what device memory does it see."""

from __future__ import annotations


def has_pallas_sqrt_kernel(backend: str | None = None) -> bool:
    """True when the Pallas TPU kernels can compile AND run in this
    process: the backend is TPU.  There the logn resolver's heuristic
    picks the subtree kernel for binary Salsa/ChaCha GGM
    (``api.DPF._heuristic_kernel``).  Elsewhere resolvers degrade a tuned
    or searched ``kernel_impl="pallas"`` to ``"xla"`` with provenance
    (``api.resolved_eval_knobs`` reports ``kernel_resolved_from=
    "degraded"`` and counts it via ``note_swallowed``) — the interpreter
    is a debugging device, not a serving path.  Pass ``backend`` to
    probe without initializing one."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    return backend == "tpu"


def device_memory_stats(device=None) -> dict | None:
    """``Device.memory_stats()`` as a plain dict, or None.

    On TPU (and CUDA) jaxlib exposes per-device allocator stats —
    notably ``bytes_limit`` (the HBM budget XLA will allocate against)
    and ``bytes_in_use``.  On CPU backends and older jaxlib the method
    is missing, returns None, or raises UNIMPLEMENTED; all of those
    collapse to a graceful ``None`` here so callers can treat "no
    stats" as "no device memory ceiling to plan around".

    ``plan/capacity.detect_hbm_budget`` seeds per-host HBM budgets from
    this probe when available.  NOTE: unlike the other probes in this
    module, resolving the default device initializes a backend — pass
    an explicit ``device`` (or call only after ``force_cpu_mesh``) in
    backend-order-sensitive code."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        stats = getattr(device, "memory_stats", None)
        if stats is None:
            return None
        out = stats()
    except Exception:  # pragma: no cover - backend-specific failures
        return None
    return dict(out) if out else None
