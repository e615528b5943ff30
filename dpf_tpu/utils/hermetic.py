"""Force a hermetic multi-device CPU JAX platform (virtual mesh).

Single source of truth for the recipe used by both ``tests/conftest.py``
and ``__graft_entry__.dryrun_multichip``: rewrite ``XLA_FLAGS`` and update
``jax_platforms`` *before* the first backend initialization, so the
process gets ``n`` virtual CPU devices and never touches an accelerator.
"""

import os
import re


def force_cpu_mesh(n_devices: int = 8, verify: bool = True) -> None:
    """Pin JAX to the CPU platform with ``n_devices`` virtual devices.

    Must be called before any JAX backend initialization (device query,
    compile, or array op).  Raises RuntimeError if a backend was already
    initialized in this process — the flags can no longer take effect and
    the caller needs a fresh process.

    ``verify=False`` skips the final ``jax.default_backend()`` check —
    that call itself initializes the backend, which must not happen yet
    when the caller still has to run ``jax.distributed.initialize``
    (multi-process tests); such callers verify after distributed init.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    flag = "--xla_force_host_platform_device_count=%d" % n_devices
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags = (flags + " " + flag).strip()
    elif int(m.group(1)) < n_devices:
        # raise a too-small pre-existing count; keep a larger user override
        flags = flags[:m.start()] + flag + flags[m.end():]
    os.environ["XLA_FLAGS"] = flags

    import jax

    initialized = False
    try:
        from jax._src import xla_bridge
        initialized = xla_bridge.backends_are_initialized()
    except (ImportError, AttributeError):  # private API moved; best effort
        pass
    if initialized:
        # Idempotent no-op when a prior call already produced what we need
        # (e.g. conftest forced 8 CPU devices and a test then calls
        # dryrun_multichip in-process).
        if (jax.default_backend() == "cpu"
                and len(jax.devices()) >= n_devices):
            return
        raise RuntimeError(
            "force_cpu_mesh needs a fresh process: a JAX backend (%r, %d "
            "devices) was initialized before the CPU platform could be "
            "forced to %d devices"
            % (jax.default_backend(), len(jax.devices()), n_devices))

    # Must run before the first backend touch: a backend query would
    # itself initialize the default (accelerator) backend.
    jax.config.update("jax_platforms", "cpu")
    if verify and jax.default_backend() != "cpu":
        raise RuntimeError(
            "failed to force the CPU platform: default backend is %r"
            % jax.default_backend())
