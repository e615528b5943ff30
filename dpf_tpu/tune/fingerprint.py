"""Cache keys for the autotuner: device fingerprint x program shape.

A tuned knob set is only valid for the (hardware, program-shape) pair it
was measured on — the whole point of measuring instead of guessing is
that a v4 TPU, a v5e, and a laptop CPU each pick differently.  The key
has two halves:

* ``device_fingerprint()`` — backend kind, device model, device count,
  and the jax/jaxlib versions (an XLA upgrade can shift the optimum, so
  it invalidates tuned entries rather than silently serving stale ones).
* ``shape_key()`` — the static program shape: (N, E, B, prf, scheme,
  radix).  These are exactly the static arguments of the fused eval jit
  (core/expand.py), so one entry per key covers one compiled program
  family.

``cache_key(kind, ...)`` joins both under a ``kind`` tag ("eval" for the
fused-eval knobs, "serve" for the engine's ladder/in-flight knobs,
"scheme" for the scheme-level winner — there scheme/radix are the
entry's ANSWER, not its shape, so the key pins them to the ``any``/0
sentinels; see ``search.scheme_cache_key``).
"""

from __future__ import annotations


def device_fingerprint() -> str:
    """Stable id of the measuring hardware+toolchain, e.g.
    ``cpu/cpu/x1/jax0.9.0+jaxlib0.9.0``."""
    import jax
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover
        jl = "?"
    devs = jax.devices()
    kind = (devs[0].device_kind if devs else "none").replace(" ", "_")
    return "%s/%s/x%d/jax%s+jaxlib%s" % (
        jax.default_backend(), kind, len(devs), jax.__version__, jl)


def shape_key(*, n: int, entry_size: int, batch: int, prf_method: int,
              scheme: str = "logn", radix: int = 2,
              mesh: str | None = None) -> str:
    """``mesh``: the mesh-shape tag (``mesh_tag``, e.g. "2x4" for a
    2-batch x 4-table mesh) for the mesh-path kinds ("mesh", "mserve",
    "meshsplit") — a knob set tuned for one split is meaningless on
    another, so the shape half of the key carries it.  None (the
    single-device kinds) keeps the pre-mesh key grammar byte-identical,
    so existing cache files stay valid."""
    key = "n%d.e%d.b%d.prf%d.%s.r%d" % (
        n, entry_size, batch, prf_method, scheme, radix)
    if mesh is not None:
        key += ".m%s" % mesh
    return key


def mesh_tag(mesh) -> str:
    """The mesh-shape half of a mesh-path cache key:
    ``<n_batch>x<n_table>`` for a ``parallel.sharded.make_mesh`` mesh,
    with an optional ``b<n_byte>`` suffix for the 2D row x entry-byte
    meshes (``make_mesh_2d``) — a trivial byte axis (size 1) drops the
    suffix, so a 2D mesh that degenerates to the 1D layout produces the
    PRE-2D tag byte-identically and every existing cache entry keeps
    resolving.  Any other axis layout (e.g. a custom batch-PIR group
    mesh) tags as ``<axis><size>`` pairs in axis order."""
    shape = dict(mesh.shape)
    if set(shape) == {"batch", "table"}:
        return "%dx%d" % (shape["batch"], shape["table"])
    if set(shape) == {"batch", "table", "byte"}:
        tag = "%dx%d" % (shape["batch"], shape["table"])
        return tag if shape["byte"] == 1 else tag + "b%d" % shape["byte"]
    return "x".join("%s%d" % (a, shape[a]) for a in mesh.axis_names)


def cache_key(kind: str, *, n: int, entry_size: int, batch: int,
              prf_method: int, scheme: str = "logn", radix: int = 2,
              mesh: str | None = None,
              fingerprint: str | None = None) -> str:
    """Full tuning-cache key: ``<kind>|<device>|<shape>``."""
    fp = fingerprint if fingerprint is not None else device_fingerprint()
    return "%s|%s|%s" % (kind, fp, shape_key(
        n=n, entry_size=entry_size, batch=batch, prf_method=prf_method,
        scheme=scheme, radix=radix, mesh=mesh))
