"""JAX version check.

Analog of ref mpi4jax/_src/jax_compat.py:11-47: the reference pins a
latest-validated JAX version (shipped as ``_latest_jax_version.txt``) and
warns when the installed JAX is newer.  This package states ONE supported
release, ``SUPPORTED_JAX_VERSION`` — the one it is built, tested and run
on the chip against: an older JAX is an error, a newer one a warning.

``MPI4JAX_TPU_NO_WARN_JAX_VERSION=1`` silences the warning
(ref jax_compat.py:35-36 ``MPI4JAX_NO_WARN_JAX_VERSION``).

The rest of the reference module — ``custom_call`` shims, ``ShapedArray``
import paths, effect allow-list registration (ref jax_compat.py:51-120) —
has no analog here: there are no custom calls and no manually-registered
effects.  ``axis_bound`` is this framework's one internals shim: the
"am I inside a shard_map over this axis?" probe, with a public-behavior
fallback, pinned by tests/test_comm_infra.py.
"""

import warnings

from .config import parse_env_bool


def axis_bound(axis_name: str) -> bool:
    """True iff ``axis_name`` is bound in the current trace's axis
    environment (i.e. we are inside a ``shard_map``/``pmap`` body over it).

    Primary probe: ``jax._src.core.get_axis_env().axis_exists`` — explicit,
    but a private module path.  Fallback if that moves in a future JAX:
    call ``lax.axis_size`` and catch the documented unbound-axis ``NameError``
    ("unbound axis name: ...").  Both behaviors are pinned by
    tests/test_comm_infra.py::test_axis_bound_probe so a JAX upgrade that
    changes either fails loudly instead of silently rerouting every
    in-region op through the eager path.
    """
    try:
        from jax._src.core import get_axis_env

        return bool(get_axis_env().axis_exists(axis_name))
    except (ImportError, AttributeError):
        pass
    from jax import lax

    try:
        lax.axis_size(axis_name)
        return True
    except NameError:
        return False


def tracer_is_live(tracer) -> bool:
    """True iff ``tracer`` belongs to a trace that is still active (the
    ambient trace or one of its parents) — i.e. using it now is legal.

    Used by the eager deferred send/recv pairing (ops/recv.py) to convert
    a dead queued payload into a clear staleness error *before* JAX's own
    leak detection produces an opaque UnexpectedTracerError at a much later
    point (outer-jit argument checking).  Probe: walk ``parent_trace`` from
    ``jax._src.core.trace_ctx.trace``; if the internals move in a future
    JAX, fall back to "assume live" — the recv-side UnexpectedTracerError
    backstop still fires, just less prettily.  Pinned by
    tests/test_send_recv.py::test_eager_send_traced_then_recv_outside_raises_clearly.
    """
    try:
        from jax._src.core import trace_ctx

        target = tracer._trace
        cur = trace_ctx.trace
    except (ImportError, AttributeError):
        return True
    seen = set()
    while cur is not None and id(cur) not in seen:
        if cur is target:
            return True
        seen.add(id(cur))
        cur = getattr(cur, "parent_trace", None)
    return False


# the JAX release this package is written for and validated against
SUPPORTED_JAX_VERSION = "0.9.0"


def versiontuple(v: str):
    """'0.9.0' -> (0, 9, 0); tolerates dev/rc suffixes
    (ref jax_compat.py:11-21)."""
    parts = []
    for p in v.split("."):
        digits = ""
        for ch in p:
            if ch.isdigit():
                digits += ch
            else:
                break
        parts.append(int(digits) if digits else 0)
    return tuple(parts[:3])


def check_jax_version(jax_version: str = None) -> None:
    """Warn/raise on unvalidated JAX versions (ref jax_compat.py:24-47)."""
    if jax_version is None:
        import jax

        jax_version = jax.__version__

    if versiontuple(jax_version) < versiontuple(SUPPORTED_JAX_VERSION):
        raise RuntimeError(
            f"mpi4jax_tpu requires jax>={SUPPORTED_JAX_VERSION} (found "
            f"{jax_version}): the package is written against that "
            "release's shard_map, VMA typing, AOT and Pallas surfaces."
        )

    if versiontuple(jax_version) > versiontuple(SUPPORTED_JAX_VERSION):
        if parse_env_bool("MPI4JAX_TPU_NO_WARN_JAX_VERSION", False):
            return
        warnings.warn(
            f"The latest supported JAX version with this release of "
            f"mpi4jax_tpu is {SUPPORTED_JAX_VERSION} (found {jax_version}). "
            "If you encounter problems, consider pinning "
            f"jax=={SUPPORTED_JAX_VERSION}. Set "
            "MPI4JAX_TPU_NO_WARN_JAX_VERSION=1 to silence this warning.",
            UserWarning,
            stacklevel=3,
        )
