"""Native host-hooks: loading, registration, and jit-visible wrappers.

The reference's native layer (Cython XLA custom-call bridge,
ref mpi4jax/_src/xla_bridge/*.pyx) *is* the transport; here the transport is
XLA collective HLO, and the native library (csrc/host_hooks.cc) instead
provides the host-side runtime services around it:

- ``op_begin``/``op_end`` — per-op runtime logging and wall-clock latency in
  the reference's debug format (ref mpi_xla_bridge.pyx:47-60, 100-112),
  threaded into the program with data dependencies so the host timestamps
  bracket the collective's execution;
- ``abort_if`` — data-dependent fail-fast (MPI_Abort-on-error semantics,
  ref mpi_xla_bridge.pyx:67-91): if the predicate is true at run time the
  whole process dies, not just the computation;
- ``wallclock`` — host timestamp as an in-graph value;
- ``watchdog_arm``/``watchdog_disarm`` — the collective watchdog's in-graph
  bracket (resilience/watchdog.py): registry and monitor thread live in C++
  so the timeout fires even when every Python thread is wedged.

All hooks are CPU-backend custom calls (the test/dev backend).  On TPU the
compute path has no host hooks by design — ``runtime_tracing_supported()``
reports availability, and the pure-Python fallbacks (``jax.debug.callback``)
cover platforms without the native library.

Build the library with ``python -m mpi4jax_tpu.native build``.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_LIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_lib")
_LIB_PATH = os.path.join(_LIB_DIR, "libmpx_hooks.so")

_lib: Optional[ctypes.CDLL] = None
_registered = False

_HANDLERS = ("MpxOpBegin", "MpxOpEnd", "MpxAbortIf", "MpxWallclock",
             "MpxWatchdogArm", "MpxWatchdogDisarm")
_TARGETS = ("mpx_op_begin", "mpx_op_end", "mpx_abort_if", "mpx_wallclock",
            "mpx_watchdog_arm", "mpx_watchdog_disarm")


def build(verbose: bool = True) -> str:
    """Compile csrc/host_hooks.cc → mpi4jax_tpu/_lib/libmpx_hooks.so.

    Direct g++ invocation (no build system needed); csrc/CMakeLists.txt
    offers the same build for CMake users.
    """
    src = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "csrc", "host_hooks.cc"
    )
    os.makedirs(_LIB_DIR, exist_ok=True)
    cmd = [
        "g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
        f"-I{jax.ffi.include_dir()}",
        os.path.abspath(src), "-o", _LIB_PATH,
    ]
    if verbose:
        print(" ".join(cmd))
    subprocess.run(cmd, check=True)
    return _LIB_PATH


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _registered
    if _lib is not None:
        return _lib
    if not os.path.exists(_LIB_PATH):
        return None
    _lib = ctypes.CDLL(_LIB_PATH)
    if not _registered:
        # the library is built from csrc/host_hooks.cc of this checkout
        # (build()); one that lacks a handler is a broken build, and the
        # AttributeError says which
        for handler, target in zip(_HANDLERS, _TARGETS):
            jax.ffi.register_ffi_target(
                target, jax.ffi.pycapsule(getattr(_lib, handler)),
                platform="cpu",
            )
        _registered = True
    return _lib


def available() -> bool:
    """True if the native hooks library is built and loadable."""
    return _load() is not None


def runtime_tracing_supported() -> bool:
    """Native runtime op tracing runs on the CPU backend only (on TPU the
    compute path is pure HLO with no host hooks, by design)."""
    return available() and jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# jit-visible wrappers
# ---------------------------------------------------------------------------


def _tie(x, dep):
    """Make ``x`` depend on ``dep`` (ordering via OptimizationBarrier)."""
    x, _ = lax.optimization_barrier((x, dep))
    return x


def op_begin(opname: str, call_id: str, rank, detail: str = ""):
    """Log op entry on the host; returns a u32 the collective's inputs
    should be tied to (so the timestamp precedes the collective)."""
    call = jax.ffi.ffi_call(
        "mpx_op_begin",
        jax.ShapeDtypeStruct((), jnp.uint32),
        has_side_effect=True,
    )
    return call(
        jnp.asarray(rank, jnp.uint32), opname=opname, call_id=call_id, detail=detail
    )


def op_end(opname: str, call_id: str, rank, dep):
    """Log op completion + elapsed; ``dep`` ties the call after the
    collective's outputs."""
    call = jax.ffi.ffi_call(
        "mpx_op_end",
        jax.ShapeDtypeStruct((), jnp.uint32),
        has_side_effect=True,
    )
    return call(_tie(jnp.asarray(rank, jnp.uint32), dep),
                opname=opname, call_id=call_id)


def abort_if(pred, rank, message: str):
    """Kill the process if ``pred`` is true at run time (fail-fast,
    ref mpi_xla_bridge.pyx:67-91 ``abort_on_error``).

    Falls back to ``jax.debug.callback`` + ``os.abort`` off-CPU or without
    the native library.  Returns a u32 to thread into downstream values if
    the caller wants the check ordered before them.
    """
    pred = jnp.asarray(pred).astype(jnp.uint32).reshape(())
    rank = jnp.asarray(rank, jnp.uint32)
    if runtime_tracing_supported():
        call = jax.ffi.ffi_call(
            "mpx_abort_if",
            jax.ShapeDtypeStruct((), jnp.uint32),
            has_side_effect=True,
        )
        return call(pred, rank, message=message)

    def _cb(p, r):
        if p:
            # a tripped guard is about to kill the process: record it as
            # a telemetry incident first (meter + flushed events-tier
            # journal instant) so the post-mortem timeline shows WHERE
            # the job died, not just that it died
            try:
                from .telemetry import journal as _tjournal

                _tjournal.incident("numeric_guard.trips",
                                   "numeric_guard_trip", r, message)
            except Exception:
                pass
            host_fatal(r, message)

    jax.debug.callback(_cb, pred, rank, ordered=False)
    return pred


# ---------------------------------------------------------------------------
# collective watchdog hooks (resilience/watchdog.py)
# ---------------------------------------------------------------------------


def host_line(rank, text: str) -> None:
    """Host-side diagnostic line in the runtime-log format (``r{rank} | ...``).

    Plain Python (not in-graph): used by host-side monitors (the watchdog's
    Python-fallback thread) that speak outside any traced program.
    """
    print(f"r{int(rank)} | {text}", file=sys.stderr, flush=True)


def host_fatal(rank, text: str) -> None:
    """Host-side fail-fast: print in ``abort_if``'s FATAL format and kill the
    process (the watchdog's fallback death path — same loud exit as the
    native ``MpxAbortIf`` hook)."""
    print(f"r{int(rank)} | FATAL: {text}", file=sys.stderr, flush=True)
    os.abort()


def watchdog_supported() -> bool:
    """True when the C++ watchdog registry/monitor can back the collective
    watchdog (native library built with the watchdog hooks, CPU backend —
    same availability rule as the runtime trace hooks)."""
    return runtime_tracing_supported()


def watchdog_arm(opname: str, call_id: str, rank, axes: str, timeout: float):
    """Register one in-flight collective with the C++ watchdog; returns a u32
    the op's inputs are computed from (``watchdog.after_arm``), so arming
    precedes the collective."""
    call = jax.ffi.ffi_call(
        "mpx_watchdog_arm",
        jax.ShapeDtypeStruct((), jnp.uint32),
        has_side_effect=True,
    )
    import numpy as np

    return call(
        jnp.asarray(rank, jnp.uint32),
        opname=opname, call_id=call_id, axes=axes,
        timeout=np.float64(timeout),
    )


def watchdog_disarm(call_id: str, rank, dep):
    """Deregister after the collective: ``dep`` (an element of the op's
    first output) is a second operand the handler ignores, which orders the
    call after completion."""
    call = jax.ffi.ffi_call(
        "mpx_watchdog_disarm",
        jax.ShapeDtypeStruct((), jnp.uint32),
        has_side_effect=True,
    )
    return call(jnp.asarray(rank, jnp.uint32), dep, call_id=call_id)


# Base timestamp for the pure-Python fallback, captured at first use.  Raw
# clock values are seconds since boot/epoch, where f32 ULP is milliseconds
# (or worse); subtracting a process-local base before any f32 downcast
# keeps sub-microsecond resolution for hours of runtime.  The FFI path's
# base lives inside the C++ hook (host_hooks.cc WallclockImpl) for the
# same reason.
_py_wallclock_base: Optional[float] = None


def host_clock():
    """Host-side ``(mono, wall)`` clock pair for the telemetry journal
    (telemetry/journal.py): ``mono`` is monotonic seconds on the SAME
    process base as the pure-Python ``wallclock`` fallback, so journal
    timestamps are directly comparable with in-graph ``wallclock()``
    values; ``wall`` is ``time.time()``, the cross-process alignment
    clock the merge CLI lays timelines out on."""
    import time

    global _py_wallclock_base
    if _py_wallclock_base is None:
        _py_wallclock_base = time.perf_counter()
    return time.perf_counter() - _py_wallclock_base, time.time()


def wallclock(dep=None):
    """Host wall-clock timestamp as an in-graph value, ordered after
    ``dep``: seconds since the process's first ``wallclock`` use.

    Returns f64 when ``jax_enable_x64`` is on, else f32 — on both the FFI
    path and the pure-Python fallback, so the API is consistent across
    platforms (with x64 disabled, callback ``result_shape_dtypes`` reject
    64-bit types outright).  Only differences of ``wallclock`` values are
    meaningful."""
    tok = jnp.zeros((), jnp.uint32) if dep is None else _tie(
        jnp.zeros((), jnp.uint32), dep
    )
    out_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    if runtime_tracing_supported():
        call = jax.ffi.ffi_call(
            "mpx_wallclock",
            jax.ShapeDtypeStruct((), jnp.float64),
            has_side_effect=True,
        )
        return call(tok).astype(out_dtype)
    import time

    import numpy as np

    from jax.experimental import io_callback

    global _py_wallclock_base
    if _py_wallclock_base is None:
        _py_wallclock_base = time.perf_counter()
    base = _py_wallclock_base

    def _now(_):
        # io_callback (ordered) rather than pure_callback: two wallclock
        # reads in one jit are byte-identical subgraphs a pure callback
        # could legally dedupe into a single host call
        return np.asarray(time.perf_counter() - base, out_dtype)

    return io_callback(
        _now, jax.ShapeDtypeStruct((), out_dtype), tok, ordered=True
    )


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    if argv[:1] == ["build"]:
        path = build()
        print(f"built {path}")
    else:
        print("usage: python -m mpi4jax_tpu.native build", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
