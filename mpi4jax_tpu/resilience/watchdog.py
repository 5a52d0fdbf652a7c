"""Collective watchdog: turn silent hangs into loud, diagnosable deaths.

In the SPMD multi-host model a single dead or stalled process leaves every
surviving process blocked *forever* inside its next collective — the classic
silent failure mode of production TPU training stacks (the job holds its
slice, burns no steps, and pages nobody).  The watchdog arms a host-side
monitor around each op's begin/end bracket (the same data-dependency
threading as the ``op_begin``/``op_end`` trace hooks, ops/_base.py
``_run_body``); when any collective stays in flight longer than
``MPI4JAX_TPU_WATCHDOG_TIMEOUT`` seconds, it dumps every in-flight op on this
process (op name, call id, comm axes, elapsed) and kills the process through
the ``abort_if`` fail-fast path, so the scheduler can reschedule instead of
the job hanging.

Two implementations, chosen per availability:

- **native** (csrc/host_hooks.cc ``MpxWatchdogArm``/``MpxWatchdogDisarm``):
  registry and monitor thread live in C++ — they keep running even if every
  Python thread is wedged (e.g. the GIL is held by a stuck extension call);
  CPU backend with the hooks library built.
- **fallback** (this module): an ``io_callback`` pair updating a Python
  registry, watched by a daemon thread.  Collectives block with the GIL
  released, so the thread fires reliably in practice; works on any backend.

Arm, collective and disarm are ordered by real data dependence: the op's
inputs are computed from the arm's output (``after_arm``) and the disarm
takes an element of the op's first output as an operand, so a disarm can
run neither before its collective nor before its arm.  (An
``optimization_barrier`` tie, which the trace hooks use, is not enough
here: XLA:CPU expands barriers away before it schedules, and inside a
``fori_loop`` body it then ran every disarm ahead of its arm — an arm left
in flight kills a healthy process ``timeout`` seconds later.)  The elapsed
time the diagnostics report is the collective's in-flight time on this
host.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional

__all__ = [
    "arm_in_graph",
    "after_arm",
    "disarm_in_graph",
    "inflight_snapshot",
    "registry_empty",
    "set_on_timeout",
    "drain_registry",
    "suspend_expiries",
]

_POLL_INTERVAL = 0.1

# nesting depth of suspend_expiries() windows: while > 0 the monitor
# keeps tracking in-flight ops but treats none as expired.  Planned
# elastic reconfigurations (grow admission, graceful drain) hold the
# window open across their re-bootstrap + restore exchange — seconds of
# legitimate cross-rank skew that must not read as a hang.
_suspend_lock = threading.Lock()
_suspended = 0


class suspend_expiries:
    """Context manager: no watchdog expiry fires while any window is
    open (arms and disarms still track normally, so coverage resumes the
    moment the window closes)."""

    def __enter__(self):
        global _suspended
        with _suspend_lock:
            _suspended += 1
        return self

    def __exit__(self, *exc):
        global _suspended
        with _suspend_lock:
            _suspended = max(0, _suspended - 1)
        return False


def expiries_suspended() -> bool:
    with _suspend_lock:
        return _suspended > 0


def _telemetry_incident(meter_name, name, rank, detail=""):
    """Mirror a watchdog lifecycle event into the telemetry layer via the
    shared incident helper.  Guarded: the telemetry package is optional
    under the isolated test loader."""
    try:
        from ..telemetry import journal
    except ImportError:
        return
    journal.incident(meter_name, name, rank, detail)


def _default_on_timeout(entries, expired):
    """Dump per-rank in-flight diagnostics, then die via the abort path."""
    from .. import native

    for e in entries:
        native.host_line(
            e["rank"],
            f"WATCHDOG | in-flight: {e['opname']} (call {e['call_id']}, "
            f"axes={e['axes']}, elapsed {e['elapsed']:.2f}s)",
        )
    native.host_fatal(
        expired["rank"],
        f"collective watchdog: {expired['opname']} exceeded "
        f"{expired['timeout']:g}s (call {expired['call_id']}, "
        f"axes={expired['axes']})",
    )


class _Registry:
    """In-flight op registry + monitor thread (the Python fallback path).

    Keys are ``(call_id, rank)`` with a FIFO of start times per key — a trace
    site inside ``lax.fori_loop`` fires once per iteration with the same call
    id, and the data dependencies order iteration N+1's arm after iteration
    N's collective but not after N's disarm (the same aliasing the native
    trace hooks handle, csrc/host_hooks.cc ``begin_times``).
    A disarm that finds no entry (its arm was drained by an epoch
    revocation or a claimed expiry) is dropped.
    """

    def __init__(self, on_timeout: Optional[Callable] = None,
                 clock=time.monotonic):
        self.lock = threading.Lock()
        self.entries = {}  # (call_id, rank) -> deque of (opname, axes, start, timeout)
        self.clock = clock
        self.on_timeout = on_timeout or _default_on_timeout
        self._thread = None

    def arm(self, opname: str, call_id: str, rank: int, axes: str,
            timeout: float) -> None:
        with self.lock:
            self.entries.setdefault((call_id, int(rank)), deque()).append(
                (opname, axes, self.clock(), float(timeout))
            )
            self._ensure_thread_locked()

    def disarm(self, call_id: str, rank: int) -> None:
        key = (call_id, int(rank))
        with self.lock:
            dq = self.entries.get(key)
            if dq:
                dq.popleft()
                if not dq:
                    del self.entries[key]

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._monitor, name="mpi4jax_tpu-watchdog", daemon=True
            )
            self._thread.start()

    def snapshot(self):
        """Diagnostic view of every in-flight op: list of dicts with opname,
        call_id, rank, axes, elapsed, timeout."""
        now = self.clock()
        with self.lock:
            return [
                {
                    "opname": opname, "call_id": call_id, "rank": rank,
                    "axes": axes, "elapsed": now - start, "timeout": timeout,
                }
                for (call_id, rank), dq in self.entries.items()
                for (opname, axes, start, timeout) in dq
            ]

    def check_expired(self):
        """One monitor scan; returns the expired snapshot entry or None
        (always None inside a ``suspend_expiries`` window — planned
        elastic reconfiguration, not a hang)."""
        if expiries_suspended():
            return None
        for e in self.snapshot():
            if e["elapsed"] > e["timeout"]:
                return e
        return None

    def empty(self) -> bool:
        with self.lock:
            return not self.entries

    def drain(self) -> int:
        """Forget every in-flight entry (epoch revocation: arms from
        collectives of a revoked world must not fire into the recovered
        job).  Returns the number of entries dropped."""
        with self.lock:
            n = sum(len(dq) for dq in self.entries.values())
            self.entries.clear()
        return n

    def drain_expired(self) -> int:
        """Forget only the entries whose timeout has elapsed (a claimed
        expiry): un-expired arms of unrelated concurrent collectives keep
        their coverage.  Returns the number of entries dropped."""
        now = self.clock()
        dropped = 0
        with self.lock:
            for key in list(self.entries):
                dq = self.entries[key]
                kept = deque(e for e in dq if now - e[2] <= e[3])
                dropped += len(dq) - len(kept)
                if kept:
                    self.entries[key] = kept
                else:
                    del self.entries[key]
        return dropped

    def _monitor(self) -> None:
        while True:
            time.sleep(_POLL_INTERVAL)
            expired = self.check_expired()
            if expired is not None:
                # the incident is journalled HERE, before the handler
                # runs: a handler that recovers (or kills) the process
                # must not be able to lose the expiry record, and a
                # replacement handler need not re-implement it
                _telemetry_incident(
                    "watchdog.expiries", "watchdog_expired",
                    expired["rank"],
                    f"{expired['opname']} call {expired['call_id']} "
                    f"exceeded {expired['timeout']:g}s",
                )
                # health-plane stall hook (telemetry/health.py): journal
                # the stall incident and write the postmortem bundle
                # while the in-flight registry + flight ring still show
                # the stuck op — also before the handler can abort
                try:
                    from ..telemetry import health as _health
                except ImportError:
                    pass
                else:
                    try:
                        _health.on_watchdog_expiry(expired)
                    except Exception:
                        pass
                self.on_timeout(self.snapshot(), expired)
                # only reachable with a non-fatal handler (the default
                # aborts the process): drop the EXPIRED entries — healthy
                # concurrent arms keep their coverage — and keep
                # monitoring; the handler's recovery (e.g. an elastic
                # shrink, which drains everything via revoke_epoch)
                # re-arms collectives of the NEW epoch under fresh entries
                self.drain_expired()


_registry = _Registry()


def registry_empty() -> bool:
    """True when no op is in flight in the Python-fallback registry."""
    return _registry.empty()


def inflight_snapshot():
    """Current in-flight ops in the Python-fallback registry (diagnostics)."""
    return _registry.snapshot()


# when True, arm/disarm skip the native C++ registry even where it is
# available: the C++ monitor always kills the process on expiry (its
# handler is not pluggable from Python), so a claimed recovery handler
# (elastic.run) needs the Python-fallback monitor to be the one watching
_force_fallback = False


def force_python_fallback(enable: bool) -> None:
    """Route watchdog arm/disarm through the Python-fallback registry
    even where the native C++ monitor is built.  Elastic recovery sets
    this for the duration of ``elastic.run`` (the native monitor cannot
    hand expiries to a Python handler); also useful in tests."""
    global _force_fallback
    _force_fallback = bool(enable)
    # arm sites are baked into traced programs per implementation: retrace
    from ..utils import config

    config.bump_config_epoch()


def native_active() -> bool:
    """Whether arm/disarm currently use the native C++ registry."""
    from .. import native

    return native.watchdog_supported() and not _force_fallback


def set_on_timeout(handler: Optional[Callable]) -> None:
    """Replace the expiry handler of the LIVE Python-fallback monitor at
    runtime (``None`` restores the default dump-and-die handler).

    ``handler(entries, expired)`` receives the full in-flight snapshot
    plus the expired entry, after the expiry was journalled as a
    telemetry incident.  A handler that returns (instead of killing the
    process) keeps the monitor alive: the expired entries are drained and
    monitoring continues — the hook elastic recovery
    (``resilience/elastic.py``) claims expiries through, without
    recreating the registry.  Only the Python-fallback monitor is
    pluggable; the native C++ monitor always dies loudly (its registry is
    not visible from Python), so elastic drills force the fallback.
    """
    _registry.on_timeout = handler or _default_on_timeout


def drain_registry() -> int:
    """Drop every in-flight entry of the Python-fallback registry (epoch
    revocation / test isolation); returns the count dropped."""
    return _registry.drain()


# ---------------------------------------------------------------------------
# in-graph arm/disarm
# ---------------------------------------------------------------------------


def _io_callback(fn, *operands):
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    return io_callback(
        fn, jax.ShapeDtypeStruct((), jnp.uint32), *operands, ordered=False
    )


# the arm returns its rank, which is never this value
_NO_RANK = 0xFFFFFFFF


def arm_in_graph(mpi_name: str, call_id: str, comm, rank, timeout: float):
    """Arm the watchdog for one collective; returns a u32 to pass, with
    each of the op's inputs, through ``after_arm`` (so arming precedes
    the collective's execution)."""
    from .. import native

    # metered HERE — the shared entry of both implementations — so the
    # native C++ path counts too (trace-time semantics: one per armed
    # collective site; the C++ registry's run-time arms are not visible
    # from Python)
    try:
        from ..telemetry import core as _tcore
    except ImportError:
        pass
    else:
        _tcore.meter("watchdog.arms")
    axes = repr(comm.axes)
    if native.watchdog_supported() and not _force_fallback:
        return native.watchdog_arm(mpi_name, call_id, rank, axes, timeout)

    import numpy as np

    def _arm(r):
        _registry.arm(mpi_name, call_id, int(r), axes, timeout)
        return np.uint32(r)

    import jax.numpy as jnp

    return _io_callback(_arm, jnp.asarray(rank, jnp.uint32))


def after_arm(x, armed):
    """``x``, bit for bit, as a function of the arm's output ``armed``: an
    elementwise select on a condition the compiler cannot decide (the
    arm's opaque result against a value it never returns), so whatever
    consumes ``x`` cannot be scheduled before the arm has run."""
    import jax.numpy as jnp

    x = jnp.asarray(x)
    return jnp.where(armed == jnp.uint32(_NO_RANK), jnp.zeros_like(x), x)


def _anchor(dep):
    """One element of ``dep`` (none when it is empty): a real operand that
    orders a callback after ``dep`` was computed, small enough to ship to
    the host."""
    import jax.numpy as jnp

    return jnp.ravel(dep)[:1]


def disarm_in_graph(mpi_name: str, call_id: str, comm, rank, dep):
    """Disarm after the collective: an element of ``dep`` (the op's first
    output) is an operand of the callback, which orders it after
    completion."""
    from .. import native

    if native.watchdog_supported() and not _force_fallback:
        return native.watchdog_disarm(call_id, rank, _anchor(dep))

    import numpy as np

    def _disarm(r, _dep):
        _registry.disarm(call_id, int(r))
        return np.uint32(r)

    import jax.numpy as jnp

    return _io_callback(_disarm, jnp.asarray(rank, jnp.uint32), _anchor(dep))
