"""Profiler integration: device time per op, host spans of the call path.

SURVEY.md §5 "Tracing / profiling": the reference's measured per-op latency
lives in host-side ``perf_counter`` brackets inside libmpi calls (ref
mpi_xla_bridge.pyx:47-60, 100-112) — a structure TPU collectives don't
have (no host call per collective; XLA schedules them asynchronously on
the device stream).  The native host-hooks path (``MPI4JAX_TPU_TRACE``,
mpi4jax_tpu/native.py) reproduces the reference's measured brackets on the
CPU backend; on TPU the measured source is the device profiler.

What carries a name there (seen on the chip in PR 26, PERF.md section 7):
every op has been wrapped in ``jax.named_scope("mpi4jax_tpu.<op>")``
(utils/debug.py) since before the chip, and the hand-built algorithms add
one scope per phase under it (ops/_algos.py).  A scope is HLO metadata: a
device event as ``jax.profiler.ProfileData`` gives it carries the scope
neither in its name (the HLO instruction's text, ``%psum_invariant.9 = ...
all-reduce(...)``) nor in any stat, so a reader of event names cannot see
it.  A Pallas kernel's ``name=`` does reach the event's name
(``%sw_steps_x2.1 = ... custom-call(...)``), and a module is named after
its function (``jit_<fn_name>(<hash>)``) on the device plane's ``XLA
Modules`` line.

``profile_ops`` packages the correct capture protocol: the one pitfall is
async dispatch — a jitted call returns before the device work runs, so a
naive ``with jax.profiler.trace(...)`` can close the trace with nothing in
it.  The context manager blocks on every live array before closing, which
fences all outstanding device work into the captured window.

``span`` is the one host-span primitive of the call path (a call of a
pinned program, of an ``mpx.spmd`` function, of an eager op; a pin).  It is
on while a profiler session runs and is one flag test otherwise: see
docs/observability.md "Host spans of the call path".
"""

import contextlib
import itertools
import os
import threading
import time

import jax

__all__ = ["profile_ops", "ProfileSummary", "span", "spans", "clear_spans",
           "spans_dropped", "tracing"]


# ---------------------------------------------------------------------------
# host spans of the call path
# ---------------------------------------------------------------------------

SPAN_CAP = 1 << 16  # records kept per session; beyond it, dropped and counted

_session_running = jax.profiler.TraceAnnotation.is_enabled
_ids = itertools.count(1)
_open = threading.local()  # .stack: the spans open on this thread
_lock = threading.Lock()  # the buffer's restart and its count of drops


class _Buffer:
    """The records of the newest profiler session."""

    __slots__ = ("records", "dropped", "live")

    def __init__(self):
        self.records = []
        self.dropped = 0
        self.live = False  # did the last span find a session running?

    def clear(self):
        self.records = []
        self.dropped = 0


_buffer = _Buffer()


# what ``span`` hands out while no session runs: nothing is made, nothing
# is read from a clock
_OFF = contextlib.nullcontext()


class _Span:
    """A span while a session runs.  ``start_ns`` is read before anything
    is made and ``end_ns`` after everything is closed, so the record
    encloses its own annotation and bookkeeping: a parent's self time
    holds what its children's spans cost, and a span that is the first
    thing in a caller's own annotation starts a constant after it."""

    __slots__ = ("record", "annotation")

    def __init__(self, name, attrs):
        start_ns = time.time_ns()
        self.record = {"name": name, "start_ns": start_ns, "end_ns": 0,
                       "id": next(_ids), "parent": None, "call": 0,
                       "attrs": attrs}
        self.annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self):
        if not _buffer.live:  # the first span of a session starts it afresh
            with _lock:
                if not _buffer.live:
                    _buffer.clear()
                    _buffer.live = True
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        record = self.record
        if stack:
            record["parent"] = stack[-1]["id"]
            record["call"] = stack[-1]["call"]
        else:
            record["call"] = record["id"]
        stack.append(record)
        self.annotation.__enter__()
        return record

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        _open.stack.pop()
        record = self.record
        record["end_ns"] = time.time_ns()
        if len(_buffer.records) < SPAN_CAP:
            _buffer.records.append(record)
        else:
            with _lock:
                _buffer.dropped += 1
        return False


def tracing() -> bool:
    """Is a profiler session running?  "Tracing on" means nothing else.
    While none runs, this is all a call path pays."""
    if _session_running():
        return True
    _buffer.live = False
    return False


def span(name: str, **attrs):
    """A host span of the call path: ``with span("mpx.call", program=...)``.

    While a profiler session runs (``jax.profiler.start_trace``,
    ``jax.profiler.trace``, :func:`profile_ops`) the span is a
    ``jax.profiler.TraceAnnotation`` — it lands in the profiler's host
    plane, on the clock of the device operations — and one record in an
    in-memory buffer read by :func:`spans`: ``name``, ``start_ns``,
    ``end_ns`` (``time.time_ns()``, read round the annotation; the trace
    file counts from the session's start, one constant away), ``id``, ``parent`` (the id of the
    span open around it on this thread, else ``None``), ``call`` (the id
    of the outermost span open around it: one identifier for all spans of
    one call of a program) and ``attrs``.  The buffer holds the newest
    session only, at most ``SPAN_CAP`` records of it (:func:`spans_dropped`
    counts the rest).

    With no session running this is one flag test (:func:`tracing`): no
    annotation, no record, no timestamp.
    """
    if not tracing():
        return _OFF
    return _Span(name, attrs)


def spans() -> list:
    """The records of the newest profiler session, in order of their end
    (a child before its parent).  Copies: the caller may keep them."""
    return [dict(r) for r in _buffer.records]


def spans_dropped() -> int:
    """How many spans of the newest session found the buffer full."""
    return _buffer.dropped


def clear_spans() -> None:
    _buffer.clear()


# ---------------------------------------------------------------------------
# the capture protocol
# ---------------------------------------------------------------------------


class ProfileSummary:
    """What a ``profile_ops`` capture did: the trace directory, how many
    live arrays the exit fence blocked on (``None`` until the context
    exits) and the host spans of the call path recorded inside the block
    (``spans``, see :func:`span`; empty until the context exits).  A zero
    ``fenced_arrays`` is the tell that the profiled block dropped its
    outputs on the floor — the work may have landed outside the capture
    window (see ``profile_ops``)."""

    __slots__ = ("trace_dir", "backend", "fenced_arrays", "spans")

    def __init__(self, trace_dir: str, backend: str):
        self.trace_dir = trace_dir
        self.backend = backend
        self.fenced_arrays = None
        self.spans = []

    def __repr__(self):
        return (
            f"ProfileSummary(trace_dir={self.trace_dir!r}, "
            f"backend={self.backend!r}, "
            f"fenced_arrays={self.fenced_arrays}, "
            f"spans={len(self.spans)})"
        )


@contextlib.contextmanager
def profile_ops(logdir: str, *, create_perfetto_link: bool = False):
    """Capture a profiler trace of the enclosed ops, async-dispatch-safe.

    Usage::

        with mpx.profile_ops("/tmp/jax-trace") as prof:
            out = step(state)          # any program using mpi4jax_tpu ops
        # prof.fenced_arrays: how many live arrays the exit fence covered

    On exit, outstanding device work is fenced into the trace
    (``jax.block_until_ready`` over every live array on the DEFAULT
    backend — not every backend: a CPU-backed sidecar array, e.g. a
    host-staged checkpoint shard, must not stall the close of a TPU
    capture), then the trace is closed.  Yields a :class:`ProfileSummary`
    whose ``fenced_arrays`` count is filled in by the fence, so callers
    (and tests) can assert the fence actually ran.  The fence covers
    everything whose output is still referenced — BIND the results you
    are profiling (``out = step(state)``, as above); a call whose outputs
    you drop on the floor has nothing live to fence and may land outside
    the window (``jax.block_until_ready(step(state))`` inside the block
    is the explicit form).  Open the directory in TensorBoard/xprof and
    filter for ``mpi4jax_tpu.<op>`` to read each collective's device
    time, queue time, and overlap with compute — measured on the real
    stream, including any fusion/reordering XLA applied (docs/usage.md
    "Observability", docs/observability.md).  ``prof.spans`` holds the
    host spans of the call path made inside the block (:func:`span`):
    what the host did between a call and the device's first operation.
    """
    os.makedirs(logdir, exist_ok=True)
    backend = jax.default_backend()
    summary = ProfileSummary(logdir, backend)
    clear_spans()
    with jax.profiler.trace(logdir, create_perfetto_link=create_perfetto_link):
        try:
            yield summary
        finally:
            # fence: async dispatch means enclosed calls may not have
            # executed yet; blocking on live arrays lands their device work
            # inside the trace window.  In a finally so the fence also runs
            # when the profiled block raises — work dispatched before the
            # exception would otherwise land outside the window and the
            # partial trace would silently under-report.
            fenced = jax.live_arrays(backend)
            summary.fenced_arrays = len(fenced)
            jax.block_until_ready(fenced)
            summary.spans = spans()
