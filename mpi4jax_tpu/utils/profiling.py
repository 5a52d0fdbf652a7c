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
pinned program, of an ``mpx.spmd`` function, of an eager op) and of the
build path (a pin; the first call of a region's or an eager op's new
program).  A call is frequent and fast: its spans are on while a profiler
session runs and one flag test otherwise.  A build is rare and slow (a
handful a process, 0.4 to 100 s each): its spans are *kept* — recorded with
or without a session, with what jax itself says of where the executable
came from (:func:`builds`).  See docs/observability.md "Host spans of the
call path".
"""

import collections
import contextlib
import itertools
import os
import threading
import time

import jax

__all__ = ["profile_ops", "ProfileSummary", "span", "spans", "clear_spans",
           "spans_dropped", "tracing", "builds", "builds_dropped",
           "build_counts", "clear_builds", "account"]


# ---------------------------------------------------------------------------
# host spans of the call path
# ---------------------------------------------------------------------------

SPAN_CAP = 1 << 16  # records kept per session; beyond it, dropped and counted
BUILD_CAP = 1 << 12  # kept records per process; beyond it, dropped and counted

_session_running = jax.profiler.TraceAnnotation.is_enabled
_ids = itertools.count(1)
# .stack: the records of the spans open on this thread; .kept: the kept
# spans among them
_open = threading.local()
_lock = threading.Lock()  # a buffer's restart, its count of drops, the counts


class _Buffer:
    """The records of the newest profiler session (``_buffer``), or the
    kept records of the process (``_builds``)."""

    __slots__ = ("records", "dropped", "live")

    def __init__(self):
        self.records = []
        self.dropped = 0
        self.live = False  # did the last span find a session running?

    def clear(self):
        self.records = []
        self.dropped = 0


_buffer = _Buffer()
_builds = _Buffer()
# builds closed, by ``kind`` and by ``origin``: cache_stats()["builds"]
_build_counts = {"by_kind": collections.Counter(),
                 "by_origin": collections.Counter()}


# what ``span`` hands out while no session runs: nothing is made, nothing
# is read from a clock
_OFF = contextlib.nullcontext()


class _Span:
    """A span that records: while a session runs (``annotation``), or kept
    (``events``), or both.  ``start_ns`` is read before anything is made
    and ``end_ns`` after everything is closed, so the record encloses its
    own annotation and bookkeeping: a parent's self time holds what its
    children's spans cost, and a span that is the first thing in a
    caller's own annotation starts a constant after it."""

    __slots__ = ("record", "annotation", "events")

    def __init__(self, name, attrs, session, keep):
        start_ns = time.time_ns()
        self.record = {"name": name, "start_ns": start_ns, "end_ns": 0,
                       "id": next(_ids), "parent": None, "call": 0,
                       "attrs": attrs}
        self.annotation = (jax.profiler.TraceAnnotation(name, **attrs)
                           if session else None)
        # kept: jax's timed events under this span, innermost last, as
        # (start, seconds, key) — see ``account``
        self.events = [] if keep else None

    def __enter__(self):
        if self.annotation is not None and not _buffer.live:
            with _lock:  # the first span of a session starts it afresh
                if not _buffer.live:
                    _buffer.clear()
                    _buffer.live = True
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
            _open.kept = []
        record = self.record
        if stack:
            record["parent"] = stack[-1]["id"]
            record["call"] = stack[-1]["call"]
        else:
            record["call"] = record["id"]
        stack.append(record)
        if self.events is not None:
            kept = _open.kept
            record["attrs"]["build"] = (kept[0].record["id"] if kept
                                        else record["id"])
            kept.append(self)
        if self.annotation is not None:
            self.annotation.__enter__()
        return record

    def __exit__(self, *exc):
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        _open.stack.pop()
        record = self.record
        record["end_ns"] = time.time_ns()
        if self.annotation is not None:
            if len(_buffer.records) < SPAN_CAP:
                _buffer.records.append(record)
            else:
                with _lock:
                    _buffer.dropped += 1
        if self.events is not None:
            _close_kept(record)
        return False


# ---------------------------------------------------------------------------
# kept spans: the build path, and where its executables came from
# ---------------------------------------------------------------------------

# jax's own timed events of a build (jax/_src/dispatch.py,
# jax/_src/compiler.py), each with the attribute its seconds go to and the
# origin it tells of; none fires on a warm call
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace_s", None),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower_s", None),
    "/jax/core/compile/backend_compile_duration": ("compile_s", "compiled"),
    "/jax/compilation_cache/cache_retrieval_time_sec": ("fetch_s",
                                                        "jax_cache"),
}
_SECONDS = tuple(key for key, _ in _JAX_EVENTS.values())
_listening = False


def _listen():
    """Register the one listener, at the first kept span."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    def on_duration(event, seconds, **_kw):
        stage = _JAX_EVENTS.get(event)
        if stage is not None:
            account(stage[0], seconds, stage[1])

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def account(key: str, seconds: float, origin: str = None) -> None:
    """Add ``seconds`` of a build's stage that has just ended (``key``:
    ``trace_s``, ``lower_s``, ``compile_s`` or ``fetch_s``) to the
    innermost kept span open on this thread; nothing where none is open.

    Every second is counted once, under the innermost stage that held it:
    a stage that encloses stages already accounted (a trace inside which
    a helper was traced, or compiled) adds its own time only.  jax 0.9.0
    closes ``backend_compile_duration`` round a fetch from its persistent
    cache too (after ``cache_retrieval_time_sec``): such a "compile" is
    the fetch's, ``compile_s`` stays 0 and the origin ``"jax_cache"``.
    ``origin`` says where an executable came from: ``"compiled"`` wins
    over the others, which stay as first given."""
    kept = getattr(_open, "kept", None)
    if not kept:
        return
    span_ = kept[-1]
    start = time.monotonic() - seconds
    events, inside = span_.events, 0.0
    while events and events[-1][0] >= start:
        _, held, held_key = events.pop()
        inside += held
        if key == "compile_s" and held_key == "fetch_s":
            key, origin = "fetch_s", "jax_cache"
    events.append((start, seconds, key))
    attrs = span_.record["attrs"]
    attrs[key] = attrs.get(key, 0.0) + max(seconds - inside, 0.0)
    _set_origin(attrs, origin)


def _set_origin(attrs, origin):
    if origin is not None and (origin == "compiled" or "origin" not in attrs):
        attrs["origin"] = origin


def _close_kept(record):
    """A kept span has ended: what it holds goes to the kept span round
    it; the outermost one is a build, and is counted."""
    kept = _open.kept
    kept.pop()
    attrs = record["attrs"]
    if kept:
        outer = kept[-1].record["attrs"]
        for key in _SECONDS:
            if key in attrs:
                outer[key] = outer.get(key, 0.0) + attrs[key]
        _set_origin(outer, attrs.get("origin"))
    else:
        with _lock:
            _build_counts["by_kind"][attrs.get("kind", "other")] += 1
            _build_counts["by_origin"][attrs.get("origin", "memory")] += 1
    if len(_builds.records) < BUILD_CAP:
        _builds.records.append(record)
    else:
        with _lock:
            _builds.dropped += 1


def tracing() -> bool:
    """Is a profiler session running?  "Tracing on" means nothing else.
    While none runs, this is all a call path pays."""
    if _session_running():
        return True
    _buffer.live = False
    return False


def span(name: str, keep: bool = False, **attrs):
    """A host span: ``with span("mpx.call", program=...)`` on the call
    path, ``with span("mpx.pin", keep=True, program=...)`` on the build
    path.

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

    ``keep=True`` is for the build path (a pin, a new program's first
    call): the span records whether or not a session runs, into the
    process's own buffer, which no session's start clears (:func:`builds`;
    at most ``BUILD_CAP`` records, :func:`builds_dropped` counts the rest);
    while a session runs it is the annotation and the session's record
    too.  Its ``attrs`` gain ``build`` (the id of the outermost kept span
    open round it: its own id says it *is* the build) and what jax said
    under it (:func:`account`): ``trace_s``, ``lower_s``, ``compile_s``,
    ``fetch_s`` — each second once, under the innermost stage — and
    ``origin``: ``"compiled"`` (XLA compiled an executable under it),
    ``"jax_cache"`` (all came from jax's persistent cache),
    ``"package_cache"`` (from the package's disk tier); no ``origin``
    means jax had every executable in this process already.  A kept span
    hands its seconds and origin to the kept span round it when it ends.
    """
    session = tracing()
    if not session and not keep:
        return _OFF
    if keep and not _listening:
        _listen()
    return _Span(name, attrs, session, keep)


def spans() -> list:
    """The records of the newest profiler session, in order of their end
    (a child before its parent).  Copies: the caller may keep them."""
    return [dict(r) for r in _buffer.records]


def spans_dropped() -> int:
    """How many spans of the newest session found the buffer full."""
    return _buffer.dropped


def clear_spans() -> None:
    """Empty the session's buffer.  Kept records stay (:func:`builds`)."""
    _buffer.clear()


def builds() -> list:
    """The kept records of this process, in order of their end (a child
    before its parent), whether or not a session ran.  A *build* is a
    record whose ``attrs["build"]`` is its own ``id``: ``mpx.pin``
    (``kind="pin"``), ``mpx.build`` (``kind="region"`` or ``"eager"``).
    Copies: the caller may keep them."""
    return [dict(r, attrs=dict(r["attrs"])) for r in _builds.records]


def builds_dropped() -> int:
    """How many kept spans found their buffer full."""
    return _builds.dropped


def build_counts() -> dict:
    """Builds ended in this process by ``kind`` and by ``origin``
    (``"memory"``: no executable was compiled or fetched under it), drops
    included: ``mpx.cache_stats()["builds"]``."""
    with _lock:
        return {k: dict(v) for k, v in _build_counts.items()}


def clear_builds() -> None:
    """Empty the kept buffer and zero the counts (``mpx.clear_caches``)."""
    with _lock:
        _builds.clear()
        for counts in _build_counts.values():
            counts.clear()


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
