"""profile_ops: the TPU-side per-op latency story (SURVEY.md §5 tracing).

The measured host-bracket path (MPI4JAX_TPU_TRACE) is CPU-backend-only by
design; on TPU the measured source is the device profiler.  ``profile_ops``
packages the capture protocol (async-dispatch fence before the trace
closes) — this file pins that a trace of a program full of collectives
actually lands on disk with content, on the test backend; the chip lane's
recipe is the same call (docs/usage.md).
"""

import glob

import jax.numpy as jnp
import numpy as np

import mpi4jax_tpu as mpx


def test_profile_ops_captures_trace(tmp_path):
    comm = mpx.get_default_comm()

    @mpx.spmd
    def step(x):
        y, tok = mpx.allreduce(x, op=mpx.SUM, comm=comm)
        z, _ = mpx.sendrecv(y, y, dest=mpx.shift(1), comm=comm, token=tok)
        return z

    x = jnp.ones((8, 64))
    step(x)  # compile outside the capture window
    logdir = str(tmp_path / "trace")
    with mpx.profile_ops(logdir):
        out = step(x)
    # the fence ran inside the context: out is ready without further sync
    assert np.isfinite(np.asarray(out)).all()
    files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    assert files, f"no trace captured under {logdir}"


def test_profile_ops_summary_reports_fence(tmp_path):
    """The yielded summary proves the exit fence ran: it names the trace
    dir/backend and counts the live arrays it blocked on (scoped to the
    DEFAULT backend — a sidecar array on another backend must not stall
    the close)."""
    logdir = str(tmp_path / "trace_summary")
    with mpx.profile_ops(logdir) as prof:
        out = jnp.ones((8, 16)) * 2
    assert prof.trace_dir == logdir
    assert prof.backend == "cpu"
    # `out` is live at exit, so the fence had at least it to block on
    assert prof.fenced_arrays >= 1
    assert np.isfinite(np.asarray(out)).all()
    assert "fenced_arrays=" in repr(prof)


def test_profile_ops_nested_exceptions_close_trace(tmp_path):
    """An exception inside the window must not leave the profiler running
    (a dangling session would poison every later capture)."""
    logdir = str(tmp_path / "trace2")
    try:
        with mpx.profile_ops(logdir):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    # a second capture works — the first session was closed
    with mpx.profile_ops(logdir):
        jnp.ones(4).sum()
    assert glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)


# ---------------------------------------------------------------------------
# host spans of the call path (utils/profiling.span)
# ---------------------------------------------------------------------------

import contextlib  # noqa: E402
import threading  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

from mpi4jax_tpu.utils import profiling  # noqa: E402


def _reduce(x):
    y, _ = mpx.allreduce(x, op=mpx.SUM)
    return y


def _world():
    mesh = mpx.make_world_mesh(devices=jax.devices()[:4])
    return mpx.Comm(mesh.axis_names, mesh=mesh)


@contextlib.contextmanager
def _session(tmp_path):
    """A profiler session, as ``--trace 1`` of the benchmark opens one."""
    profiling.clear_spans()
    jax.profiler.start_trace(str(tmp_path / "session"))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _named(name):
    return [r for r in profiling.spans() if r["name"] == name]


def _children(parent):
    return [r for r in profiling.spans() if r["parent"] == parent["id"]]


def _inside(child, parent):
    return (parent["start_ns"] <= child["start_ns"]
            and child["end_ns"] <= parent["end_ns"])


@pytest.fixture
def pinned():
    comm = _world()
    x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)
    program = mpx.compile(mpx.spmd(_reduce, comm=comm), x)
    return program, x, np.asarray(program(x))


def test_spans_off_outside_a_session(pinned):
    """No session: N calls leave the buffer empty, hand out the one shared
    do-nothing object, and return what they returned before."""
    program, x, want = pinned
    profiling.clear_spans()
    assert not profiling.tracing()
    for _ in range(5):
        np.testing.assert_array_equal(np.asarray(program(x)), want)
    assert profiling.spans() == [] and profiling.spans_dropped() == 0
    assert profiling.span("mpx.call") is profiling.span("mpx.launch")
    with profiling.span("mpx.call", program="f") as record:
        assert record is None


def test_pinned_calls_give_call_and_launch_spans(pinned, tmp_path):
    """Under a session, N calls give N ``mpx.call`` spans, each with one
    ``mpx.launch`` child inside it that shares its ``call`` id, and N is
    the rise of the counter at the same boundary."""
    program, x, want = pinned
    before = mpx.cache_stats()["aot"]["calls"]
    with _session(tmp_path):
        outs = [program(x) for _ in range(7)]
    assert mpx.cache_stats()["aot"]["calls"] - before == 7
    for out in outs:
        np.testing.assert_array_equal(np.asarray(out), want)
    calls = _named("mpx.call")
    assert len(calls) == 7 == len(_named("mpx.launch"))
    assert len({c["call"] for c in calls}) == 7
    for call in calls:
        assert call["parent"] is None and call["call"] == call["id"]
        assert call["attrs"] == {"program": "_reduce"}
        (launch,) = _children(call)
        assert launch["name"] == "mpx.launch"
        assert launch["call"] == call["call"]
        assert _inside(launch, call)
        assert 0 < launch["start_ns"] <= launch["end_ns"]
    assert profiling.spans_dropped() == 0


@pytest.mark.parametrize("tier", ["compile", "load"])
def test_a_pin_inside_a_session_names_its_stages(tier, tmp_path,
                                                 monkeypatch):
    """``mpx.pin`` carries the program's name and two children: the trace,
    then the compile, or the load where the disk tier has the program."""
    comm = _world()
    x = jnp.ones((4, 16), jnp.float32)

    @mpx.spmd(comm=comm)
    def scaled_sum(v):
        return _reduce(v) * 2.0

    if tier == "load":
        monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR",
                           str(tmp_path / "tier"))
        mpx.compile(scaled_sum, x)  # the first pin writes it
    with _session(tmp_path):
        program = mpx.compile(scaled_sum, x)
    assert program.from_disk == (tier == "load")
    (pin,) = _named("mpx.pin")
    assert pin["attrs"] == {"program": "scaled_sum"}
    stages = sorted(_children(pin), key=lambda r: r["start_ns"])
    assert [s["name"] for s in stages] == ["mpx.pin.trace", "mpx.pin." + tier]
    assert all(_inside(s, pin) and s["call"] == pin["call"] for s in stages)
    assert stages[0]["end_ns"] <= stages[1]["start_ns"]


@pytest.mark.parametrize("path", ["region", "eager"])
def test_region_and_eager_calls_give_spans(path, tmp_path):
    """A call of an ``mpx.spmd`` function outside a pin is
    ``mpx.region_call``, an eager op ``mpx.eager.<op>``: each with one
    ``mpx.launch`` child inside it.  Ops inside the region run at trace
    time and get no span of their own."""
    comm = _world()
    x = jnp.ones((4, 8), jnp.float32)
    if path == "region":
        fn, name = mpx.spmd(_reduce, comm=comm), "mpx.region_call"
        attrs = {"program": "_reduce"}
    else:
        fn, name = (lambda v: mpx.allreduce(v, op=mpx.SUM, comm=comm)[0]), \
            "mpx.eager.allreduce"
        attrs = {}
    want = np.asarray(fn(x))  # traced and compiled outside the session
    with _session(tmp_path):
        outs = [fn(x) for _ in range(3)]
    for out in outs:
        np.testing.assert_array_equal(np.asarray(out), want)
    spans = _named(name)
    assert len(spans) == 3 == len(profiling.spans()) // 2
    for s in spans:
        assert s["parent"] is None and s["attrs"] == attrs
        (launch,) = _children(s)
        assert launch["name"] == "mpx.launch" and _inside(launch, s)


def test_region_call_pins_beside_its_launch(tmp_path, monkeypatch):
    """With the disk tier on, a program-cache miss pins: ``mpx.pin`` is a
    child of ``mpx.region_call`` and ends before ``mpx.launch`` starts."""
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", str(tmp_path / "t"))
    fn = mpx.spmd(_reduce, comm=_world())
    with _session(tmp_path):
        fn(jnp.ones((4, 8), jnp.float32))
    (region,) = _named("mpx.region_call")
    pin, launch = sorted(_children(region), key=lambda r: r["start_ns"])
    assert (pin["name"], launch["name"]) == ("mpx.pin", "mpx.launch")
    assert pin["end_ns"] <= launch["start_ns"]


def test_spans_of_two_threads_keep_separate_parents(tmp_path):
    """The stack of open spans is the thread's own: a span opened on one
    thread while another thread's span is open is no child of it."""
    opened, release = threading.Event(), threading.Event()

    def other():
        with profiling.span("other.outer"):
            opened.set()
            assert release.wait(timeout=30)
            with profiling.span("other.inner"):
                pass

    with _session(tmp_path):
        thread = threading.Thread(target=other)
        thread.start()
        assert opened.wait(timeout=30)
        with profiling.span("main.outer"):
            with profiling.span("main.inner"):
                pass
        release.set()
        thread.join(timeout=30)
        assert not thread.is_alive()
    by_name = {r["name"]: r for r in profiling.spans()}
    assert len(by_name) == 4
    for side in ("main", "other"):
        outer, inner = by_name[side + ".outer"], by_name[side + ".inner"]
        assert outer["parent"] is None
        assert inner["parent"] == outer["id"]
        assert inner["call"] == outer["call"] == outer["id"]
    assert by_name["main.outer"]["call"] != by_name["other.outer"]["call"]


def test_span_buffer_cap_drops_and_counts(tmp_path, monkeypatch):
    monkeypatch.setattr(profiling, "SPAN_CAP", 4)
    with _session(tmp_path):
        for i in range(10):
            with profiling.span("s", i=str(i)):
                pass
    assert [r["attrs"]["i"] for r in profiling.spans()] == list("0123")
    assert profiling.spans_dropped() == 6
    profiling.clear_spans()
    assert profiling.spans() == [] and profiling.spans_dropped() == 0


def test_span_buffer_holds_the_newest_session(tmp_path):
    """A span that finds a session running after one that found none
    starts the buffer afresh; ``profile_ops`` starts it afresh too and
    hands the block's spans out as ``prof.spans``."""
    with _session(tmp_path):
        with profiling.span("first"):
            pass
    with profiling.span("between") as record:
        assert record is None
    assert [r["name"] for r in profiling.spans()] == ["first"]
    with mpx.profile_ops(str(tmp_path / "ops")) as prof:
        with profiling.span("second"):
            pass
        assert prof.spans == []
    assert [r["name"] for r in prof.spans] == ["second"]
    assert [r["name"] for r in profiling.spans()] == ["second"]
    assert "spans=1" in repr(prof)


def _host_events(logdir, prefix):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return {e.name: int(e.start_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)}


def test_span_clock_is_the_trace_files_plus_one_constant(tmp_path):
    """Ten spans, each entered directly inside a plain ``TraceAnnotation``
    as ``mpx.call`` is inside the benchmark's ``dispatch_`` span: the
    buffer's ``time.time_ns()`` start less the file's start of the
    enclosing annotation (and of the span's own) is one constant to within
    50 us.  A machine busy with other tests may take a thread off the
    processor between two readings, so the best of three sessions counts."""
    scatters = []
    for attempt in range(3):
        logdir = tmp_path / f"clock{attempt}"
        profiling.clear_spans()
        jax.profiler.start_trace(str(logdir))
        try:
            with profiling.span("clock.warm"):  # the thread's first span
                pass
            for k in range(10):
                with jax.profiler.TraceAnnotation(f"clock.outer{k}"):
                    with profiling.span(f"clock.inner{k}"):
                        pass
        finally:
            jax.profiler.stop_trace()
        in_file = _host_events(logdir, "clock.")
        records = {r["name"]: r for r in profiling.spans()}
        outer = [records[f"clock.inner{k}"]["start_ns"]
                 - in_file[f"clock.outer{k}"] for k in range(10)]
        own = [records[f"clock.inner{k}"]["start_ns"]
               - in_file[f"clock.inner{k}"] for k in range(10)]
        assert min(outer) > 10 ** 18  # the file counts from the session
        scatters.append(max(max(outer) - min(outer), max(own) - min(own)))
        if scatters[-1] <= 50_000:
            break
    assert min(scatters) <= 50_000, scatters


def test_lowered_hlo_is_the_same_inside_and_outside_a_session(tmp_path):
    """Spans are host code: the program a pin lowers is the same text
    whether or not a profiler session runs, and its module carries the
    function's name."""
    comm = _world()
    x = jnp.ones((4, 8), jnp.float32)
    fn = mpx.spmd(_reduce, comm=comm)
    fn.__name__ = "renamed_after_decorating"

    def text():
        return mpx.compile(fn, x)._call.as_text()

    texts = []
    for traced in (False, True):  # one call site: locations are in the text
        with _session(tmp_path) if traced else contextlib.nullcontext():
            texts.append(text())
    outside, inside = texts
    assert _named("mpx.pin") and inside == outside
    assert "HloModule jit_renamed_after_decorating" in outside


def test_algorithm_phases_carry_a_scope_under_the_ops(monkeypatch):
    """One ``jax.named_scope`` per phase of a hand-built algorithm, under
    the op's ``mpi4jax_tpu.<op>`` scope: names in HLO metadata only."""
    monkeypatch.setenv("MPI4JAX_TPU_COLLECTIVE_ALGO", "ring")
    comm = _world()

    @mpx.spmd(comm=comm)
    def step(x):
        y, _ = mpx.allreduce(x, op=mpx.PROD)
        z, _ = mpx.bcast(y, 0)
        return z

    text = jax.jit(step).lower(jnp.ones((4, 64))).as_text(debug_info=True)
    for scope in ("mpi4jax_tpu.allreduce/ring_reduce_scatter/ppermute",
                  "mpi4jax_tpu.allreduce/ring_allgather/ppermute",
                  "mpi4jax_tpu.bcast/binomial_scatter/ppermute",
                  "mpi4jax_tpu.bcast/ring_allgather/ppermute"):
        assert scope in text, scope
