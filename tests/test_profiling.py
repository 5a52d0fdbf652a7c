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
import time  # noqa: E402

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
    """``mpx.pin`` carries the program's name and its stages as children:
    the trace, then the lowering and the compile, or the load where the
    disk tier has the program.  It says where the executable came from."""
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
    assert pin["attrs"]["program"] == "scaled_sum"
    assert pin["attrs"]["kind"] == "pin"
    assert pin["attrs"]["origin"] == ("compiled" if tier == "compile"
                                      else "package_cache")
    stages = sorted(_children(pin), key=lambda r: r["start_ns"])
    assert [s["name"] for s in stages] == ["mpx.pin.trace"] + (
        ["mpx.pin.lower", "mpx.pin.compile"] if tier == "compile"
        else ["mpx.pin.load"])
    assert all(_inside(s, pin) and s["call"] == pin["call"] for s in stages)
    assert all(a["end_ns"] <= b["start_ns"]
               for a, b in zip(stages, stages[1:]))
    # the session's records of a kept span are the kept buffer's
    assert [r["id"] for r in profiling.builds()][-len(stages) - 1:] == [
        s["id"] for s in stages] + [pin["id"]]


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
    """With the disk tier on, a program-cache miss pins: ``mpx.pin`` lies
    in the region's ``mpx.build`` (a child of ``mpx.region_call``) and
    ends before ``mpx.launch`` starts.  The build is the region's: the
    pin inside it is no build of its own, and hands it what it found."""
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", str(tmp_path / "t"))
    fn = mpx.spmd(_reduce, comm=_world())
    before = mpx.cache_stats()["builds"]["by_kind"]
    with _session(tmp_path):
        fn(jnp.ones((4, 8), jnp.float32))
    (region,) = _named("mpx.region_call")
    (build,) = _children(region)
    assert build["name"] == "mpx.build"
    pin, launch = sorted(_children(build), key=lambda r: r["start_ns"])
    assert (pin["name"], launch["name"]) == ("mpx.pin", "mpx.launch")
    assert pin["end_ns"] <= launch["start_ns"]
    assert "kind" not in pin["attrs"]
    assert pin["attrs"]["build"] == build["id"] == build["attrs"]["build"]
    assert build["attrs"]["origin"] == pin["attrs"]["origin"] == "compiled"
    assert build["attrs"]["compile_s"] == pin["attrs"]["compile_s"] > 0
    after = mpx.cache_stats()["builds"]["by_kind"]
    assert after["region"] - before.get("region", 0) == 1
    assert after.get("pin", 0) == before.get("pin", 0)


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


# ---------------------------------------------------------------------------
# kept spans: the build path, with or without a session
# ---------------------------------------------------------------------------


def _builds_named(name, since=0):
    return [r for r in profiling.builds()[since:] if r["name"] == name]


def _fresh(tag):
    """A region no test has built before: its own function, its own
    constant (so neither jax's caches nor the package's know it)."""
    def body(v):
        return _reduce(v) * float(tag)

    body.__name__ = f"fresh_{tag}"
    return mpx.spmd(body, comm=_world())


def test_a_kept_span_records_with_no_session():
    """No session runs: a kept span still makes a record of the common
    shape, in the kept buffer and not in the session's; a call-path span
    beside it stays the shared do-nothing object and records nothing."""
    profiling.clear_spans()
    n = len(profiling.builds())
    assert not profiling.tracing()
    off = profiling.span("mpx.call", program="f")
    with profiling.span("kept.outer", keep=True, program="p") as outer:
        assert profiling.span("mpx.launch") is off
        with off as nothing:
            assert nothing is None
        with profiling.span("kept.inner", keep=True) as inner:
            pass
    assert profiling.spans() == []
    got = profiling.builds()[n:]
    assert [r["name"] for r in got] == ["kept.inner", "kept.outer"]
    assert set(outer) == {"name", "start_ns", "end_ns", "id", "parent",
                          "call", "attrs"}
    assert outer["parent"] is None and outer["call"] == outer["id"]
    assert inner["parent"] == outer["id"] and inner["call"] == outer["id"]
    assert outer["attrs"] == {"program": "p", "build": outer["id"]}
    assert inner["attrs"] == {"build": outer["id"]}
    assert _inside(inner, outer) and 0 < outer["start_ns"]


def test_a_call_path_span_with_no_session_reads_no_clock(monkeypatch):
    """The call path off: the same object every time, no record in either
    buffer, and the clock is not read (``keep=False`` says the same)."""
    def no_clock():
        raise AssertionError("a call-path span read the clock")

    profiling.clear_spans()
    n = len(profiling.builds())
    monkeypatch.setattr(profiling.time, "time_ns", no_clock)
    names = ("mpx.call", "mpx.region_call", "mpx.eager.allreduce",
             "mpx.launch")
    handed = {id(profiling.span(name, program="f")) for name in names}
    handed.add(id(profiling.span("mpx.call", keep=False)))
    assert handed == {id(profiling._OFF)}
    for name in names:
        with profiling.span(name) as record:
            assert record is None
    assert profiling.spans() == [] and len(profiling.builds()) == n


def test_kept_records_outlive_a_session_and_clear_spans(tmp_path):
    """A session's first span clears the session's buffer, and
    ``clear_spans()`` empties it: neither touches the kept records.
    ``clear_builds()`` (``mpx.clear_caches``) does."""
    with profiling.span("kept.before", keep=True):
        pass
    n = len(profiling.builds())
    with _session(tmp_path):
        with profiling.span("in.session"):
            pass
        with profiling.span("kept.during", keep=True):
            pass
    assert [r["name"] for r in profiling.spans()] == ["in.session",
                                                      "kept.during"]
    assert [r["name"] for r in profiling.builds()[n - 1:]] == [
        "kept.before", "kept.during"]
    profiling.clear_spans()
    assert profiling.spans() == []
    assert [r["name"] for r in profiling.builds()[n - 1:]] == [
        "kept.before", "kept.during"]
    mpx.clear_caches()
    assert profiling.builds() == [] and profiling.builds_dropped() == 0
    assert mpx.cache_stats()["builds"] == {"by_kind": {}, "by_origin": {}}


def test_a_fresh_pin_is_one_build_compiled_here():
    """``mpx.compile`` of a program nobody has built: one ``mpx.pin``,
    a build (``build`` is its own id), ``origin`` ``"compiled"``, the
    stages' seconds summed from its children and inside its duration;
    the counters at the same boundary rise by one each."""
    before, n = mpx.cache_stats(), len(profiling.builds())
    x = jnp.ones((4, 16), jnp.float32)
    program = mpx.compile(_fresh(11), x)
    after = mpx.cache_stats()
    (pin,) = _builds_named("mpx.pin", n)
    attrs = pin["attrs"]
    assert attrs["program"] == "fresh_11" and attrs["kind"] == "pin"
    assert attrs["build"] == pin["id"] and attrs["origin"] == "compiled"
    assert attrs["compile_s"] > 0 and attrs["trace_s"] > 0
    assert attrs["lower_s"] > 0 and "fetch_s" not in attrs
    stages = {r["name"]: r for r in profiling.builds()[n:]
              if r["parent"] == pin["id"]}
    assert sorted(stages) == ["mpx.pin.compile", "mpx.pin.lower",
                              "mpx.pin.trace"]
    for key, stage in (("trace_s", "mpx.pin.trace"),
                       ("lower_s", "mpx.pin.lower"),
                       ("compile_s", "mpx.pin.compile")):
        assert stages[stage]["attrs"][key] == attrs[key]
        assert stages[stage]["attrs"]["build"] == pin["id"]
    held = attrs["trace_s"] + attrs["lower_s"] + attrs["compile_s"]
    assert held <= (pin["end_ns"] - pin["start_ns"]) * 1e-9
    assert after["aot"]["pins"] - before["aot"]["pins"] == 1
    assert after["aot"]["compiles"] - before["aot"]["compiles"] == 1
    for counts, key in (("by_kind", "pin"), ("by_origin", "compiled")):
        assert (after["builds"][counts][key]
                - before["builds"][counts].get(key, 0)) == 1
    assert program.memory_bytes().keys() == {"temporaries", "arguments",
                                             "results", "code"}
    assert program.memory_bytes()["arguments"] == x.nbytes // 4


@pytest.fixture
def jax_cache(tmp_path):
    """jax's persistent cache on, in a directory of the test's own, for
    every program however small and quick."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {name: getattr(jax.config, name) for name in names}
    jax.config.update(names[0], str(tmp_path / "jax_cache"))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], -1)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for name, value in old.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_a_pin_fetched_from_jaxs_cache_says_so(jax_cache):
    """The same program pinned again after ``jax.clear_caches()`` with
    jax's persistent cache on: ``origin`` ``"jax_cache"``, ``fetch_s``
    over 0 and ``compile_s`` 0 — jax 0.9.0 closes a
    ``backend_compile_duration`` round the fetch too, which is the
    fetch's.  ``aot.compiles`` counts the compile alone."""
    x = jnp.ones((4, 16), jnp.float32)
    fn = _fresh(13)
    n = len(profiling.builds())
    before = mpx.cache_stats()
    mpx.compile(fn, x)
    jax.clear_caches()
    want = np.asarray(mpx.compile(fn, x)(x))
    after = mpx.cache_stats()
    first, second = _builds_named("mpx.pin", n)
    assert first["attrs"]["origin"] == "compiled"
    assert first["attrs"]["compile_s"] > 0
    assert second["attrs"]["origin"] == "jax_cache"
    assert second["attrs"]["fetch_s"] > 0
    assert second["attrs"].get("compile_s", 0.0) == 0.0
    assert second["attrs"]["trace_s"] > 0 and second["attrs"]["lower_s"] > 0
    np.testing.assert_array_equal(want, np.full((4, 16), 4.0 * 13))
    assert after["aot"]["pins"] - before["aot"]["pins"] == 2
    assert after["aot"]["compiles"] - before["aot"]["compiles"] == 1
    origins = after["builds"]["by_origin"]
    assert origins["jax_cache"] - before["builds"]["by_origin"].get(
        "jax_cache", 0) == 1


@pytest.mark.parametrize("kind", ["region", "eager"])
def test_a_new_programs_first_call_is_one_build(kind):
    """A region's first call (a program-cache miss) runs under one kept
    ``mpx.build`` with ``kind="region"``, an eager op's cache miss under
    one with ``kind="eager"``: its second call, a hit, under none."""
    comm = _world()
    if kind == "region":
        fn, program = _fresh(17), "fresh_17"
        x = jnp.ones((4, 8), jnp.float32)
    else:
        fn, program = (lambda v: mpx.allreduce(v, op=mpx.MAX, comm=comm)[0]), \
            "allreduce"
        x = jnp.ones((4, 24), jnp.float32)
        mpx.clear_caches()  # the eager cache: a miss for certain
    n = len(profiling.builds())
    before = mpx.cache_stats()["builds"]["by_kind"].get(kind, 0)
    first = np.asarray(fn(x))
    (build,) = profiling.builds()[n:]
    assert build["name"] == "mpx.build" and build["parent"] is None
    assert build["attrs"]["program"] == program
    assert build["attrs"]["kind"] == kind
    assert build["attrs"]["build"] == build["id"]
    assert build["attrs"]["origin"] == "compiled"
    assert build["attrs"]["trace_s"] > 0 and build["attrs"]["compile_s"] > 0
    np.testing.assert_array_equal(np.asarray(fn(x)), first)
    assert len(profiling.builds()) == n + 1
    assert mpx.cache_stats()["builds"]["by_kind"][kind] - before == 1


def test_kept_pins_are_as_many_as_the_counter_says():
    """The count of kept ``mpx.pin`` builds equals the rise of
    ``cache_stats()["aot"]["pins"]``, across ``clear_caches()`` too."""
    mpx.clear_caches()
    x = jnp.ones((4, 8), jnp.float32)
    fn = _fresh(19)
    for _ in range(3):
        mpx.compile(fn, x)
    fn(x)  # a region's build is no pin
    pins = [r for r in profiling.builds()
            if r["name"] == "mpx.pin" and r["attrs"]["build"] == r["id"]]
    assert len(pins) == 3 == mpx.cache_stats()["aot"]["pins"]
    assert mpx.cache_stats()["builds"]["by_kind"] == {"pin": 3, "region": 1}


def test_kept_buffer_cap_drops_and_counts(monkeypatch):
    """Beyond ``BUILD_CAP`` records a kept span is dropped and counted;
    the counts of builds go on."""
    mpx.clear_caches()
    monkeypatch.setattr(profiling, "BUILD_CAP", 3)
    for i in range(5):
        with profiling.span("kept", keep=True, kind="test", i=str(i)):
            pass
    assert [r["attrs"]["i"] for r in profiling.builds()] == list("012")
    assert profiling.builds_dropped() == 2
    assert mpx.cache_stats()["builds"]["by_kind"] == {"test": 5}
    assert mpx.cache_stats()["builds"]["by_origin"] == {"memory": 5}
    profiling.clear_builds()
    assert profiling.builds() == [] and profiling.builds_dropped() == 0


def test_kept_and_session_spans_nest_across_each_other(tmp_path):
    """One stack of open spans for both sorts: ``parent`` and ``call``
    cross from a session span to a kept one and back, ``build`` names the
    outermost *kept* span, and a session span is in the session's buffer
    alone."""
    n = len(profiling.builds())
    with _session(tmp_path):
        with profiling.span("call.outer") as outer:
            with profiling.span("kept.build", keep=True) as build:
                with profiling.span("call.launch") as launch:
                    with profiling.span("kept.stage", keep=True) as stage:
                        profiling.account("fetch_s", 0.25, "jax_cache")
    assert [r["name"] for r in profiling.spans()] == [
        "kept.stage", "call.launch", "kept.build", "call.outer"]
    assert [r["name"] for r in profiling.builds()[n:]] == [
        "kept.stage", "kept.build"]
    assert build["parent"] == outer["id"] and launch["parent"] == build["id"]
    assert stage["parent"] == launch["id"]
    assert {r["call"] for r in (outer, build, launch, stage)} == {
        outer["id"]}
    assert stage["attrs"]["build"] == build["id"] == build["attrs"]["build"]
    assert "build" not in launch["attrs"] and "build" not in outer["attrs"]
    assert build["attrs"]["fetch_s"] == stage["attrs"]["fetch_s"] == 0.25
    assert build["attrs"]["origin"] == "jax_cache"


def test_a_builds_seconds_are_counted_once():
    """A stage that ends round stages already accounted adds its own time
    only; a "compile" that ends round a fetch is the fetch's; one real
    compile makes the whole build ``"compiled"``."""
    with profiling.span("kept", keep=True) as record:
        time.sleep(0.02)
        profiling.account("compile_s", 0.004, "compiled")  # a helper
        profiling.account("trace_s", 0.015)  # the trace that held it
        time.sleep(0.02)
        profiling.account("fetch_s", 0.003, "jax_cache")
        profiling.account("compile_s", 0.005, "compiled")  # round the fetch
    attrs = record["attrs"]
    assert attrs["compile_s"] == pytest.approx(0.004)
    assert attrs["trace_s"] == pytest.approx(0.011)
    assert attrs["fetch_s"] == pytest.approx(0.005)
    assert attrs["origin"] == "compiled"
    with profiling.span("kept", keep=True) as fetched:
        time.sleep(0.01)
        profiling.account("fetch_s", 0.003, "jax_cache")
        profiling.account("compile_s", 0.005, "compiled")
    assert fetched["attrs"]["origin"] == "jax_cache"
    assert "compile_s" not in fetched["attrs"]
    profiling.account("compile_s", 1.0, "compiled")  # no kept span open
