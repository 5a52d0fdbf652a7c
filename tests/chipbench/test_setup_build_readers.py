"""Tests of the readers of the program's kept build spans
(``chipbench/layer_metrics/setup_builds.py`` and the three metrics built on
it), on a synthetic trace and planted buffers.  No time is asserted here
that a chip would give: the numbers are the hand-made case's.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import mpi4jax_tpu as mpx  # noqa: E402
from chipbench import harness, trace_reduce, work  # noqa: E402
from mpi4jax_tpu.utils import profiling  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
READERS = ("setup_programs_compiled", "setup_build_fetch_s",
           "setup_build_host_s")
CELLS = ["sw3600x28800.1chip", "sw3600x28800_walls.1chip",
         "sw3600x28800_walls_solve.1chip", "osu_2x2.4chip"]

S, MS = 10 ** 9, 10 ** 6
T0 = 1_790_000_000 * S  # time.time_ns() when the process began to build
FIRST_CALL = T0 + 100 * S  # the window's first mpx.call


def _build(ident, name, begin_s, seconds, parent=None, **attrs):
    return {"name": name, "id": ident, "parent": parent,
            "call": parent or ident, "start_ns": T0 + int(begin_s * S),
            "end_ns": T0 + int((begin_s + seconds) * S),
            "attrs": dict(attrs, build=parent or ident)}


def _case():
    """Two pins before the window (one compiled in 40 s, one fetched in
    1.5 s), a region's and an eager op's first calls before it, a region's
    build inside it.  Returns ``(kept records, session records)`` as the
    program's buffers would hold them (a child before its parent)."""
    kept = [
        _build(2, "mpx.pin.trace", 1.0, 0.5, parent=1, trace_s=0.4),
        _build(3, "mpx.pin.lower", 1.5, 0.25, parent=1, lower_s=0.2),
        _build(4, "mpx.pin.compile", 1.75, 40.0, parent=1, compile_s=39.9,
               origin="compiled"),
        _build(1, "mpx.pin", 0.9, 41.0, program="large", kind="pin",
               trace_s=0.4, lower_s=0.2, compile_s=39.9, origin="compiled"),
        _build(5, "mpx.pin", 50.0, 2.0, program="small", kind="pin",
               trace_s=0.3, lower_s=0.1, fetch_s=1.5, origin="jax_cache"),
        _build(6, "mpx.build", 60.0, 0.5, program="make", kind="region",
               trace_s=0.1, lower_s=0.05, fetch_s=0.25, origin="jax_cache"),
        _build(7, "mpx.build", 70.0, 0.125, program="allreduce",
               kind="eager", trace_s=0.05),
        _build(8, "mpx.build", 101.0, 3.0, program="late", kind="region",
               compile_s=2.5, origin="compiled"),
    ]
    session = [
        {"name": "mpx.launch", "id": 21, "parent": 20, "call": 20,
         "start_ns": FIRST_CALL + 1000, "end_ns": FIRST_CALL + 2000,
         "attrs": {}},
        {"name": "mpx.call", "id": 20, "parent": None, "call": 20,
         "start_ns": FIRST_CALL, "end_ns": FIRST_CALL + 3000,
         "attrs": {"program": "large"}},
        kept[-1],  # a kept span inside a session is in both buffers
    ]
    return kept, session


WANT = {"setup_programs_compiled": 1.0,
        "setup_build_fetch_s": 1.5 + 0.25,
        # durations 41 + 2 + 0.5 + 0.125, less the fetches, less 39.9
        "setup_build_host_s": 43.625 - 1.75 - 39.9}


def _ctx(counters, devices=True):
    op = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %x)"
    raw = {"devices": {0: [(op, 1 * MS, 2 * MS)]} if devices else {},
           "host": [(trace_reduce.WINDOW_SPAN, 0, 10 * MS)]}
    ctx = {"trace": trace_reduce.reduce_events(raw), "counters": counters,
           "config": {}, "traffic": {}, "peaks": {}, "chips": 1,
           "work": work, "reduce": trace_reduce}
    ctx["reader"] = lambda name: harness.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    return ctx


def _plant(monkeypatch, kept, session, pins=2, dropped=0):
    monkeypatch.setattr(profiling, "builds", lambda: kept)
    monkeypatch.setattr(profiling, "spans", lambda: session)
    monkeypatch.setattr(profiling, "builds_dropped", lambda: dropped)
    monkeypatch.setattr(mpx, "cache_stats",
                        lambda: {"aot": {"pins": pins}})


def _read_all(ctx):
    return {name: ctx["reader"](name).read(ctx) for name in READERS}


SWEEP = {"calls": {"large": 3, "small": 3}}


@pytest.mark.parametrize("metric", READERS)
def test_reader_gives_the_hand_made_value(metric, monkeypatch):
    kept, session = _case()
    _plant(monkeypatch, kept, session)
    ctx = _ctx(SWEEP)
    assert ctx["reader"](metric).read(ctx) == pytest.approx(WANT[metric])
    # the builds before the window's first call, the late one left out
    assert [b["id"] for b in ctx["setup_builds"]] == [1, 5, 6, 7]


def test_a_leg_cell_counts_one_program(monkeypatch):
    """A cell that counts ``legs`` has one pinned program; a cold one reads
    1 compiled and no fetch."""
    kept, session = _case()
    _plant(monkeypatch, kept[:4], session[:2], pins=1)
    got = _read_all(_ctx({"legs": 3}))
    assert got == {"setup_programs_compiled": 1,
                   "setup_build_fetch_s": 0.0,
                   "setup_build_host_s": pytest.approx(41.0 - 39.9)}


def test_an_unpinned_cell_needs_no_pin(monkeypatch):
    """The host-loop cells pin nothing: their region builds are read, and
    the driver's count of programs holds nothing back."""
    kept, session = _case()
    _plant(monkeypatch, kept[5:7], session[:2], pins=0)
    got = _read_all(_ctx({"calls": {"first_step": 1, "multistep": 44}}))
    assert got == {"setup_programs_compiled": 0,
                   "setup_build_fetch_s": 0.25,
                   "setup_build_host_s": pytest.approx(0.25 + 0.125)}


def _no_builds(kept, session, plant):
    del kept[:]


def _no_session_span(kept, session, plant):
    del session[:2]


def _a_pin_the_counter_does_not_know(kept, session, plant):
    plant["pins"] = 1


def _a_pin_fewer_than_the_drivers_programs(kept, session, plant):
    del kept[4]
    plant["pins"] = 1


def _a_dropped_span(kept, session, plant):
    plant["dropped"] = 1


@pytest.mark.parametrize("fault", [
    _no_builds, _no_session_span, _a_pin_the_counter_does_not_know,
    _a_pin_fewer_than_the_drivers_programs, _a_dropped_span],
    ids=lambda f: f.__name__.strip("_"))
def test_readers_leave_their_metric_out(fault, monkeypatch):
    """Nothing to read, or a count that does not agree with the counter at
    the same boundary: every reader returns ``None`` (the harness then
    leaves the metric out), and none raises."""
    kept, session = _case()
    plant = {"pins": 2, "dropped": 0}
    fault(kept, session, plant)
    _plant(monkeypatch, kept, session, **plant)
    assert _read_all(_ctx(SWEEP)) == dict.fromkeys(READERS)


def test_readers_leave_their_metric_out_without_a_device_plane(monkeypatch):
    kept, session = _case()
    _plant(monkeypatch, kept, session)
    assert _read_all(_ctx(SWEEP, devices=False)) == dict.fromkeys(READERS)


def test_readers_leave_their_metric_out_on_a_program_without_builds(
        monkeypatch):
    """The parent commit's ``profiling`` has no ``builds``: nothing is
    reported and nothing raises."""
    kept, session = _case()
    _plant(monkeypatch, kept, session)
    monkeypatch.delattr(profiling, "builds")
    assert _read_all(_ctx(SWEEP)) == dict.fromkeys(READERS)


def test_readers_read_the_programs_own_buffers(tmp_path):
    """Unplanted: one real pin and one real region build on the CPU, a
    session with one call — the helper finds both builds before it and
    agrees with the counter; the readers, with a device plane lent by the
    synthetic trace, give one compiled program and no fetch."""
    import jax
    import jax.numpy as jnp

    mpx.clear_caches()
    mesh = mpx.make_world_mesh(devices=jax.devices()[:2])
    comm = mpx.Comm(mesh.axis_names, mesh=mesh)

    @mpx.spmd(comm=comm)
    def doubled(v):
        return mpx.allreduce(v, op=mpx.SUM)[0] * 2.0

    x = jnp.ones((2, 8), jnp.float32)
    program = mpx.compile(doubled, x)
    doubled(x)
    jax.profiler.start_trace(str(tmp_path / "session"))
    try:
        jax.block_until_ready(program(x))
    finally:
        jax.profiler.stop_trace()
    ctx = _ctx({"legs": 1})
    got = _read_all(ctx)
    assert [b["attrs"]["kind"] for b in ctx["setup_builds"]] == [
        "pin", "region"]
    assert got["setup_programs_compiled"] == 2
    assert got["setup_build_fetch_s"] == 0.0
    assert 0 < got["setup_build_host_s"] < sum(
        (b["end_ns"] - b["start_ns"]) * 1e-9 for b in ctx["setup_builds"])


def test_each_metric_has_a_reader_and_an_entry_that_waits():
    """No run loads the three yet: an entry put before PR 26's five is
    refused as a change to ``call_self_us``, and one put after them fails
    ``test_call_path_readers.py``, which pins ``per_layer[-5:]`` and is a
    ``benchmark`` PR's to edit.  That PR appends them as held here."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    units = {"setup_programs_compiled": ("count", "program_counter"),
             "setup_build_fetch_s": ("s", "program_span"),
             "setup_build_host_s": ("s", "program_span")}
    assert set(CELLS) <= cells
    for name in READERS:
        assert hasattr(harness.load_module(os.path.join(
            BENCH, "layer_metrics", name + ".py")), "read")
        if name not in by_name:
            continue
        metric = by_name[name]
        assert (metric["unit"], metric["source"]) == units[name]
        assert metric["better"] == "lower" and metric["moves"] == "setup_s"
        assert metric["layer"] == "Program pinning"
        assert set(CELLS) <= set(metric["workloads"]) <= cells
