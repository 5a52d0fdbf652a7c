"""Tests of the readers of the program's own host spans
(``chipbench/layer_metrics/call_path_spans.py`` and the five metrics built
on it), on a synthetic trace and a planted span buffer.  No time is
asserted here that a chip would give: the numbers are the hand-made case's.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness, trace_reduce, work  # noqa: E402
from mpi4jax_tpu.utils import profiling  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
READERS = {"call_self_us": "call_self_us", "launch_us": "launch_us",
           "first_op_delay_us": "first_op_delay_us",
           "sw_call_self_us": "call_self_us",
           "sw_first_op_delay_us": "first_op_delay_us"}

US, MS = 1_000, 1_000_000
SESSION = 1_790_000_000 * 10 ** 9  # time.time_ns() when the session began
LEAD = 2 * US          # a call starts this long after its dispatch_ span
JITTER = (0, 400, -300)             # ... give or take (ns)
SELF_US = (10, 20, 60)              # mpx.call less mpx.launch
LAUNCH_US = (400, 300, 500)
DELAY_US = (1500, 1200, 2200)       # mpx.launch start to the first op
WANT = {"call_self_us": 20.0, "launch_us": 400.0,
        "first_op_delay_us": 1500.0}


def _case():
    """Three calls on two devices.  Returns the raw trace and the span
    records as the program's buffer would hold them (a child before its
    parent)."""
    host = [(trace_reduce.WINDOW_SPAN, 0, 400 * MS)]
    devices = {0: [], 1: []}
    records = []
    for k in range(3):
        begin = (10 + 100 * k) * MS
        name = ("large_allreduce_1GiB", "small_sendrecv_4KiB",
                "small_allreduce_4B")[k]
        host += [("dispatch_" + name, begin, 1 * MS),
                 ("wait_" + name, begin + 1 * MS, 50 * MS)]
        call_start = SESSION + begin + LEAD + JITTER[k]
        launch_start = call_start + (SELF_US[k] - 1) * US
        launch_end = launch_start + LAUNCH_US[k] * US
        call = {"name": "mpx.call", "id": 10 * k + 1, "parent": None,
                "call": 10 * k + 1, "attrs": {"program": name},
                "start_ns": call_start, "end_ns": launch_end + 1 * US}
        launch = {"name": "mpx.launch", "id": 10 * k + 2,
                  "parent": call["id"], "call": call["id"], "attrs": {},
                  "start_ns": launch_start, "end_ns": launch_end}
        records += [launch, call]
        # on the trace's clock the launch began at its time_ns less the
        # session's constant, SESSION + LEAD by the median of the calls
        first = launch_start - (SESSION + LEAD) + DELAY_US[k] * US
        op = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %x)"
        devices[0] += [(op, first, 20 * MS), (op, first + 21 * MS, 20 * MS)]
        devices[1] += [(op, first + 100 * US, 20 * MS)]
    return {"devices": devices, "host": host}, records


def _ctx(raw, counters):
    ctx = {"trace": trace_reduce.reduce_events(raw), "counters": counters,
           "config": {}, "traffic": {}, "peaks": {}, "chips": 2,
           "work": work, "reduce": trace_reduce}
    ctx["reader"] = lambda name: harness.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    return ctx


SWEEP = {"calls": {"large_allreduce_1GiB": 1, "small_sendrecv_4KiB": 1,
                   "small_allreduce_4B": 1}}


def _read_all(ctx):
    return {name: ctx["reader"](name).read(ctx) for name in READERS}


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("counters", [SWEEP, {"legs": 3}],
                         ids=["calls", "legs"])
def test_reader_gives_the_hand_made_value(metric, counters, monkeypatch):
    raw, records = _case()
    monkeypatch.setattr(profiling, "spans", lambda: records)
    ctx = _ctx(raw, counters)
    assert ctx["reader"](metric).read(ctx) == pytest.approx(
        WANT[READERS[metric]])
    path = ctx["call_path"]
    assert path["constant_ns"] == SESSION + LEAD
    assert path["scatter_ns"] == 400
    assert [c["call"]["attrs"]["program"] for c in path["calls"]] == [
        "large_allreduce_1GiB", "small_sendrecv_4KiB", "small_allreduce_4B"]


def _empty_buffer(raw, records, counters):
    del records[:]


def _one_call_more_than_the_driver_made(raw, records, counters):
    counters["calls"]["small_allreduce_4B"] = 2


def _one_span_fewer(raw, records, counters):
    del records[-2:]


def _a_call_outlasts_its_dispatch_span(raw, records, counters):
    records[-1]["end_ns"] += 2 * MS


def _a_scattered_constant(raw, records, counters):
    for record in records[2:4]:  # the second call, 80 us late on its clock
        record["start_ns"] += 80 * US
        record["end_ns"] += 80 * US


def _a_call_without_its_launch(raw, records, counters):
    records[0]["parent"] = None


def _no_device_plane(raw, records, counters):
    raw["devices"] = {}


@pytest.mark.parametrize("fault", [
    _empty_buffer, _one_call_more_than_the_driver_made, _one_span_fewer,
    _a_call_outlasts_its_dispatch_span, _a_scattered_constant,
    _a_call_without_its_launch, _no_device_plane],
    ids=lambda f: f.__name__.strip("_"))
def test_readers_leave_their_metric_out(fault, monkeypatch):
    """Nothing to read, or clocks that cannot be shown to agree: every
    reader returns ``None`` (the harness then leaves the metric out), and
    none raises."""
    raw, records = _case()
    counters = {"calls": dict(SWEEP["calls"])}
    fault(raw, records, counters)
    monkeypatch.setattr(profiling, "spans", lambda: records)
    assert _read_all(_ctx(raw, counters)) == dict.fromkeys(READERS)


def test_readers_leave_their_metric_out_on_a_program_without_spans(
        monkeypatch):
    """The parent commit's ``profiling`` has no ``spans``: nothing is
    reported and nothing raises."""
    raw, _records = _case()
    monkeypatch.delattr(profiling, "spans")
    assert _read_all(_ctx(raw, SWEEP)) == dict.fromkeys(READERS)


def test_a_call_with_no_device_operation_silences_the_delay_alone(
        monkeypatch):
    raw, records = _case()
    for dev in raw["devices"]:  # the second call's operations never ran
        raw["devices"][dev] = [op for op in raw["devices"][dev]
                               if not 110 * MS <= op[1] < 200 * MS]
    monkeypatch.setattr(profiling, "spans", lambda: records)
    got = _read_all(_ctx(raw, SWEEP))
    assert got["first_op_delay_us"] is None
    assert got["sw_first_op_delay_us"] is None
    assert got["call_self_us"] == pytest.approx(20.0)
    assert got["launch_us"] == pytest.approx(400.0)


def test_new_metrics_are_entries_at_the_end_with_a_reader_each():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    added = bench["per_layer"][-5:]
    assert [m["name"] for m in added] == [
        "call_self_us", "launch_us", "first_op_delay_us", "sw_call_self_us",
        "sw_first_op_delay_us"]
    cells = {w["name"] for w in bench["workloads"]}
    for metric in added:
        assert metric["source"] == "program_span"
        assert metric["unit"] == "us" and metric["better"] == "lower"
        assert set(metric["workloads"]) <= cells
        assert hasattr(harness.load_module(os.path.join(
            BENCH, "layer_metrics", metric["name"] + ".py")), "read")
