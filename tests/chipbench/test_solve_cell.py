"""Tests of the documented-driver cell's own files, on the CPU at tiny sizes:
``chipbench/configs/sw3600x28800_walls_solve.json``,
``traffic/runs_multistep10.json``, ``drivers/solver_loop.py`` and the readers
``layer_metrics/sw_call_overhead_share.py`` and
``layer_metrics/sw_region_call_self_us.py``.  No time, rate or share is
asserted here that a chip would give.

What is tested: that the cell's names resolve; that ``correct`` comes out
true for a sound run and false for the control (the reference in bfloat16 in
the program's place) and for each planted fault, through the harness's own
run; what the driver counts; and the two readers on synthetic traces.
"""

import os
import sys
import types

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (REPO, os.path.join(REPO, "examples"),
             os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import shallow_water as program  # noqa: E402
from chipbench import harness, trace_reduce, work  # noqa: E402
from chipbench.reference import shallow_water_walls as walls_ref  # noqa: E402
from mpi4jax_tpu.utils import profiling  # noqa: E402
from test_chipbench import PEAK, SEED, make_root  # noqa: E402
from test_walls_cell import _failed, _named  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
CELL = "sw3600x28800_walls_solve.1chip"
SIBLING = "sw3600x28800_walls.1chip"
N_ITERS = 3   # the tiny run: 1 + 3 x 10 steps
MS = 1_000_000
NEW_READERS = [
    {"name": name, "unit": unit, "better": "lower", "source": source,
     "layer": layer, "moves": "steps_per_s_per_chip",
     "workloads": ["tiny.solve"]}
    for name, unit, source, layer in (
        ("sw_call_overhead_share", "%", "device_trace", "Applications"),
        ("sw_region_call_self_us", "us", "program_span", "Program pinning"))]


def _tiny():
    config = harness.load_json(os.path.join(
        BENCH, "configs", "sw3600x28800_walls_solve.json"))
    config.update(nx=48, ny=72)
    config["scaled"]["ny"]["published"] = 24
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "runs_multistep10.json"))
    # the first multiple of ten steps that reaches it is the third
    traffic["run_model_days"] = 25 * walls_ref.params(config)["dt"] / 86_400
    return config, traffic


@pytest.fixture
def solve_root(tmp_path):
    config, traffic = _tiny()
    make_root(str(tmp_path), [("tiny.solve", CELL, "tiny_solve", config,
                               "tiny_runs", traffic, 1)], NEW_READERS)
    return str(tmp_path)


def run_solve(root, hook=None, traced=False):
    return harness.run("tiny.solve", SEED, 0.2, traced, root=root,
                       devices=jax.devices()[:1], peaks=PEAK,
                       driver_hook=hook)


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------


def test_the_cells_names_resolve():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = _named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sw3600x28800_walls_solve", "runs_multistep10", 1)
    assert len(cell["why"]) <= 200
    entry = _named(bench["configs"], "sw3600x28800_walls_solve")
    assert entry["reduced"] == ["ny"] and len(entry["why"]) <= 200
    resolved = harness.resolve_cell(REPO, CELL)
    config, traffic = resolved["config"], resolved["traffic"]
    assert resolved["driver_path"].endswith("drivers/solver_loop.py")
    assert config["reference"] == "shallow_water_walls"
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    # the sibling's domain and initial state, key for key
    sibling = harness.resolve_cell(REPO, SIBLING)["config"]
    for key in ("nx", "ny", "dx", "dy", "gravity", "depth", "coriolis_f",
                "coriolis_beta", "periodic_x", "ab_a", "ab_b", "nproc_y",
                "nproc_x", "dtype", "fast", "reduced", "reference"):
        assert config[key] == sibling[key], key
    assert config["scaled"]["ny"]["published"] == 1800
    assert config["assumed"]["seeded_modes"] == sibling["assumed"][
        "seeded_modes"]
    assert len(config["guarantees"]) == 4
    assert "441 steps a run" in config["guarantees"][3]
    # the published loop, and the mix that runs it
    assert (config["num_multisteps"], config["t1_days"]) == (10, 0.1)
    assert (traffic["steps_per_call"], traffic["run_model_days"],
            traffic["warm_up_runs"], traffic["trace_runs"],
            traffic["seeded_modes"]) == (10, 0.1, 1, 1, 4)
    assert set(traffic["limits"]) == {
        f"{n}_gap" for n in walls_ref.FIELDS} | {"wall_flow"}
    assert traffic["limits"]["wall_flow"] == 0
    # what it reports, and what reads it; what later PRs enter is theirs
    assert {"steps_per_s_per_chip", "setup_s"} <= {
        m["name"] for m in resolved["end_to_end"]}
    assert {"sw_kernel_roofline", "sw_idle_share",
            "sw_compiles_in_window"} <= {
                m["name"] for m in resolved["per_layer"]}
    for reader in ("sw_call_overhead_share", "sw_region_call_self_us"):
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           reader + ".py"))
    # the driver works out the run from the mix as the program does
    driver = _driver_module().Driver(config, traffic, SEED,
                                     jax.devices()[:1], PEAK)
    cfg = program.Config(nx=3600, ny=28800, periodic_x=False)
    assert driver.n_iters == program.n_multisteps(
        cfg, 0.1 * program.DAY_IN_SECONDS, 10) == 44
    assert driver.steps == 441
    assert program.select_steps("auto", cfg)[1] is program.model_step2_wide


def _driver_module():
    return harness.load_module(os.path.join(BENCH, "drivers",
                                            "solver_loop.py"))


# ---------------------------------------------------------------------------
# correct: a sound run, the control, the faults
# ---------------------------------------------------------------------------


def test_solve_cell_sound_run_is_correct(solve_root):
    result = run_solve(solve_root)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"steps_per_s_per_chip", "setup_s"}
    assert result["compared"]["wall_flow"] == [0.0, 0]
    assert list(result["compared"])[-2:] == ["wall_flow", "nonfinite"]
    counters, runs = result["counters"], result["attempted"]
    assert counters["runs"] == runs
    assert counters["steps"] == (1 + 10 * N_ITERS) * runs
    assert counters["calls"] == {"first_step": runs,
                                 "multistep": N_ITERS * runs}
    assert counters["steps_per_kernel_call"] == 2
    assert counters["run_plan"] == program.run_plan(
        program.Config(nx=48, ny=72, periodic_x=False), "auto", N_ITERS, 10)
    assert len(counters["run_wall_s"]) == len(counters["warm_up_run_s"]) \
        + runs - 1 == runs
    assert set(counters["setup_stages"]) >= {"state_s", "compile_s",
                                             "warm_up_s"}


def test_solve_cell_traced_run_on_the_cpu(solve_root, monkeypatch):
    """No device plane on the CPU: the counters are in the line, the shares
    of the device's time are not.  The program's spans are: as many
    ``mpx.region_call`` as the driver counts calls, which is what
    ``sw_region_call_self_us`` asks before it reports."""
    monkeypatch.setattr(harness, "ROOT", solve_root)  # where the driver looks
    result = run_solve(solve_root, traced=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 1  # the mix's trace_runs
    assert set(result["metrics"]) == {"sw_compiles_in_window",
                                      "sw_region_call_self_us"}
    assert result["metrics"]["sw_compiles_in_window"]["value"] == 0.0
    assert result["metrics"]["sw_region_call_self_us"]["value"] > 0
    counters = result["counters"]
    assert "run_plan" in counters
    assert "traced_custom_calls_a_run" not in counters
    region_calls = [r for r in profiling.spans()
                    if r["name"] == "mpx.region_call"]
    assert len(region_calls) == sum(counters["calls"].values()) == \
        counters["run_plan"]["calls"]
    assert [r["attrs"]["program"] for r in region_calls] == \
        ["first_step"] + ["multistep"] * N_ITERS


def _reference_in_the_programs_place(precision):
    def hook(driver):
        fields = walls_ref.make_run(driver.params, driver.steps, precision)(
            *driver.initial_fields())
        out = [a[None] for a in fields]
        driver.run_multisteps = lambda _f, _m, state, *_a: type(state)(*out)
    return hook


def test_solve_control_bfloat16_is_not_correct(solve_root):
    """The control: the walled reference in bfloat16 in the program's
    place, through the harness's own run.  The float32 reference there is
    correct, so it is the precision that fails."""
    result = run_solve(solve_root,
                       _reference_in_the_programs_place(jnp.bfloat16))
    assert result["correct"] is False
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"h_gap", "u_gap", "v_gap"} <= _failed(result)
    value, limit = result["compared"]["h_gap"]
    assert value > 100 * limit
    sound = run_solve(solve_root,
                      _reference_in_the_programs_place(jnp.float32))
    assert sound["correct"] is True


def one_multistep_fewer(driver):
    driver.n_iters -= 1


def nothing_carried(driver):
    """Every call is fed the run's initial state."""
    first_step, multistep = driver.first_step, driver.multistep
    driver.first_step = lambda _state: first_step(driver.state)
    driver.multistep = lambda _state, n: multistep(driver.state, n)


@pytest.mark.parametrize("fault", [one_multistep_fewer, nothing_carried])
def test_solve_faults_come_out_not_correct(solve_root, fault):
    result = run_solve(solve_root, fault)
    assert not result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"h_gap", "u_gap", "v_gap"} <= _failed(result)


def test_a_tree_without_run_multisteps_stops_in_setup(monkeypatch):
    config, traffic = _tiny()
    monkeypatch.delattr(program, "run_multisteps")
    driver = _driver_module().Driver(config, traffic, SEED,
                                     jax.devices()[:1], PEAK)
    with pytest.raises(SystemExit, match="no run_multisteps"):
        driver.setup()


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

FRAME = "f32[28832,3632]{1,0:T(8,128)}"
FIELD = "f32[1,28802,3602]{2,1,0:T(8,128)}"
SIX = "(" + ", ".join([FRAME] * 6) + ")"
EULER = f"%sw_wide_x1_euler.1 = {SIX} custom-call({FRAME} %concatenate.21)"
PAIR = f"%sw_wide_x2.9 = {SIX} custom-call({FRAME} %concatenate.21)"
PAIR_IN_LOOP = f"%sw_wide_x2.10 = {SIX} custom-call({FRAME} %gte.4)"
COPY = f"%copy.5 = {FIELD} copy(f32[1,28802,3602]{{1,0,2:T(1,128)}} %p)"
BAND = (f"%dynamic-update-slice.99 = {FRAME} dynamic-update-slice("
        f"{FRAME} %gte, f32[28832,15]{{1,0}} %broadcast.3)")
BUILD = f"%concatenate.21 = {FRAME} concatenate(f32[15,3632]{{1,0}} %b)"
CROP = f"%slice_fusion.2 = f32[28802,3602]{{1,0}} fusion({FRAME} %gte.9)"
GLUE = (f"%custom-call.3 = {FRAME} custom-call(f32[28832,15]{{1,0}} %s), "
        'custom_call_target="ConcatBitcast"')

PLAN = {"calls": 3, "steps": 9, "steps_per_kernel_call": 2,
        "first_step": {"steps": 1, "euler_calls": 1, "chunk_calls": 0,
                       "single_step_calls": 0, "frames_built": 1,
                       "band_refreshes": 0, "crops": 1},
        "multistep": {"steps": 4, "euler_calls": 0, "chunk_calls": 2,
                      "single_step_calls": 0, "frames_built": 1,
                      "band_refreshes": 1, "crops": 1}}


def _runs(multisteps_a_run=2):
    """Two runs on one device, each a first-step call (layout copy 2 ms,
    frame built 2 ms, the Euler-step kernel 8 ms, crop 2 ms) and
    ``multisteps_a_run`` multistep calls (copy 2, build 2, the pair kernel
    20, a band update 1, a custom call of XLA's own 1, the pair kernel 20,
    crop 2, copy 2: 50 ms, 40 of them the kernel's), with 1 ms idle between
    calls."""
    device, host = [], [(trace_reduce.WINDOW_SPAN, 0, 1000 * MS)]
    at = 0
    for _run in range(2):
        start = at
        device += [(COPY, at, 2 * MS), (BUILD, at + 2 * MS, 2 * MS),
                   (EULER, at + 4 * MS, 8 * MS), (CROP, at + 12 * MS, 2 * MS)]
        at += 15 * MS
        for _call in range(multisteps_a_run):
            device += [(COPY, at, 2 * MS), (BUILD, at + 2 * MS, 2 * MS),
                       (PAIR, at + 4 * MS, 20 * MS),
                       (BAND, at + 24 * MS, 1 * MS),
                       (GLUE, at + 25 * MS, 1 * MS),
                       (PAIR_IN_LOOP, at + 26 * MS, 20 * MS),
                       (CROP, at + 46 * MS, 2 * MS),
                       (COPY, at + 48 * MS, 2 * MS)]
            at += 51 * MS
        host += [("dispatch_leg_run", start, 3 * MS),
                 ("wait_leg_run", start + 3 * MS, at - start - 3 * MS)]
        at += 100 * MS
    return {"devices": {0: device}, "host": host}


def _overhead_share(raw, plan):
    """The reader on the counters the driver would have given it."""
    module = _driver_module()
    counters = {"runs": 2}
    if plan is not None:
        counters["run_plan"] = plan
    calls = module._runner.custom_calls_a_leg(raw, 2)
    if calls is not None:
        counters["traced_custom_calls_a_run"] = calls
    ctx = {"trace": trace_reduce.reduce_events(raw), "config": {},
           "traffic": {}, "peaks": {}, "chips": 1, "work": work,
           "reduce": trace_reduce, "counters": counters}
    return harness.load_module(os.path.join(
        BENCH, "layer_metrics", "sw_call_overhead_share.py")).read(ctx)


def test_overhead_share_of_the_runs_busy_time():
    # a run: 14 ms busy in the first step, 8 of them the kernel's; 50 and 40
    # in each of two multisteps
    assert _overhead_share(_runs(), PLAN) == pytest.approx(
        100 * (1 - 88 / 114))
    module = _driver_module()
    assert module._runner.custom_calls_a_leg(_runs(), 2) == {
        "custom-call": 2.0, "sw_wide_x1_euler": 1.0, "sw_wide_x2": 4.0}


@pytest.mark.parametrize("why", ["no_plan", "another_count", "no_euler",
                                 "no_device", "no_run"])
def test_overhead_share_reports_nothing_it_cannot_stand_behind(why):
    plan, raw = PLAN, _runs()
    if why == "no_plan":          # a tree without run_plan
        plan = None
    elif why == "another_count":  # the trace is not of the planned run
        raw = _runs(multisteps_a_run=3)
    elif why == "no_euler":
        raw["devices"][0] = [e for e in raw["devices"][0] if e[0] != EULER]
    elif why == "no_device":
        raw["devices"] = {}
    else:
        raw["host"] = [h for h in raw["host"] if not h[0].endswith("_run")]
    assert _overhead_share(raw, plan) is None


def _region_call_self_us(records, calls):
    spans = harness.load_module(os.path.join(BENCH, "layer_metrics",
                                             "call_path_spans.py"))
    fake = types.SimpleNamespace(program_spans=lambda: records,
                                 driver_calls=spans.driver_calls)
    ctx = {"counters": {"calls": calls}, "reader": lambda name: fake}
    return harness.load_module(os.path.join(
        BENCH, "layer_metrics", "sw_region_call_self_us.py")).read(ctx)


def _records(self_us=(30, 50, 40)):
    """One ``mpx.region_call`` a value of ``self_us``, each with its one
    child ``mpx.launch`` of 700 us."""
    records, at = [], 0
    for i, us in enumerate(self_us):
        call = {"name": "mpx.region_call", "id": 2 * i, "parent": None,
                "start_ns": at, "end_ns": at + (700 + us) * 1000}
        launch = {"name": "mpx.launch", "id": 2 * i + 1, "parent": 2 * i,
                  "start_ns": at + us * 500,
                  "end_ns": at + us * 500 + 700_000}
        records += [launch, call]  # in order of their end
        at += 1_000_000
    return records


def test_region_call_self_time_is_the_span_less_its_launch():
    calls = {"first_step": 1, "multistep": 2}
    assert _region_call_self_us(_records(), calls) == pytest.approx(40.0)


@pytest.mark.parametrize("why", ["no_spans", "another_count", "two_launches",
                                 "no_launch", "no_counter"])
def test_region_call_self_time_reports_nothing_it_cannot_stand_behind(why):
    records, calls = _records(), {"first_step": 1, "multistep": 2}
    if why == "no_spans":          # a tree without the span, or no session
        records = []
    elif why == "another_count":   # a call the driver did not count
        calls = {"first_step": 1, "multistep": 1}
    elif why == "two_launches":
        records.append(dict(records[0], id=99))
    elif why == "no_launch":
        records = [r for r in records if r["id"] != 1]
    else:
        calls = None
    assert _region_call_self_us(records, calls) is None
