"""Tests of the published benchmark's cell, on the CPU at tiny sizes:
``chipbench/configs/sw3600x28800_solve.json``,
``traffic/runs_multistep10_periodic.json`` and
``drivers/solver_loop_periodic.py``.  No time, rate or share is asserted
here that a chip would give.

What is tested: that the cell's names resolve, by name and not by position;
that ``correct`` comes out true for a sound run and false for the control
(the periodic reference in bfloat16 in the program's place) and for each
planted fault, through the harness's own run; and that what the driver
counts is what ``run_plan`` says of ``pallas2``.
"""

import os
import sys

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
from chipbench import harness  # noqa: E402
from chipbench.reference import shallow_water as periodic_ref  # noqa: E402
from mpi4jax_tpu.utils import profiling  # noqa: E402
from test_chipbench import PEAK, SEED, make_root  # noqa: E402
from test_solve_cell import nothing_carried, one_multistep_fewer  # noqa: E402
from test_walls_cell import _failed, _named  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
CELL = "sw3600x28800_solve.1chip"
CONFIG = "sw3600x28800_solve"
MIX = "runs_multistep10_periodic"
LEGS = "sw3600x28800.1chip"               # the same domain, in legs
WALLED = "sw3600x28800_walls_solve.1chip"  # the same loop, walled
N_ITERS = 3   # the tiny run: 1 + 3 x 10 steps
GAPS = {f"{n}_gap" for n in periodic_ref.FIELDS}


def _tiny():
    config = harness.load_json(os.path.join(BENCH, "configs",
                                            CONFIG + ".json"))
    config.update(nx=36, ny=54)
    config["scaled"]["ny"]["published"] = 18
    traffic = harness.load_json(os.path.join(BENCH, "traffic", MIX + ".json"))
    # the first multiple of ten steps that reaches it is the third
    traffic["run_model_days"] = 25 * periodic_ref.params(config)["dt"] / 86_400
    return config, traffic


@pytest.fixture
def periodic_root(tmp_path):
    config, traffic = _tiny()
    make_root(str(tmp_path), [("tiny.psolve", CELL, "tiny_psolve", config,
                               "tiny_pruns", traffic, 1)])
    return str(tmp_path)


def run_cell(root, hook=None, traced=False):
    return harness.run("tiny.psolve", SEED, 0.2, traced, root=root,
                       devices=jax.devices()[:1], peaks=PEAK,
                       driver_hook=hook)


def _driver_module():
    return harness.load_module(os.path.join(BENCH, "drivers",
                                            "solver_loop_periodic.py"))


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------


def test_the_cells_names_resolve():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = _named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    entry = _named(bench["configs"], CONFIG)
    assert entry["reduced"] == ["ny"] and len(entry["why"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    resolved = harness.resolve_cell(REPO, CELL)
    config, traffic = resolved["config"], resolved["traffic"]
    assert resolved["driver_path"].endswith("drivers/solver_loop_periodic.py")
    assert config["reference"] == "shallow_water"
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    # the legs cell's domain and initial state, key for key
    legs = harness.resolve_cell(REPO, LEGS)["config"]
    for key in ("nx", "ny", "dx", "dy", "gravity", "depth", "coriolis_f",
                "coriolis_beta", "periodic_x", "ab_a", "ab_b", "nproc_y",
                "nproc_x", "dtype", "fast", "reduced", "reference"):
        assert config[key] == legs[key], key
    assert config["periodic_x"] is True
    assert config["scaled"]["ny"]["published"] == 1800
    for key in ("initial_condition", "seeded_modes"):
        assert config["assumed"][key] == legs["assumed"][key]
    # the walled loop's guarantees, less the wall's
    walled = harness.resolve_cell(REPO, WALLED)
    assert config["guarantees"] == [g for g in walled["config"]["guarantees"]
                                    if "wall" not in g]
    assert len(config["guarantees"]) == 3
    assert config["assumed"]["calls_in_flight"]
    assert "wall_flow" not in config["assumed"]
    # the published loop, and the mix that runs it: the walled loop's, with
    # limits of its own
    assert (config["num_multisteps"], config["t1_days"]) == (10, 0.1)
    for key in ("steps_per_call", "run_model_days", "warm_up_runs",
                "trace_runs", "seeded_modes", "mode_amplitude_m"):
        assert traffic[key] == walled["traffic"][key], key
    assert (traffic["steps_per_call"], traffic["run_model_days"],
            traffic["warm_up_runs"], traffic["trace_runs"],
            traffic["seeded_modes"]) == (10, 0.1, 1, 1, 4)
    assert set(traffic["limits"]) == GAPS
    assert "PR 35" in traffic["limits_from"]
    # what it reports: no pinned call, so neither reader of one
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "steps_per_s_per_chip", "setup_s"}
    assert {m["name"] for m in resolved["per_layer"]} == {
        "sw_kernel_roofline", "sw_idle_share", "sw_compiles_in_window"}
    # the driver works out the run from the mix as the program does
    driver = _driver_module().Driver(config, traffic, SEED,
                                     jax.devices()[:1], PEAK)
    cfg = program.Config(nx=3600, ny=28800, periodic_x=True)
    assert driver.n_iters == program.n_multisteps(
        cfg, 0.1 * program.DAY_IN_SECONDS, 10) == 44
    assert driver.steps == 441
    assert program.select_steps("auto", cfg) == (
        program.model_step_pallas, program.model_step2_pallas, 2)


def test_the_published_run_by_run_plan():
    """What ISSUE 35 point 5 asks of ``run_plan`` at the cell's size: the
    carry is the ``State``, so no frame, no crop and no refresh, and a
    ten-step call is five calls of the two-step kernel."""
    plan = program.run_plan(program.Config(nx=3600, ny=28800), "auto", 44, 10)
    assert plan == {
        "calls": 45, "steps": 441, "steps_per_kernel_call": 2,
        "frames_built": 0, "crops": 0,
        "first_step": {"steps": 1, "euler_calls": 1, "chunk_calls": 0,
                       "single_step_calls": 0, "frames_built": 0,
                       "band_refreshes": 0, "crops": 0},
        "multistep": {"steps": 10, "euler_calls": 0, "chunk_calls": 5,
                      "single_step_calls": 0, "frames_built": 0,
                      "band_refreshes": 0, "crops": 0}}
    # what a traced run's ``traced_custom_calls_a_run`` must then read
    assert {"sw_steps_x1_euler": plan["first_step"]["euler_calls"],
            "sw_steps_x2": (plan["calls"] - 1)
            * plan["multistep"]["chunk_calls"]} == {
                "sw_steps_x1_euler": 1, "sw_steps_x2": 220}


# ---------------------------------------------------------------------------
# correct: a sound run, the control, the faults
# ---------------------------------------------------------------------------


def test_periodic_cell_sound_run_is_correct(periodic_root):
    result = run_cell(periodic_root)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"steps_per_s_per_chip", "setup_s"}
    # drivers/solver.py's check: the six gaps and nonfinite, no wall_flow
    assert list(result["compared"]) == [
        f"{n}_gap" for n in periodic_ref.FIELDS] + ["nonfinite"]
    assert result["compared"]["nonfinite"] == [0.0, 0]
    assert "wall_m_per_s" not in result["readings"]
    assert result["readings"]["moved"]["h"] > 0
    counters, runs = result["counters"], result["attempted"]
    assert counters["runs"] == runs
    assert counters["steps"] == (1 + 10 * N_ITERS) * runs
    assert counters["calls"] == {"first_step": runs,
                                 "multistep": N_ITERS * runs}
    assert counters["steps_per_kernel_call"] == 2
    plan = program.run_plan(program.Config(nx=36, ny=54), "auto", N_ITERS, 10)
    assert counters["run_plan"] == plan
    assert (plan["frames_built"], plan["crops"],
            plan["multistep"]["chunk_calls"],
            plan["multistep"]["band_refreshes"]) == (0, 0, 5, 0)
    assert len(counters["run_wall_s"]) == runs
    assert len(counters["warm_up_run_s"]) == 1
    assert set(counters["setup_stages"]) >= {"state_s", "compile_s",
                                             "warm_up_s"}


def test_periodic_cell_traced_run_counts_its_calls(periodic_root,
                                                   monkeypatch):
    """No device plane on the CPU, so no share and no
    ``traced_custom_calls_a_run``; the program's spans are there: as many
    ``mpx.region_call`` as the driver counts calls and ``run_plan`` plans,
    under the two programs' names in the run's order."""
    monkeypatch.setattr(harness, "ROOT", periodic_root)
    result = run_cell(periodic_root, traced=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 1  # the mix's trace_runs
    assert set(result["metrics"]) == {"sw_compiles_in_window"}
    assert result["metrics"]["sw_compiles_in_window"]["value"] == 0.0
    counters = result["counters"]
    assert "traced_custom_calls_a_run" not in counters
    region_calls = [r for r in profiling.spans()
                    if r["name"] == "mpx.region_call"]
    assert len(region_calls) == sum(counters["calls"].values()) == \
        counters["run_plan"]["calls"]
    assert [r["attrs"]["program"] for r in region_calls] == \
        ["first_step"] + ["multistep"] * N_ITERS


def _reference_in_the_programs_place(precision):
    def hook(driver):
        fields = periodic_ref.make_run(driver.params, driver.steps,
                                       precision)(*driver.initial_fields())
        out = [periodic_ref.with_halo_columns(a)[None] for a in fields]
        driver.run_multisteps = lambda _f, _m, state, *_a: type(state)(*out)
    return hook


def test_periodic_control_bfloat16_is_not_correct(periodic_root):
    """The control: the periodic reference in bfloat16 in the program's
    place, through the harness's own run.  The float32 reference there is
    correct, so it is the precision that fails."""
    result = run_cell(periodic_root,
                      _reference_in_the_programs_place(jnp.bfloat16))
    assert result["correct"] is False
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"h_gap", "u_gap", "v_gap"} <= _failed(result)
    value, limit = result["compared"]["h_gap"]
    assert value > 100 * limit
    sound = run_cell(periodic_root,
                     _reference_in_the_programs_place(jnp.float32))
    assert sound["correct"] is True


@pytest.mark.parametrize("fault", [one_multistep_fewer, nothing_carried])
def test_periodic_faults_come_out_not_correct(periodic_root, fault):
    result = run_cell(periodic_root, fault)
    assert not result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"h_gap", "u_gap", "v_gap"} <= _failed(result)
