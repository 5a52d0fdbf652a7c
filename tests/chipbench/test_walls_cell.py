"""Tests of the closed-basin cell's own files, on the CPU at tiny sizes:
``chipbench/configs/sw3600x28800_walls.json``, ``traffic/legs_walls.json``,
``drivers/solver_runner.py``, ``reference/shallow_water_walls.py`` and the
reader ``layer_metrics/sw_frame_share.py``.  No time, rate or share is
asserted here that a chip would give.

What is tested: that the cell's names resolve; the walled reference against
the program's reference-structured ``model_step``; and that ``correct``
comes out true for a sound run and false for the control (the reference in
bfloat16 in the program's place) and for each planted fault, through the
harness's own run.
"""

import ast
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (REPO, os.path.join(REPO, "examples"),
             os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import mpi4jax_tpu as mpx  # noqa: E402
import shallow_water as program  # noqa: E402
from chipbench import harness, trace_reduce, work  # noqa: E402
from chipbench.reference import shallow_water_walls as walls_ref  # noqa: E402
from test_chipbench import PEAK, SEED, make_root  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
CELL = "sw3600x28800_walls.1chip"
STEPS = 11
MS = 1_000_000


def _tiny():
    config = harness.load_json(os.path.join(
        BENCH, "configs", "sw3600x28800_walls.json"))
    config.update(nx=48, ny=72)
    config["scaled"]["ny"]["published"] = 24
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "legs_walls.json"))
    traffic["steps_per_leg"] = STEPS
    return config, traffic


@pytest.fixture
def walls_root(tmp_path):
    config, traffic = _tiny()
    make_root(str(tmp_path), [("tiny.walls", CELL, "tiny_walls", config,
                               "tiny_legs_walls", traffic, 1)])
    return str(tmp_path)


def run_walls(root, hook=None, traced=False):
    return harness.run("tiny.walls", SEED, 0.2, traced, root=root,
                       devices=jax.devices()[:1], peaks=PEAK,
                       driver_hook=hook)


def _failed(result):
    return {n for n, (v, lim) in result["compared"].items() if not v <= lim}


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------


SW_READERS = {"sw_kernel_roofline", "sw_idle_share", "sw_compiles_in_window",
              "sw_call_self_us", "sw_first_op_delay_us"}


def _named(entries, name):
    """The one entry of a list of ``BENCHMARK.json`` under ``name``, wherever
    it stands: the lists grow at their ends, PR by PR."""
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_the_cells_names_resolve():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = _named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sw3600x28800_walls", "legs_walls", 1)
    entry = _named(bench["configs"], "sw3600x28800_walls")
    assert entry["reduced"] == ["ny"]
    resolved = harness.resolve_cell(REPO, CELL)
    config, traffic = resolved["config"], resolved["traffic"]
    assert resolved["driver_path"].endswith("drivers/solver_runner.py")
    assert os.path.isfile(os.path.join(
        BENCH, "reference", config["reference"] + ".py"))
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert (config["nx"], config["ny"], config["periodic_x"], config["fast"],
            config["scaled"]["ny"]["published"]) == (
                3600, 28800, False, "auto", 1800)
    assert len(config["guarantees"]) == 3 and "wall_flow" in config["assumed"]
    assert traffic["limits"]["wall_flow"] == 0
    assert set(traffic["limits"]) == {
        f"{n}_gap" for n in walls_ref.FIELDS} | {"wall_flow"}
    # the sibling's numbers, so the two cells set wide2 beside pallas2
    sibling = harness.load_json(os.path.join(BENCH, "traffic",
                                             "legs_periodic.json"))
    for key in ("steps_per_leg", "warm_up_legs", "seeded_modes",
                "mode_amplitude_m", "trace_legs"):
        assert traffic[key] == sibling[key], key
    # it reports the solver's end-to-end metrics and is read by the
    # solver's readers; what later PRs enter beside them is theirs
    assert {"steps_per_s_per_chip", "setup_s"} <= {
        m["name"] for m in resolved["end_to_end"]}
    assert SW_READERS <= {m["name"] for m in resolved["per_layer"]}
    # what auto gives this configuration, and what a leg of it is made of
    cfg = program.Config(nx=3600, ny=28800, periodic_x=False)
    assert program.select_steps("auto", cfg)[1] is program.model_step2_wide
    plan = program.leg_plan(cfg, "auto", traffic["steps_per_leg"])
    assert (plan["euler_calls"], plan["chunk_calls"],
            plan["single_step_calls"], plan["frames_built"],
            plan["crops"]) == (1, 35, 0, 1, 1)


def test_the_walled_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "reference", "shallow_water_walls.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            roots.add((node.module or ".").split(".")[0])
    assert roots == {"math", "numpy", "jax"}


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def test_walled_initial_state_is_the_published_one_in_its_place():
    config, _ = _tiny()
    p = walls_ref.params(config)
    h, u, v = (np.asarray(a) for a in walls_ref.initial_fields(p, SEED))
    assert h.shape == (74, 50) and h.dtype == np.float32
    published = program.initial_state(
        program.Config(nx=48, ny=24, periodic_x=False))
    # border cells included; the seeded modes stay under the published 0.2 m
    assert np.abs(h[:26] - np.asarray(published.h)[0]).max() <= 0.2
    np.testing.assert_allclose(u[:26], np.asarray(published.u)[0],
                               rtol=1e-6, atol=1e-12)
    # the walls are left to the first step: the jet still blows through
    assert u[1:-1, -2].max() > 9.0
    assert not v.any() and h.min() > 0
    assert walls_ref.with_halo_columns(h) is h
    with pytest.raises(ValueError, match="closed basin"):
        walls_ref.params(dict(config, periodic_x=True))


@pytest.mark.parametrize("seed", [SEED, 5, 2 ** 31 + 4099])
def test_walled_reference_agrees_with_model_step(seed):
    """48 x 24 closed basin, the reference-structured ``model_step`` (the
    parity oracle): twelve steps apart by rounding only, border cells and
    all, and both hold the friction increment and no more on the walls."""
    config, _ = _tiny()
    config.update(ny=24)
    p = walls_ref.params(config)
    h, u, v = walls_ref.initial_fields(p, seed)
    cfg = program.Config(nx=48, ny=24, periodic_x=False)
    _mesh, comm = program.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    zero = jnp.zeros((1, 26, 50), jnp.float32)
    state = program.State(h[None], u[None], v[None], zero, zero, zero)

    @mpx.spmd(comm=comm)
    def run(state):
        state = program.model_step(state, cfg, comm, True)
        for _ in range(11):
            state = program.model_step(state, cfg, comm, False)
        return state

    got = run(state)
    want = dict(zip(walls_ref.FIELDS,
                    (np.asarray(b) for b in walls_ref.make_run(p, 12)(h, u,
                                                                      v))))
    speed = max(np.abs(want["u"]).max(), np.abs(want["v"]).max())
    scale = {"h": np.abs(want["h"]).max(), "u": speed, "v": speed}
    for n in ("h", "u", "v"):
        scale["d" + n] = scale[n] / p["dt"]  # as the cell's check scales
    for name, a in zip(walls_ref.FIELDS, got):
        gap = np.abs(np.asarray(a)[0] - want[name]).max()
        assert gap <= 1e-6 * scale[name], name
    assert np.abs(want["h"] - np.asarray(h)).max() > 1.0  # it moved
    # the ring: the state's never changes, the tendencies' stays zero
    for a, b in ((want["h"], h), (want["u"], u), (want["v"], v)):
        b = np.asarray(b)
        for edge in (np.s_[0], np.s_[-1], np.s_[:, 0], np.s_[:, -1]):
            assert (a[edge] == b[edge]).all()
    assert not want["dh"][0].any() and not want["du"][:, -1].any()
    # the walls: one friction increment, not zero, and far under the jet
    most = walls_ref.friction_increment(p) * speed
    east, north = want["u"][1:-1, -2], want["v"][-2, 1:-1]
    assert 0 < np.abs(east).max() <= most
    assert np.abs(north).max() <= most


# ---------------------------------------------------------------------------
# correct: a sound run, the control, the faults
# ---------------------------------------------------------------------------


def test_walls_cell_sound_run_is_correct(walls_root):
    result = run_walls(walls_root)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"steps_per_s_per_chip", "setup_s"}
    assert result["compared"]["wall_flow"] == [0.0, 0]
    assert list(result["compared"])[-2:] == ["wall_flow", "nonfinite"]
    counters = result["counters"]
    assert counters["steps"] == STEPS * result["attempted"]
    assert counters["steps_per_kernel_call"] == 2
    assert counters["leg_plan"] == program.leg_plan(
        program.Config(nx=48, ny=72, periodic_x=False), "auto", STEPS)
    walls = result["readings"]["wall_m_per_s"]
    assert 0 < walls["east_u"] < walls["most"]


def test_walls_cell_traced_run_reports_what_it_can_on_the_cpu(
        walls_root, monkeypatch):
    """No device plane on the CPU: the counter is in the line, the shares
    are not, and the driver, which reads the trace the harness has just
    closed, finds no custom call to count."""
    read = []
    read_xplane = trace_reduce.read_xplane
    monkeypatch.setattr(harness, "ROOT", walls_root)  # where the driver looks
    monkeypatch.setattr(
        trace_reduce, "read_xplane",
        lambda path, names=(): read.append(path) or read_xplane(path, names))
    result = run_walls(walls_root, traced=True)
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == {"sw_compiles_in_window"}
    assert result["metrics"]["sw_compiles_in_window"]["value"] == 0.0
    assert len(read) == 2 and read[0] == read[1]  # the driver, the harness
    assert "traced_custom_calls_a_leg" not in result["counters"]
    assert "leg_plan" in result["counters"]


def test_the_traced_custom_calls_are_counted_by_instruction():
    """What the driver puts beside ``leg_plan`` in a traced line: every
    custom call of the legs under its instruction's name, XLA's own too, so
    that one that ran as often as the loop's kernel shows."""
    runner = _runner()
    assert runner.custom_calls_a_leg(_wide_legs(), 2) == {
        "custom-call": 3.0, "sw_wide_x1_euler": 1.0, "sw_wide_x2": 3.0}
    raw = _wide_legs()
    raw["devices"] = {}
    assert runner.custom_calls_a_leg(raw, 2) is None


def _reference_in_the_programs_place(precision):
    def hook(driver):
        fields = walls_ref.make_run(driver.params, driver.steps, precision)(
            *driver.initial_fields())
        out = [a[None] for a in fields]
        driver.program = lambda state: type(state)(*out)
    return hook


def test_walls_control_bfloat16_is_not_correct(walls_root):
    """The control: the walled reference in bfloat16 in the program's
    place, through the harness's own run.  The float32 reference there is
    correct, so it is the precision that fails."""
    result = run_walls(walls_root,
                       _reference_in_the_programs_place(jnp.bfloat16))
    assert result["correct"] is False
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {"h_gap", "u_gap", "v_gap"} <= _failed(result)
    value, limit = result["compared"]["h_gap"]
    assert value > 100 * limit
    sound = run_walls(walls_root,
                      _reference_in_the_programs_place(jnp.float32))
    assert sound["correct"] is True


@pytest.mark.parametrize("fault", ["wall_condition_dropped",
                                   "one_step_fewer", "state_unchanged"])
def test_walls_faults_come_out_not_correct(walls_root, monkeypatch, fault):
    def without_walls(cfg, first_step, giy, gix, fields, roll):
        """``_wide_step_window`` less its post-integration wall
        conditions (the fluxes through the walls stay zero)."""
        h1, u1, v1, dh_n, du_n, dv_n = program._phase1_window(
            cfg, first_step, giy, gix, giy, gix, fields, roll, wide=True)
        u1, v1 = program._phase2_window(cfg, giy, gix, giy, gix, u1, v1,
                                        roll, wide=True)
        return h1, u1, v1, dh_n, du_n, dv_n

    def hook(driver):
        if fault == "wall_condition_dropped":
            monkeypatch.setattr(program, "_wide_step_window", without_walls)
            driver.program = mpx.compile(driver.fused, driver.state,
                                         driver.steps - 1)
        elif fault == "one_step_fewer":
            driver.program = mpx.compile(driver.fused, driver.state,
                                         driver.steps - 2)
        else:
            driver.program = lambda state: type(state)(*state)

    result = run_walls(walls_root, hook)
    assert not result["correct"], result["compared"]
    assert result["attempted"] >= 1
    if fault == "wall_condition_dropped":
        # the gaps alone would pass it at this size: the walls' own number
        # is what catches it
        assert "wall_flow" in _failed(result), result["compared"]
        assert result["compared"]["wall_flow"][0] > 0


# ---------------------------------------------------------------------------
# the reader of the frame's share
# ---------------------------------------------------------------------------

FRAME = "f32[28832,3632]{1,0:T(8,128)}"
SIX = "(" + ", ".join([FRAME] * 6) + ")"
EULER = f"%sw_wide_x1_euler.1 = {SIX} custom-call({FRAME} %concatenate.21)"
PAIR = f"%sw_wide_x2.3 = {SIX} custom-call({FRAME} %copy.51)"
COPY = f"%copy.51 = {FRAME} copy({FRAME} %dynamic-update-slice.99)"
BAND = (f"%dynamic-update-slice.99 = {FRAME} dynamic-update-slice("
        f"{FRAME} %gte, f32[28832,15]{{1,0}} %broadcast.3)")
BUILD = f"%concatenate.21 = {FRAME} concatenate(f32[15,3632]{{1,0}} %b)"
CROP = f"%slice_fusion.2 = f32[28802,3602]{{1,0}} fusion({FRAME} %gte.9)"
GLUE = (f"%custom-call.3 = {FRAME} custom-call(f32[28832,15]{{1,0}} %s), "
        'custom_call_target="ConcatBitcast"')
LOOP = f"%while.33 = (s32[], {FRAME}) while((s32[]) %tuple)"


def _wide_legs(pairs_a_leg=3):
    """Two legs of 100 ms on one device, each: the frame built (2 ms), the
    Euler-step kernel (8 ms), a loop of ``pairs_a_leg`` iterations of a
    band update (1 ms), a copy of the carry (2 ms), a custom call of XLA's
    own (1 ms: no kernel, and not the frame's by its kind) and the pair
    kernel (20 ms), and the crop (2 ms, a fusion: not the frame's
    either)."""
    device = []
    for leg in (0, 200 * MS):
        device += [(BUILD, leg + 1 * MS, 2 * MS),
                   (EULER, leg + 3 * MS, 8 * MS),
                   (LOOP, leg + 11 * MS, 24 * pairs_a_leg * MS),
                   (CROP, leg + (11 + 24 * pairs_a_leg) * MS, 2 * MS)]
        for i in range(pairs_a_leg):
            at = leg + (11 + 24 * i) * MS
            device += [(BAND, at, 1 * MS), (COPY, at + 1 * MS, 2 * MS),
                       (GLUE, at + 3 * MS, 1 * MS),
                       (PAIR, at + 4 * MS, 20 * MS)]
    host = [(trace_reduce.WINDOW_SPAN, 0, 400 * MS)]
    for leg in (0, 200 * MS):
        host += [("dispatch_leg", leg, 1 * MS),
                 ("wait_leg", leg + 1 * MS, 99 * MS)]
    return {"devices": {0: device}, "host": host}


def _runner():
    return harness.load_module(os.path.join(BENCH, "drivers",
                                            "solver_runner.py"))


def _frame_share(raw, plan):
    """The reader on the counters the driver would have given it."""
    counters = {"legs": 2}
    if plan is not None:
        counters["leg_plan"] = plan
    calls = _runner().custom_calls_a_leg(raw, 2)
    if calls is not None:
        counters["traced_custom_calls_a_leg"] = calls
    ctx = {"trace": trace_reduce.reduce_events(raw), "config": {},
           "traffic": {}, "peaks": {}, "chips": 1, "work": work,
           "reduce": trace_reduce, "counters": counters}
    return harness.load_module(os.path.join(
        BENCH, "layer_metrics", "sw_frame_share.py")).read(ctx)


def test_frame_share_of_the_legs_busy_time():
    plan = {"frames_built": 1, "euler_calls": 1, "chunk_calls": 3,
            "single_step_calls": 0}
    # 2 (build) + 3 x (1 + 2) of 2 + 8 + 72 + 2 = 84 ms busy a leg
    assert _frame_share(_wide_legs(), plan) == pytest.approx(100 * 11 / 84)


@pytest.mark.parametrize("why", ["no_plan", "no_frame", "another_count",
                                 "no_device", "no_leg"])
def test_frame_share_reports_nothing_it_cannot_stand_behind(why):
    plan = {"frames_built": 1, "euler_calls": 1, "chunk_calls": 3,
            "single_step_calls": 0}
    raw = _wide_legs()
    if why == "no_plan":        # a tree without leg_plan
        plan = None
    elif why == "no_frame":     # a leg of the whole-step kernel
        plan = dict(plan, frames_built=0)
    elif why == "another_count":  # the trace is not of the planned leg
        raw = _wide_legs(pairs_a_leg=2)
    elif why == "no_device":
        raw["devices"] = {}
    else:
        raw["host"] = [h for h in raw["host"] if not h[0].endswith("_leg")]
    assert _frame_share(raw, plan) is None
