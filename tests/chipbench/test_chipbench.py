"""Tests of the benchmark's own files (``chipbench/``), on the CPU at tiny
sizes.  No time, rate or share is asserted here: those come from the chip.

What is tested: that names resolve to files and a new cell needs new files
only; the arithmetic (bytes, bus factors, trace reduction); the device
gate; the plain references; and that ``correct`` comes out false for the
control (the reference one precision down in the program's place) and for
each fault a cell can have, driven through the harness's own run.
"""

import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness, trace_reduce, work  # noqa: E402
from chipbench.reference import collectives as coll_ref  # noqa: E402
from chipbench.reference import shallow_water as sw_ref  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
PEAK = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
SEED = 2 ** 31 + 17  # the driver's seeds are large


# ---------------------------------------------------------------------------
# a checkout in a temporary directory: the existing files linked, not copied
# ---------------------------------------------------------------------------


def _tiny_solver():
    config = harness.load_json(os.path.join(BENCH, "configs",
                                            "sw3600x28800.json"))
    config.update(nx=36, ny=54)
    config["scaled"]["ny"]["published"] = 18
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "legs_periodic.json"))
    traffic["steps_per_leg"] = 11
    return config, traffic


def _tiny_sweep():
    config = harness.load_json(os.path.join(BENCH, "configs",
                                            "osu_collectives_2x2.json"))
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "sweep_world.json"))
    for p in traffic["programs"]:
        p["block_elems"] = min(p["block_elems"], 256)
        p["chain"] = 5 if p["chain"] % 2 == 0 else 6
    traffic["sample"] = 64
    return config, traffic


def make_root(tmp, cells, extra_metrics=()):
    """``cells``: ``(workload, like, config_name, config, traffic_name,
    traffic, chips)``; ``like`` is the real cell whose metrics the new cell
    reports."""
    base = os.path.join(tmp, "chipbench")
    for d in ("configs", "traffic", "drivers", "layer_metrics"):
        os.makedirs(os.path.join(base, d))
        for f in os.listdir(os.path.join(BENCH, d)):
            os.symlink(os.path.join(BENCH, d, f), os.path.join(base, d, f))
    os.symlink(os.path.join(BENCH, "peaks.json"),
               os.path.join(base, "peaks.json"))
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    for wl, like, cname, config, tname, traffic, chips in cells:
        with open(os.path.join(base, "configs", cname + ".json"), "w") as f:
            json.dump(config, f)
        with open(os.path.join(base, "traffic", tname + ".json"), "w") as f:
            json.dump(traffic, f)
        bench["configs"].append({
            "name": cname, "source": "test", "reduced": [], "why": "test",
            "file": f"chipbench/configs/{cname}.json"})
        bench["workloads"].append({"name": wl, "config": cname,
                                   "traffic": tname, "chips": chips,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(wl)
    bench["per_layer"] += list(extra_metrics)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return base


@pytest.fixture
def solver_root(tmp_path):
    config, traffic = _tiny_solver()
    make_root(str(tmp_path), [("tiny.sw", "sw3600x28800.1chip", "tiny_sw",
                               config, "tiny_legs", traffic, 1)])
    return str(tmp_path)


@pytest.fixture
def sweep_root(tmp_path):
    config, traffic = _tiny_sweep()
    make_root(str(tmp_path), [("tiny.osu", "osu_2x2.4chip", "tiny_osu",
                               config, "tiny_sweep", traffic, 4)])
    return str(tmp_path)


def run_solver(root, hook=None, traced=False):
    return harness.run("tiny.sw", SEED, 0.2, traced, root=root,
                       devices=jax.devices()[:1], peaks=PEAK,
                       driver_hook=hook)


def run_sweep(root, hook=None):
    return harness.run("tiny.osu", SEED, 0.2, False, root=root,
                       devices=jax.devices()[:4], peaks=PEAK,
                       driver_hook=hook)


# ---------------------------------------------------------------------------
# BENCHMARK.json and the data files
# ---------------------------------------------------------------------------


def test_benchmark_json_names_resolve_to_files():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for cell in bench["workloads"]:
        resolved = harness.resolve_cell(REPO, cell["name"])
        assert os.path.isfile(resolved["driver_path"])
        assert len(resolved["end_to_end"]) >= 2  # setup_s and one more
        assert resolved["per_layer"]
        for metric in resolved["per_layer"]:
            assert metric["moves"] in e2e
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", metric["name"] + ".py"))
        config = resolved["config"]
        assert config["reduced"] == next(
            c["reduced"] for c in bench["configs"]
            if c["name"] == cell["config"])
        assert config["guarantees"]
        traffic = resolved["traffic"]
        assert traffic.get("limits") or all(
            "limit" in p for p in traffic["programs"])
    sw = harness.resolve_cell(REPO, "sw3600x28800.1chip")["config"]
    assert (sw["nx"], sw["scaled"]["ny"]["published"]) == (3600, 1800)


def test_a_new_cell_is_new_files_only(tmp_path):
    """A configuration, a traffic mix, a driver and a per-layer metric
    that no existing file knows of, found by name."""
    base = make_root(
        str(tmp_path),
        [("toy.cell", "-", "toy_config", {"driver": "toy", "n": 3},
          "toy_mix", {"calls": 4}, 1)],
        extra_metrics=[{"name": "toy.calls-made", "unit": "count",
                        "better": "higher", "source": "program_counter",
                        "layer": "Toy", "moves": "setup_s",
                        "workloads": ["toy.cell"]}])
    with open(os.path.join(base, "drivers", "toy.py"), "w") as f:
        f.write(textwrap.dedent('''
            import jax.numpy as jnp
            class Driver:
                def __init__(self, config, traffic, seed, devices, peaks):
                    self.n, self.calls = config["n"], traffic["calls"]
                def setup(self): pass
                def compile_count(self): return 0
                def window(self, seconds, traced):
                    self.out = [float(jnp.sum(jnp.ones(self.n)))
                                for _ in range(self.calls)]
                    return {"attempted": self.calls, "failed": 0,
                            "end_to_end": {}, "span_names": [],
                            "counters": {"made": len(self.out)}}
                def release(self): pass
                def check(self):
                    return [{"name": "sum", "limit": 0.0,
                             "value": abs(self.out[-1] - self.n)}]
        '''))
    with open(os.path.join(base, "layer_metrics", "toy.calls-made.py"),
              "w") as f:
        f.write("def read(ctx):\n    return ctx['counters']['made']\n")
    result = harness.run("toy.cell", SEED, 0.1, True, root=str(tmp_path),
                         devices=jax.devices()[:1], peaks=PEAK)
    assert result["correct"] and result["attempted"] == 4
    assert result["metrics"] == {
        "toy.calls-made": {"value": 4.0, "unit": "count"}}
    plain = harness.run("toy.cell", SEED, 0.1, False, root=str(tmp_path),
                        devices=jax.devices()[:1], peaks=PEAK)
    assert set(plain["metrics"]) == {"setup_s"}
    assert list(plain)[-1] == "compared"


def test_a_reader_with_nothing_to_read_is_left_out(solver_root):
    """On the CPU a trace has no device plane: the roofline and the idle
    share return nothing and are not in the line; the counter is."""
    result = run_solver(solver_root, traced=True)
    assert set(result["metrics"]) == {"sw_compiles_in_window"}
    assert result["metrics"]["sw_compiles_in_window"]["value"] == 0.0
    assert result["correct"]


# ---------------------------------------------------------------------------
# the device gate
# ---------------------------------------------------------------------------


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_gate_refuses_what_is_not_a_known_tpu():
    peaks = harness.load_json(os.path.join(BENCH, "peaks.json"))
    tpu = _device("tpu", "TPU v5 lite")
    devices, peak = harness.check_devices([tpu] * 4, 4, peaks)
    assert len(devices) == 4 and peak["hbm_bytes_per_s"] == 819e9
    assert peak["ici_bytes_per_s"] == 200e9 and peak["hbm_bytes"] == 16e9
    for bad, chips in (([_device("cpu", "cpu")], 1),
                       ([_device("tpu", "TPU v9")], 1),
                       ([tpu], 4)):
        with pytest.raises(SystemExit) as refused:
            harness.check_devices(bad, chips, peaks)
        assert refused.value.code == 2


def test_run_on_the_cpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "sw3600x28800.1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def test_bus_bytes_and_solver_bytes_by_hand():
    assert work.bus_factor("allreduce", 4) == 1.5
    for op in ("allgather", "reduce_scatter", "alltoall"):
        assert work.bus_factor(op, 4) == 0.75
    assert work.bus_factor("sendrecv", 4) == 1.0
    gib = 2 ** 30
    assert work.bus_bytes("allreduce", 2 ** 28, 4) == 1.5 * gib
    assert work.bus_bytes("sendrecv", 2 ** 28, 4) == gib
    # k blocks of 256 MiB: the 1 GiB buffer
    assert work.buffer_bytes("allgather", 2 ** 26, 4) == gib
    assert work.bus_bytes("alltoall", 2 ** 26, 4) == 0.75 * gib
    assert work.bus_bytes("reduce_scatter", 1, 4) == 12.0
    # one field of the cell: 3602 x 28802 x 4 B
    assert work.solver_field_bytes(3600, 28800) == 414_979_216
    assert work.solver_step_bytes(3600, 28800) == 12 * 414_979_216
    assert work.solver_field_bytes(3600, 1800) == 3602 * 1802 * 4


def test_trace_reduce_on_a_synthetic_trace():
    raw = {
        "devices": {
            0: [("%while.1 = (s32[]{:T(128)}, f32[8]{0:T(1024)}) while((s32[], "
                 "f32[8]) %tuple)", 100, 800),    # encloses the next two
                ("%psum_invariant.3 = f32[8]{0:T(1024)} all-reduce(f32[8] %x)",
                 100, 300),
                ("%multiply_fusion.7 = f32[8]{0:T(1024)} fusion(f32[8] %y)",
                 500, 400),
                ("%copy.2", 1200, 100)],
            1: [("%psum_invariant.3 = f32[8]{0:T(1024)} all-reduce(f32[8] %x)",
                 150, 350)],
        },
        "host": [(trace_reduce.WINDOW_SPAN, 0, 2000),
                 ("dispatch_a", 0, 90), ("wait_a", 90, 1000),
                 ("dispatch_b", 1100, 60), ("wait_b", 1160, 700)],
    }
    trace = trace_reduce.reduce_events(raw)
    assert trace["window_ns"] == (0, 2000)
    assert trace["devices"][0]["busy"] == [(100, 900), (1200, 1300)]
    # device 0 busy 900 ns, device 1 busy 350 ns
    assert trace["busy_s"] == pytest.approx(625e-9)
    assert trace["window_s"] == pytest.approx(2000e-9)
    self_ns = {trace_reduce.op_kind(n): s
               for n, _a, _b, s in trace["devices"][0]["ops"]}
    assert self_ns == {"while": 100, "all-reduce": 300,
                       "multiply_fusion": 400, "copy": 100}
    assert trace_reduce.top_ops(trace, 2) == [
        ["all-reduce", pytest.approx(325e-9)],
        ["multiply_fusion", pytest.approx(200e-9)]]
    # idle of device 0: 0-100 under dispatch_a (90 of it), 900-1200 under
    # wait_a (190) against dispatch_b (60), 1300-2000 under wait_b
    assert trace_reduce.idle_gaps(trace) == [
        ["wait_b", pytest.approx(700e-9)], ["wait_a", pytest.approx(300e-9)],
        ["dispatch_a", pytest.approx(100e-9)]]
    a = trace_reduce.spans_named(trace, "wait_a")
    assert trace_reduce.ops_within(trace, a, ("all-reduce",)) == [
        (0, "all-reduce", 300), (1, "all-reduce", 350)]
    assert trace_reduce.busy_within(trace, a) == pytest.approx(
        (800 + 350) / 2 * 1e-9)
    assert trace_reduce.op_kind("%collective-permute-done.12") == \
        "collective-permute-done"
    assert trace_reduce.op_kind(
        "%copy-start = (f32[4]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start(f32[4] "
        "%p)") == "copy-start"
    assert trace_reduce.op_kind(
        "%closed_call.4 = (f32[1,130,3602]{2,1,0:T(8,128)}) custom-call("
        "f32[1,130,3602] %a)") == "custom-call"


# ---------------------------------------------------------------------------
# the plain references
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def program():
    examples = os.path.join(REPO, "examples")
    if examples not in sys.path:
        sys.path.insert(0, examples)
    import shallow_water

    return shallow_water


def test_initial_state_is_the_published_one_in_its_place(program):
    config, _ = _tiny_solver()
    p = sw_ref.params(config)
    h, u, v = (np.asarray(a) for a in sw_ref.initial_fields(p, SEED))
    assert h.shape == (56, 36) and h.dtype == np.float32
    published = program.initial_state(program.Config(nx=36, ny=18))
    # the seeded modes stay under the published 0.2 m perturbation
    assert np.abs(h[:20] - np.asarray(published.h)[0, :, 1:-1]).max() <= 0.2
    np.testing.assert_allclose(u[:20], np.asarray(published.u)[0, :, 1:-1],
                               rtol=1e-6, atol=1e-12)
    assert not v.any() and h.min() > 0
    # another seed, another state; the same seed, the same state
    again = np.asarray(sw_ref.initial_fields(p, SEED)[0])
    other = np.asarray(sw_ref.initial_fields(p, SEED + 1)[0])
    assert (again == h).all() and (other != h).any()
    full = np.asarray(sw_ref.with_halo_columns(jnp.asarray(h)))
    assert (full[:, 0] == h[:, -1]).all() and (full[:, -1] == h[:, 0]).all()


def test_plain_solver_reference_agrees_with_model_step(program):
    """36 x 18 grid, the reference-structured ``model_step``: eleven steps
    apart by rounding only."""
    import mpi4jax_tpu as mpx

    config, _ = _tiny_solver()
    config.update(ny=18)
    p = sw_ref.params(config)
    h, u, v = sw_ref.initial_fields(p, SEED)
    cfg = program.Config(nx=36, ny=18)
    _mesh, comm = program.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    zero = jnp.zeros((1, 20, 38), jnp.float32)
    state = program.State(
        *(sw_ref.with_halo_columns(a)[None] for a in (h, u, v)),
        zero, zero, zero)

    @mpx.spmd(comm=comm)
    def run(state):
        state = program.model_step(state, cfg, comm, True)
        for _ in range(10):
            state = program.model_step(state, cfg, comm, False)
        return state

    got = run(state)
    want = sw_ref.make_run(p, 11)(h, u, v)
    want = {n: np.asarray(b)[1:-1] for n, b in zip(sw_ref.FIELDS, want)}
    speed = max(np.abs(want["u"]).max(), np.abs(want["v"]).max())
    scale = {"h": np.abs(want["h"]).max(), "u": speed, "v": speed}
    for n in ("h", "u", "v"):
        scale["d" + n] = scale[n] / p["dt"]  # as the cell's check scales
    for name, a in zip(sw_ref.FIELDS, got):
        gap = np.abs(np.asarray(a)[0, 1:-1, 1:-1] - want[name]).max()
        assert gap <= 1e-6 * scale[name], name
    moved = np.abs(want["h"] - np.asarray(h)[1:-1]).max()
    assert moved > 1e-2  # the comparison is not of a state that stood still


def test_collective_reference_by_hand():
    x = np.array([[1.0], [2.0], [3.0], [6.0]], np.float32)
    g = coll_ref.GROWTH
    np.testing.assert_allclose(
        coll_ref.run_chain("allreduce", x, 2), np.full((4, 1), 3 * g * g),
        rtol=1e-6)
    np.testing.assert_array_equal(
        coll_ref.run_chain("sendrecv", x, 1), np.roll(x, 1, axis=0))
    np.testing.assert_array_equal(coll_ref.run_chain("sendrecv", x, 4), x)
    coef = coll_ref.coefficients(4)
    np.testing.assert_allclose(
        coll_ref.run_chain("allgather", x, 1),
        np.full((4, 1), (x[:, 0] * coef).sum()), rtol=1e-6)
    blocks = np.arange(16, dtype=np.float32).reshape(4, 4, 1)
    np.testing.assert_allclose(
        coll_ref.run_chain("alltoall", blocks, 1),
        np.swapaxes(blocks, 0, 1) * g, rtol=1e-6)
    out = coll_ref.run_chain("reduce_scatter", blocks, 1)
    # rank r holds the sum of every rank's block r, spread over k blocks
    np.testing.assert_allclose(out[2, :, 0], blocks[:, 2, 0].sum() * coef,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# correct: sound runs, the control, the faults
# ---------------------------------------------------------------------------


def _compared(result):
    return {n: (v, lim) for n, (v, lim) in result["compared"].items()}


def test_solver_cell_sound_run_is_correct(solver_root):
    result = run_solver(solver_root)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"steps_per_s_per_chip", "setup_s"}
    assert result["counters"]["steps"] == 11 * result["attempted"]
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]


def _reference_in_the_programs_place(precision):
    """A hook that puts the plain reference, computed in ``precision``, in
    the place of the pinned program: every leg of the window returns it."""
    def hook(driver):
        fields = sw_ref.make_run(driver.params, driver.steps, precision)(
            *driver.initial_fields())
        out = [sw_ref.with_halo_columns(a)[None] for a in fields]
        driver.program = lambda state: type(state)(*out)
    return hook


def test_solver_control_bfloat16_is_not_correct(solver_root):
    """The control: the reference in bfloat16 in the program's place,
    through the harness's own run.  The float32 reference there is
    correct, so it is the precision that fails."""
    result = run_solver(solver_root,
                        _reference_in_the_programs_place(jnp.bfloat16))
    assert result["correct"] is False
    assert result["attempted"] >= 1 and result["failed"] == 0
    failed = {n for n, (v, lim) in result["compared"].items()
              if not v <= lim}
    assert {"h_gap", "u_gap", "v_gap"} <= failed
    value, limit = result["compared"]["h_gap"]
    assert value > 100 * limit
    sound = run_solver(solver_root,
                       _reference_in_the_programs_place(jnp.float32))
    assert sound["correct"] is True


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "not_a_number"])
def test_solver_faults_come_out_not_correct(solver_root, fault):
    def hook(driver):
        sound = driver.program

        def unchanged(state):
            return type(state)(*state)

        def altered(state):
            out = sound(state)
            return out._replace(u=out.u.at[0, 7, 9].add(0.01))

        def not_a_number(state):
            out = sound(state)
            return out._replace(h=out.h.at[0, 3, 3].set(jnp.nan))

        driver.program = {"state_unchanged": unchanged,
                          "answer_altered": altered,
                          "not_a_number": not_a_number}[fault]

    result = run_solver(solver_root, hook)
    assert not result["correct"], result["compared"]
    assert result["attempted"] >= 1


def test_sweep_cell_sound_run_is_correct(sweep_root):
    result = run_sweep(sweep_root)
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == {"large_busbw_GBps", "small_lat_us",
                                      "setup_s"}
    assert len(result["compared"]) == 15
    sets = result["counters"]["sets"]
    rounds = result["counters"]["rounds"]
    assert sets["large"]["calls"] == 5 * rounds
    assert sets["small"]["calls"] == 10 * rounds
    assert result["compared"]["large_sendrecv_1GiB"] == [0.0, 0]


def test_sweep_control_bfloat16_is_not_correct(sweep_root):
    """The control: the NumPy reference in bfloat16 in every program's
    place, through the harness's own run.  Every reducing program fails;
    bfloat16 rounds the data that sendrecv only moves, so it fails too."""
    import ml_dtypes

    def hook(driver):
        for p in driver.programs.values():
            spec = p["spec"]
            p["call"] = lambda x, spec=spec: jnp.asarray(coll_ref.run_chain(
                spec["op"], np.asarray(x), spec["chain"],
                ml_dtypes.bfloat16))

    result = run_sweep(sweep_root, hook)
    assert result["correct"] is False
    assert result["attempted"] >= 15 and result["failed"] == 0
    failed = {n for n, (v, lim) in result["compared"].items()
              if not v <= lim}
    for op in ("allreduce", "reduce_scatter", "allgather", "sendrecv"):
        assert {n for n in result["compared"] if f"_{op}_" in n} <= failed


@pytest.mark.parametrize("fault", ["exchange_left_out", "answer_altered"])
def test_sweep_faults_come_out_not_correct(sweep_root, fault):
    def hook(driver):
        for name, p in driver.programs.items():
            sound = p["call"]
            if fault == "answer_altered":
                if name != "large_alltoall_1GiB":
                    continue
                p["call"] = lambda x, f=sound: f(x) * (1.0 + 2.0 ** -10)
            else:
                # every rank keeps its own data: the rescale without the
                # collective
                p["call"] = lambda x: x * 1.0

    result = run_sweep(sweep_root, hook)
    assert not result["correct"]
    wrong = {n for n, (v, lim) in result["compared"].items()
             if not v <= lim}
    if fault == "answer_altered":
        assert wrong == {"large_alltoall_1GiB"}
    else:
        assert len(wrong) == 15


# ---------------------------------------------------------------------------
# the collective programs compile for the described 2x2 at the timed sizes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_sweep_programs_compile_for_the_2x2_at_the_timed_sizes(topo):
    """Every program of ``sweep_world`` at its real size, compiled by the
    TPU's compiler for a described (not attached) 2x2: each holds the
    collective it is named after, and what the cell keeps on a device
    (inputs, last outputs, the largest program in flight) fits the chip.
    The solver's leg is not compiled here: two minutes, whatever the rows
    (the kernel's width sets it)."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec

    import mpi4jax_tpu as mpx

    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "sweep_world.json"))
    driver = harness.load_module(os.path.join(BENCH, "drivers",
                                              "collectives.py"))
    mesh = mpx.make_world_mesh(devices=topo.devices)
    comm = mpx.Comm(mesh.axis_names, mesh=mesh)
    sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names))
    coef = jnp.asarray(coll_ref.coefficients(4), jnp.float32)
    opcode = {"allreduce": ("all-reduce",),
              "reduce_scatter": ("reduce-scatter", "all-reduce"),
              "allgather": ("all-gather", "all-reduce"),
              "alltoall": ("all-to-all",),
              "sendrecv": ("collective-permute",)}
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        resident, in_flight = 0, 0
        for spec in traffic["programs"]:
            n = spec["block_elems"]
            shape = (4, 4, n) if spec["op"] in ("reduce_scatter",
                                                "alltoall") else (4, n)
            x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
            body = driver._link(spec["op"], 4, coll_ref.GROWTH, coef)

            @partial(mpx.spmd, comm=comm)
            def chained(x, body=body, links=spec["chain"]):
                return jax.lax.fori_loop(0, links, body, x)

            compiled = mpx.compile(chained, x)._call
            text = compiled.as_text()
            assert any(f" {op}(" in text or f" {op}-start(" in text
                       for op in opcode[spec["op"]]), spec["name"]
            mem = compiled.memory_analysis()
            resident += mem.argument_size_in_bytes + mem.output_size_in_bytes
            in_flight = max(in_flight, mem.temp_size_in_bytes
                            + mem.output_size_in_bytes)
        assert resident + in_flight < PEAK["hbm_bytes"]
        assert resident > 0.25 * PEAK["hbm_bytes"]  # the driver's floor
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_collective_readers_on_a_synthetic_trace():
    """The readers of the collective cell on events whose sums are known:
    two links of the 1 GiB allreduce taking 20 ms each on both devices, and
    the 4 KiB shift's hops as start/done pairs of 1 us and 2 us."""
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             "sweep_world.json"))
    for p in traffic["programs"]:
        p["chain"] = 2
    ms = 1_000_000
    allreduce = "%psum_invariant.9 = f32[268435456]{0} all-reduce(f32[] %x)"
    rescale = "%multiply_fusion.2 = f32[268435456]{0} fusion(f32[] %y)"
    start = "%collective-permute-start = (f32[1024]) collective-permute-start("
    done = "%collective-permute-done = f32[1024] collective-permute-done("
    device = [(allreduce, 1 * ms, 20 * ms), (rescale, 21 * ms, 4 * ms),
              (allreduce, 25 * ms, 20 * ms), (rescale, 45 * ms, 4 * ms),
              (start, 60 * ms, 1000), (done, 60 * ms + 1000, 2000),
              (start, 60 * ms + 3000, 1000), (done, 60 * ms + 4000, 2000)]
    raw = {"devices": {0: device, 1: device}, "host": [
        (trace_reduce.WINDOW_SPAN, 0, 100 * ms),
        ("dispatch_large_allreduce_1GiB", 0, 1 * ms),
        ("wait_large_allreduce_1GiB", 1 * ms, 49 * ms),
        ("dispatch_small_sendrecv_4KiB", 59 * ms, 1 * ms),
        ("wait_small_sendrecv_4KiB", 60 * ms, 1 * ms)]}
    ctx = {"trace": trace_reduce.reduce_events(raw), "traffic": traffic,
           "counters": {"calls": {"large_allreduce_1GiB": 1,
                                  "small_sendrecv_4KiB": 1}},
           "peaks": PEAK, "chips": 4, "work": work, "reduce": trace_reduce,
           "config": {}}

    def read(name):
        ctx["reader"] = lambda n: harness.load_module(
            os.path.join(BENCH, "layer_metrics", n + ".py"))
        return ctx["reader"](name).read(ctx)

    busbw = 2 * 1.5 * 2 ** 30 / 0.040 / 1e9   # bus bytes over 40 ms
    assert read("native_allreduce_busbw_GBps") == pytest.approx(busbw)
    assert read("allreduce_ici_share") == pytest.approx(busbw / 2.0)
    assert read("hop_4KiB_us") == pytest.approx(3.0)
    # 40 of the 48 busy ms inside the large call are the collective's; the
    # call is 50 ms long
    assert read("collective_share") == pytest.approx(100 * 40 / 48)
    assert read("large_idle_share") == pytest.approx(100 * 2 / 50)
    ctx["counters"]["calls"] = {}
    assert read("hop_4KiB_us") is None and read("allreduce_ici_share") is None



def test_solver_readers_on_a_synthetic_trace():
    """The readers of the solver cell on events whose sums are known: a leg
    of one Euler-step kernel call (8 ms), three calls of the loop's
    two-step kernel (20 ms each) and copies between them."""
    config = harness.load_json(os.path.join(BENCH, "configs",
                                            "sw3600x28800.json"))
    ms = 1_000_000
    fields = "(f32[28802,3602]{1,0:T(8,128)}, f32[28802,3602]{1,0:T(8,128)})"
    euler = f"%body.1 = {fields} custom-call(f32[28802,3602] %bitcast)"
    pair = f"%closed_call.4 = {fields} custom-call(f32[28802,3602] %a)"
    copy = "%copy.7 = f32[1,28802,3602]{2,1,0} copy(f32[1,28802,3602] %h)"
    loop = "%while = (s32[], f32[28802,3602]{1,0}) while((s32[]) %tuple)"
    device = [(euler, 2 * ms, 8 * ms), (loop, 10 * ms, 72 * ms)]
    for i in range(3):
        device += [(pair, (10 + 24 * i) * ms, 20 * ms),
                   (copy, (30 + 24 * i) * ms, 4 * ms)]
    raw = {"devices": {0: device}, "host": [
        (trace_reduce.WINDOW_SPAN, 0, 100 * ms),
        ("dispatch_leg", 0, 1 * ms), ("wait_leg", 1 * ms, 82 * ms)]}
    ctx = {"trace": trace_reduce.reduce_events(raw), "config": config,
           "traffic": {}, "peaks": PEAK, "chips": 1, "work": work,
           "reduce": trace_reduce,
           "counters": {"steps_per_kernel_call": 2, "compiles_in_window": 0}}

    def read(name):
        return harness.load_module(
            os.path.join(BENCH, "layer_metrics", name + ".py")).read(ctx)

    # one unfused step's 12 field-moves at 819 GB/s against 20 ms / 2 steps;
    # the 8 ms Euler-step call is not averaged in
    least_ms = 12 * 414_979_216 / 819e9 * 1e3
    assert read("sw_kernel_roofline") == pytest.approx(100 * least_ms / 10)
    ctx["counters"]["steps_per_kernel_call"] = 4  # faster per step: higher
    assert read("sw_kernel_roofline") == pytest.approx(100 * least_ms / 5)
    assert read("sw_idle_share") == pytest.approx(100 * (1 - 80 / 100))
    assert read("sw_compiles_in_window") == 0
    del ctx["counters"]["steps_per_kernel_call"]
    assert read("sw_kernel_roofline") is None
