"""``run_multisteps`` and ``run_plan``: the documented driver's host loop of
the shallow-water solver as a public function, and the program's own count
of what one run of it is made of.

``run_multisteps`` is what ``solve()`` runs and what the benchmark times
(``chipbench/drivers/solver_loop.py``): it must advance what a
``fused_runner`` leg of the same steps advances, carry every call's result
into the next — in ``"wide2"`` the widened frame, built once a run and
cropped once, where the same calls chained by hand build and crop one a
call — and leave ``solve()`` the snapshots it gave.  ``run_plan`` is held
against the calls the programs really make, counted while they are traced.
"""

import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE), os.path.join(HERE, "..", "examples"),
             HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import mpi4jax_tpu as mpx  # noqa: E402
import shallow_water as sw  # noqa: E402
from chipbench.reference import shallow_water as periodic_ref  # noqa: E402
from chipbench.reference import shallow_water_walls as walls_ref  # noqa: E402
from test_fused_runner import (  # noqa: E402
    _config, _frame_shape, _solver_tally, _unfused)

NUM = 10     # upstream's and solve()'s default num_multisteps
N_ITERS = 2  # 1 + 2 x 10 steps

# (fast, mesh, periodic_x): what ``auto`` gives one chip in either boundary
# mode (``wide2`` since PR 38, the carried frame on both), the whole-step
# kernel by name while that mode lives, and the wide-halo path on a mesh
CASES = [
    ("auto", (1, 1), True),     # wide2: its x bands slices of its own frame
    ("auto", (1, 1), False),    # wide2: every band zeros
    ("pallas2", (1, 1), True),  # the carry is the State
    ("wide2", (1, 1), True),
    ("wide2", (2, 2), True),
    ("wide2", (2, 2), False),
    ("wide2", (2, 4), True),
    ("wide2", (2, 4), False),
]
# tests/test_fused_runner.py states ``rtol=1e-5, atol=1e-6`` for the
# stepper's two programs against the leg (2e-6 on the walled single rank),
# read at 12 steps in two calls.  Here the leg is 21 steps and the run
# three calls; smallest ``atol`` that passes beside ``rtol`` 1e-5 on the
# CPU at 64 x 32, largest over the six fields: pallas2 0; wide2 walled
# (1, 1) 2.43e-6 (v; u 2.29e-6); wide2 (2, 2) periodic 1.50e-6 (v), walled
# 1.30e-6 (v) — one to three ulps of the jet's 10 m/s (9.5e-7), for that
# file's reason (LLVM contracts multiply-adds across what XLA fused around
# the interpreted kernel; with fusion off every gap is 0: the test below).
# One multistep fewer reads 1.7e-2 on u at the least.  Read again on the
# aligned frame (PR 36: 64 x 128 cells a rank where it was 64 x 96, so
# XLA:CPU fuses a differently shaped program round the same kernels):
# wide2 walled (1, 1) 1.65e-6 (v), walled meshes 0, and the three periodic
# wide2 cases 3.18e-6 (v; u 1.23e-6), the same bits on (1, 1), (2, 2) and
# (2, 4) — the limit is the next round figure above that.  It is rounding,
# not a dead cell reaching a valid one: with fusion off run and leg still
# agree bit for bit in all seven cases (the test below), and a frame whose
# dead cells hold ``nan`` crops to the same bits
# (tests/test_wide_dead_cells.py).  ``("auto", (1, 1), True)`` is the
# program of ``("wide2", (1, 1), True)`` since PR 38 and reads what it
# reads (u 1.49e-6 where ``pallas2`` read 0 and had the 1e-6 it keeps).
ATOL = {("pallas2", (1, 1), True): 1e-6}
WIDE_ATOL = 4e-6


def _stepper(fast, mesh, periodic_x):
    cfg = _config(mesh, periodic_x)
    _mesh, comm = sw.make_mesh_and_comm(cfg,
                                        devices=jax.devices()[: cfg.nproc])
    return cfg, comm, sw.initial_state(cfg, comm), sw.make_stepper(
        cfg, comm, fast=fast)


@pytest.mark.parametrize("fast,mesh,periodic_x", CASES)
def test_a_run_advances_what_a_leg_of_the_same_steps_advances(
        fast, mesh, periodic_x):
    cfg, comm, state, (first_step, multistep) = _stepper(fast, mesh,
                                                         periodic_x)
    fused, _ = sw.fused_runner(cfg, comm, fast)
    want = fused(state, N_ITERS * NUM)
    got = sw.run_multisteps(first_step, multistep, state, N_ITERS, NUM)
    fewer = sw.run_multisteps(first_step, multistep, state, N_ITERS - 1, NUM)
    atol = ATOL.get((fast, mesh, periodic_x), WIDE_ATOL)
    for name, a, b in zip(want._fields, got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=atol, err_msg=name)
    assert np.abs(np.asarray(fewer.u) - np.asarray(want.u)).max() > 1e-2


@pytest.mark.parametrize("fast,mesh,periodic_x", CASES)
def test_a_run_is_the_leg_bit_for_bit_with_fusion_off(fast, mesh,
                                                      periodic_x):
    """The same kernels on the same operands in the same order: with XLA's
    fusion pass off (see tests/test_fused_runner.py) a run's three calls —
    in ``"wide2"`` the frame built by the first, refreshed and advanced by
    the others, cropped once behind the last — give in all six fields the
    bits of the leg, and of the same three calls chained by hand through
    the ``State -> State`` programs, a frame built and cropped in each."""
    cfg, comm, state, (first_step, multistep) = _stepper(fast, mesh,
                                                         periodic_x)
    fused, _ = sw.fused_runner(cfg, comm, fast)
    want = _unfused(lambda s: fused(s, N_ITERS * NUM), state)
    runs = {
        "carried": _unfused(lambda s: sw.run_multisteps(
            first_step, multistep, s, N_ITERS, NUM), state),
        "by hand": _unfused(lambda s: multistep(multistep(
            first_step(s), NUM), NUM), state)}
    assert (multistep.carried is not multistep) == (
        sw._resolve_mode(fast, cfg) == "wide2")
    for how, got in runs.items():
        assert isinstance(got, sw.State)
        for name, a, b in zip(want._fields, got, want):
            assert a.shape == state.h.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          f"{how}: {name}")


@pytest.mark.parametrize("fast,mesh,periodic_x", CASES)
def test_the_hook_gets_states_and_the_wait_is_on_the_carry(
        monkeypatch, fast, mesh, periodic_x):
    """``on_multistep`` is handed a finished ``State`` of the physical
    shape after every call — in ``"wide2"`` a crop of the carried frame,
    equal to what stepping by hand gives there — and without a hook two
    dispatches stay in flight: before call k + 1 goes, and before the crop
    behind the last call, the loop waits for call k - 1's result,
    ``n_iters`` times a run, on what the loop carries (six frames in
    ``"wide2"``)."""
    n_iters = 3
    cfg, comm, state, (first_step, multistep) = _stepper(fast, mesh,
                                                         periodic_x)
    wide = sw._resolve_mode(fast, cfg) == "wide2"
    seen, waits = [], []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(x) or block(x))
    plain = sw.run_multisteps(first_step, multistep, state, n_iters, NUM)
    flight, waits = waits, []
    got = sw.run_multisteps(first_step, multistep, state, n_iters, NUM,
                            seen.append)
    monkeypatch.undo()
    assert len(seen) == 1 + n_iters and len(flight) == n_iters
    frame = (cfg.nproc, *_frame_shape(cfg))
    for waited in flight:
        assert len(waited) == 6
        assert {f.shape for f in waited} == {frame if wide
                                             else state.h.shape}
    # with the hook: every snapshot finished before the hook has it
    assert len(waits) == len(flight) + len(seen)
    assert all(any(w is snap for w in waits) for snap in seen)
    for a, b in zip(got, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    by_hand = first_step(state)
    for i, snap in enumerate(seen):
        assert isinstance(snap, sw.State)
        assert {f.shape for f in snap} == {state.h.shape}
        assert snap.h.sharding == state.h.sharding
        for name, a, b in zip(snap._fields, snap, by_hand):
            # to rounding (31 steps; 3.3e-6 read on u): the bits are the
            # test's above, with fusion off
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=2 * WIDE_ATOL,
                                       err_msg=f"call {i}: {name}")
        by_hand = multistep(by_hand, NUM)
    for a, b in zip(got, seen[-1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_pair_with_one_form_missing_is_called_as_it_is():
    """A wrapper round one of ``make_stepper``'s programs hides its carried
    form: the loop then calls both as ``State -> State``, never a frame
    into a program that takes a ``State``."""
    cfg, comm, state, (first_step, multistep) = _stepper("auto", (1, 1),
                                                         False)
    calls = []

    def wrapped(state, n):
        calls.append({f.shape for f in state})
        return multistep(state, n)

    got = sw.run_multisteps(first_step, wrapped, state, N_ITERS, NUM)
    want = sw.run_multisteps(first_step, multistep, state, N_ITERS, NUM)
    assert calls == [{state.h.shape}] * N_ITERS
    for name, a, b in zip(want._fields, got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=WIDE_ATOL, err_msg=name)


def test_every_calls_state_is_the_next_calls_input():
    """The loop itself, on stand-ins: one first step, ``n_iters`` multisteps
    of ``num_multisteps``, each fed what the call before returned, the
    callback after every call."""
    log, seen = [], []

    def first_step(state):
        log.append(("first", state))
        return state + 1

    def multistep(state, n):
        log.append(("multi", state, n))
        return state + n

    out = sw.run_multisteps(first_step, multistep, 100, 3, 10, seen.append)
    # (the loop's waits pass a stand-in's plain numbers through)
    assert out == 131
    assert log == [("first", 100), ("multi", 101, 10), ("multi", 111, 10),
                   ("multi", 121, 10)]
    assert seen == [101, 111, 121, 131]
    assert sw.run_multisteps(first_step, multistep, 0, 0, 10) == 1


@pytest.mark.parametrize("steps,num,want", [
    (30, 10, 3), (20, 5, 4), (10, 5, 2), (23, 5, 5), (1, 10, 0),
    (0.5, 10, 0), (2, 10, 1)])
def test_n_multisteps_is_the_first_multiple_that_reaches_t1(steps, num,
                                                            want):
    cfg = sw.Config()
    assert sw.n_multisteps(cfg, steps * cfg.dt, num) == want


def test_the_published_run_is_45_calls_and_441_steps():
    cfg = sw.Config(nx=3600, ny=28800, periodic_x=False)
    n_iters = sw.n_multisteps(cfg, 0.1 * sw.DAY_IN_SECONDS, NUM)
    plan = sw.run_plan(cfg, "auto", n_iters, NUM)
    assert (n_iters, plan["calls"], plan["steps"],
            plan["steps_per_kernel_call"]) == (44, 45, 441, 2)
    # the run carries the frame: built by the first call, cropped once
    # behind the last, outside any region
    assert (plan["frames_built"], plan["crops"]) == (1, 1)
    assert plan["first_step"] == {
        "steps": 1, "euler_calls": 1, "chunk_calls": 0,
        "single_step_calls": 0, "frames_built": 1, "band_refreshes": 1,
        "crops": 0}
    # every call hands the frame on with its margins refreshed: the first
    # kernel call of the next runs off them, a refresh goes before each of
    # the other four and one behind the last
    assert plan["multistep"] == {
        "steps": 10, "euler_calls": 0, "chunk_calls": 5,
        "single_step_calls": 0, "frames_built": 0, "band_refreshes": 5,
        "crops": 0}
    # the periodic chip carries the same frame since PR 38: the same plan
    assert sw.run_plan(sw.Config(nx=3600, ny=28800), "auto", n_iters,
                       NUM) == plan
    # the whole-step kernel, by name: the carry is the ``State``
    periodic = sw.run_plan(sw.Config(nx=3600, ny=28800), "pallas2",
                           n_iters, NUM)
    assert (periodic["frames_built"], periodic["crops"]) == (0, 0)
    assert periodic["multistep"]["band_refreshes"] == 0
    assert periodic["multistep"]["chunk_calls"] == 5
    with pytest.raises(ValueError, match="n_iters"):
        sw.run_plan(cfg, "auto", -1, NUM)
    with pytest.raises(ValueError, match="num_multisteps"):
        sw.run_plan(cfg, "auto", 1, 0)


def test_the_periodic_benchmark_domain_carries_the_frame_under_auto():
    """``Config(nx=3600, ny=28800)``, the published domain on one chip:
    ``auto`` is ``wide2`` there since PR 38, so the run and the leg are
    counted on the carried frame — by the schedule ``_wide_run`` follows —
    and the whole-step kernel's plan is what a caller gets by name."""
    cfg = sw.Config(nx=3600, ny=28800)
    assert sw._resolve_mode("auto", cfg) == "wide2"
    run = sw.run_plan(cfg, "auto", 44, NUM)
    assert run == sw.run_plan(cfg, "wide2", 44, NUM)
    assert (run["frames_built"], run["crops"],
            run["steps_per_kernel_call"]) == (1, 1, 2)
    first, multi = run["first_step"], run["multistep"]
    head, trips, rem = sw._wide_schedule(NUM, 2, False)
    assert (head, trips, rem) == (1, 4, 0)
    assert (multi["chunk_calls"], multi["single_step_calls"]) == (5, 0)
    # a refresh a round, and one behind the last call for the next call
    assert multi["band_refreshes"] == trips + rem + 1 == 5
    assert (first["euler_calls"], first["frames_built"],
            first["band_refreshes"]) == (1, 1, 1)
    # a run: what the traced line's ``traced_custom_calls_a_run`` must read
    assert (first["euler_calls"], 44 * multi["chunk_calls"],
            first["band_refreshes"] + 44 * multi["band_refreshes"]) == (
        1, 220, 221)
    assert sw.leg_plan(cfg, "auto", 71) == {
        "steps": 71, "steps_per_kernel_call": 2, "euler_calls": 1,
        "chunk_calls": 35, "single_step_calls": 0, "frames_built": 1,
        "band_refreshes": 35, "crops": 1}
    named = sw.leg_plan(cfg, "pallas2", 71)
    assert (named["chunk_calls"], named["frames_built"],
            named["band_refreshes"], named["crops"]) == (35, 0, 0, 0)


@pytest.mark.parametrize("num", [1, 2, 3, 7, 10])
@pytest.mark.parametrize("fast,periodic_x", [("wide2", False),
                                             ("pallas2", True)])
def test_run_plan_counts_the_calls_a_run_makes(monkeypatch, fast,
                                               periodic_x, num):
    """The programs a run calls, each traced once under the tally of
    tests/test_fused_runner.py: what each carried call makes is what
    ``run_plan`` says of it, a run is one of the first and ``n_iters`` of
    the other, and its one frame and one crop are the first call's build
    and the crop behind the last."""
    cfg = _config((1, 1), periodic_x)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    tally = _solver_tally(monkeypatch)
    first_step, multistep = sw.make_stepper(cfg, comm, fast=fast)
    state = sw.initial_state(cfg, comm)
    kernel = "_wide_kernel_call" if fast == "wide2" else "model_step_pallas"
    plan = sw.run_plan(cfg, fast, 4, num)
    assert (plan["calls"], plan["steps"]) == (5, 1 + 4 * num)
    carry, built = state, 0  # what goes from call to call, by its shapes
    for program, args, first in ((first_step.carried, (), True),
                                 (multistep.carried, (num,), False)):
        tally.counts.clear()
        carry = jax.eval_shape(lambda s: program(s, *args), carry)
        got = tally.counts
        call = plan["first_step" if first else "multistep"]
        assert call["steps"] == (
            call["euler_calls"] + 2 * call["chunk_calls"]
            + call["single_step_calls"])
        assert got.get(f"{kernel}/True/1", 0) == call["euler_calls"] == first
        assert got.get(f"{kernel}/False/2", 0) == call["chunk_calls"]
        assert got.get(f"{kernel}/False/1", 0) == call["single_step_calls"]
        assert got.get("_wide_exchange", 0) == call["frames_built"]
        assert got.get("_wide_refresh", 0) == call["band_refreshes"]
        assert got.get("_wide_crop", 0) == call["crops"] == 0
        built += got.get("_wide_exchange", 0) * (1 if first else 4)
    tally.counts.clear()
    assert jax.eval_shape(multistep.crop, carry) == jax.eval_shape(
        lambda s: s, state)
    cropped = tally.counts.get("_wide_crop", 0)
    assert (built, cropped) == (plan["frames_built"], plan["crops"])
    assert plan["crops"] == int(fast == "wide2")


@pytest.mark.parametrize("num", [1, 2, 3, 7, 10])
def test_stepping_by_hand_builds_and_crops_a_frame_a_call(monkeypatch,
                                                          num):
    """``first_step(state)`` and ``multistep(state, n)`` take and leave a
    ``State``: in ``"wide2"`` each builds a frame and crops it, and the
    first kernel call of a multistep runs off the just-built frame (one
    refresh fewer than the carried call of the same steps, which leaves
    one behind its last kernel call for the call that follows)."""
    cfg = _config((1, 1), False)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    tally = _solver_tally(monkeypatch)
    first_step, multistep = sw.make_stepper(cfg, comm, fast="wide2")
    state = sw.initial_state(cfg, comm)
    carried = sw.run_plan(cfg, "wide2", 1, num)["multistep"]
    for program, args, calls in ((first_step, (), 1),
                                 (multistep, (num,), (num + 1) // 2)):
        tally.counts.clear()
        jax.eval_shape(lambda s: program(s, *args), state)
        got = tally.counts
        assert (got["_wide_exchange"], got["_wide_crop"]) == (1, 1)
        assert sum(n for k, n in got.items()
                   if k.startswith("_wide_kernel_call")) == calls
    assert got.get("_wide_refresh", 0) == carried["band_refreshes"] - 1
    assert calls == carried["chunk_calls"] + carried["single_step_calls"]


@pytest.mark.parametrize("steps", [1, 2, 12, 71])
@pytest.mark.parametrize("fast,periodic_x", [("wide2", False),
                                             ("pallas2", True), (True, True)])
def test_a_leg_is_a_first_step_and_one_multistep_of_the_rest(fast,
                                                             periodic_x,
                                                             steps):
    """``leg_plan`` and ``run_plan`` read one schedule: the kernel calls
    and the band refreshes of a leg are those of the first step and of one
    carried multistep of the rest, and the run builds one frame and crops
    one, as the leg does."""
    cfg = _config((1, 1), periodic_x)
    leg = sw.leg_plan(cfg, fast, steps)
    run = sw.run_plan(cfg, fast, int(steps > 1), max(steps - 1, 1))
    first, rest = run["first_step"], run["multistep"]
    assert run["steps"] == (steps if steps > 1 else 1)
    for key in ("euler_calls", "chunk_calls", "single_step_calls",
                "frames_built"):
        assert leg[key] == first[key] + (rest[key] if steps > 1 else 0), key
    # a refresh between any two kernel calls, in one program or across two,
    # and the one behind the run's last call, which a crop follows
    assert (first["band_refreshes"]
            + (rest["band_refreshes"] if steps > 1 else 0)
            == leg["band_refreshes"] + leg["frames_built"])
    assert (run["frames_built"], run["crops"]) == (leg["frames_built"],
                                                   leg["crops"])
    assert first["crops"] == rest["crops"] == 0
    assert leg["steps_per_kernel_call"] == run["steps_per_kernel_call"]


def _solve_as_it_was(cfg, t1, num_multisteps, devices, fast):
    """``solve()``'s loop before ``run_multisteps`` was split out of it."""
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=devices)
    first_step, multistep = sw.make_stepper(cfg, comm, fast=fast)
    state = sw.initial_state(cfg, comm)
    snapshots = [np.asarray(state.h)]
    state = first_step(state)
    snapshots.append(np.asarray(state.h))
    t, n_steps = cfg.dt, 1
    while t < t1:
        state = multistep(state, num_multisteps)
        snapshots.append(np.asarray(state.h))
        t += cfg.dt * num_multisteps
        n_steps += num_multisteps
    gathered, _ = mpx.gather(state.h, root=0, comm=comm)
    snapshots.append(np.asarray(gathered[0]))
    return snapshots, n_steps


@pytest.mark.parametrize("mesh,periodic_x,fast,steps,num", [
    ((2, 4), True, True, 23, 5),
    ((1, 1), False, "auto", 30, 10),
    ((2, 2), False, "wide2", 12, 10),
])
def test_solve_gives_the_snapshots_it_gave(mesh, periodic_x, fast, steps,
                                           num):
    cfg = sw.Config(nproc_y=mesh[0], nproc_x=mesh[1], nx=64, ny=32,
                    periodic_x=periodic_x)
    devices = jax.devices()[: cfg.nproc]
    want, want_steps = _solve_as_it_was(cfg, steps * cfg.dt, num, devices,
                                        fast)
    got, wall, n_steps = sw.solve(cfg, steps * cfg.dt, num_multisteps=num,
                                  devices=devices, fast=fast)
    assert n_steps == want_steps and wall > 0
    assert len(got) == len(want) == 3 + math.ceil((steps - 1) / num)
    # ``solve()`` carries the frame where the loop as it was built and
    # cropped one a call: in ``"wide2"`` the snapshots agree to rounding
    # (the bits: the test with fusion off, above), elsewhere bit for bit
    atol = 0 if fast is True else WIDE_ATOL
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5 if atol else 0,
                                   atol=atol, err_msg=f"snapshot {i}")


def test_solve_without_snapshots_keeps_two_calls_in_flight(monkeypatch):
    """Benchmark mode: no field is read on the host and nothing is cropped
    but the last result; before call k + 1 goes the loop waits for call
    k - 1 (one call runs, one is queued), and one ``jax.block_until_ready``
    on the cropped state closes the run.  The warm-up before the clock is a
    run of one multistep through the same loop: the programs it compiles
    are the ones the timed run calls."""
    cfg = sw.Config(nx=64, ny=32, periodic_x=False)
    waits, results, crops = [], [], []
    block = jax.block_until_ready

    def waited(x):
        known = [i for i, r in enumerate(results, 1) if r is x]
        waits.append((len(results), known[0] if known else None))
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", waited)
    make_stepper = sw.make_stepper

    def recorded(*args, **kwargs):
        """``make_stepper``'s pair, the calls of its carried forms kept."""
        first_step, multistep = make_stepper(*args, **kwargs)

        def keep(program, into):
            def called(*a):
                into.append(program(*a))
                return into[-1]
            return called

        first_step.carried = keep(first_step.carried, results)
        multistep.carried = keep(multistep.carried, results)
        multistep.crop = keep(multistep.crop, crops)
        return first_step, multistep

    monkeypatch.setattr(sw, "make_stepper", recorded)
    snaps, wall, n_steps = sw.solve(cfg, 30 * cfg.dt, devices=jax.devices()[:1],
                                    collect=False, fast="auto")
    assert snaps == [] and n_steps == 31 and wall > 0
    # (calls dispatched so far, the call waited for): the warm-up's two
    # calls, its wait on the first before the crop goes and solve()'s on
    # the cropped state; then call 1 of the run (the third dispatched)
    # before its call 3 goes, call 2 before call 4, call 3 before the crop;
    # the cropped last
    assert waits == [(2, 1), (2, None), (4, 3), (5, 4), (6, 5), (6, None)]
    assert len(crops) == 2  # one a run, the warm-up's and the timed one's
    assert _frame_shape(cfg) == (64, 128)  # 32 + 2 + 30 rows; 96 columns up
    assert {f.shape for r in results for f in r} == {(1, 64, 128)}


# the 48 x 24 domain of the two tests against the benchmark's plain
# references, as a configuration file of the benchmark states it
REFERENCE_CONFIG = {"nx": 48, "ny": 24, "dx": 5e3, "dy": 5e3, "gravity": 9.81,
                    "depth": 100.0, "coriolis_f": 2e-4, "coriolis_beta": 2e-11,
                    "ab_a": 1.6, "ab_b": -0.6,
                    "scaled": {"ny": {"published": 24}}}


def _check_scales(want, dt):
    """What the cells' check divides a gap by: the reference's largest
    height or speed; a tendency's times the time step first."""
    speed = max(np.abs(want["u"]).max(), np.abs(want["v"]).max())
    scale = {"h": np.abs(want["h"]).max(), "u": speed, "v": speed}
    for n in ("h", "u", "v"):
        scale["d" + n] = scale[n] / dt
    return scale


@pytest.mark.parametrize("seed", [2 ** 31 + 17, 5, 2 ** 31 + 4099])
def test_a_run_agrees_with_the_walled_reference(seed):
    """48 x 24 closed basin from the benchmark's own initial state: 21 steps
    through ``run_multisteps`` on what ``auto`` picks (``wide2``) against
    the plain reference, gaps scaled as the cell's check scales them.
    Largest reading over the three seeds: 3.8e-7 (u); the limit is 1e-6."""
    p = walls_ref.params(dict(REFERENCE_CONFIG, periodic_x=False))
    h, u, v = walls_ref.initial_fields(p, seed)
    cfg = sw.Config(nx=48, ny=24, periodic_x=False)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    zero = jnp.zeros((1, 26, 50), jnp.float32)
    state = sw.State(h[None], u[None], v[None], zero, zero, zero)
    first_step, multistep = sw.make_stepper(cfg, comm, fast="auto")
    got = sw.run_multisteps(first_step, multistep, state, N_ITERS, NUM)
    want = dict(zip(walls_ref.FIELDS, (np.asarray(b) for b in
                                       walls_ref.make_run(p, 21)(h, u, v))))
    scale = _check_scales(want, p["dt"])
    for name, a in zip(walls_ref.FIELDS, got):
        gap = np.abs(np.asarray(a)[0] - want[name]).max()
        assert gap <= 1e-6 * scale[name], (name, gap / scale[name])
    assert np.abs(want["h"] - np.asarray(h)).max() > 1.0  # it moved


@pytest.mark.parametrize("seed", [2 ** 31 + 17, 5, 2 ** 31 + 4099])
def test_a_run_agrees_with_the_periodic_reference(seed):
    """48 x 24 domain, periodic in x, from the benchmark's own initial
    state: 21 steps through ``run_multisteps`` on what ``auto`` gives one
    periodic chip (``wide2`` since PR 38: the carry is the widened frame,
    its x bands read out of itself) against the plain reference — not
    only ``fused_runner``'s leg, which shares the kernel — gaps scaled as
    the cell's check scales them.  Largest reading over the three seeds:
    5.3e-7 (u; h 1.5e-7, and 2.3e-7 on ``pallas2``, which ``auto`` gave
    until PR 38); the limit is 1e-6 — the gaps are float32 rounding over
    21 steps of two independent orderings of the same sums (an ulp of the
    100 m height is 7.6e-8 of it), twice the reading, and the limit of the
    walled case above.  One multistep fewer reads 3.5e-3 on h."""
    p = periodic_ref.params(dict(REFERENCE_CONFIG, periodic_x=True))
    h, u, v = periodic_ref.initial_fields(p, seed)
    cfg = sw.Config(nx=48, ny=24, periodic_x=True)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    zero = jnp.zeros((1, 26, 50), jnp.float32)
    state = sw.State(*(periodic_ref.with_halo_columns(a)[None]
                       for a in (h, u, v)), zero, zero, zero)
    first_step, multistep = sw.make_stepper(cfg, comm, fast="auto")
    assert multistep.carried is not multistep
    assert first_step.carried is not first_step
    frames = jax.eval_shape(first_step.carried, state)
    assert {f.shape for f in frames} == {(1, *_frame_shape(cfg))}
    assert jax.eval_shape(multistep.crop, frames) == jax.eval_shape(
        lambda s: s, state)
    got = sw.run_multisteps(first_step, multistep, state, N_ITERS, NUM)
    fewer = sw.run_multisteps(first_step, multistep, state, N_ITERS - 1, NUM)
    want = dict(zip(periodic_ref.FIELDS, (
        np.asarray(b) for b in periodic_ref.make_run(p, 21)(h, u, v))))
    scale = _check_scales(want, p["dt"])
    for name, a in zip(periodic_ref.FIELDS, got):
        # the physical domain: the reference has no halo columns, and its
        # boundary rows are the initial state's
        gap = np.abs(np.asarray(a)[0, 1:-1, 1:-1] - want[name][1:-1]).max()
        assert gap <= 1e-6 * scale[name], (name, gap / scale[name])
    assert np.abs(np.asarray(fewer.h)[0, 1:-1, 1:-1]
                  - want["h"][1:-1]).max() > 1e-4 * scale["h"]
    assert np.abs(want["h"] - np.asarray(h)).max() > 1e-2  # it moved
