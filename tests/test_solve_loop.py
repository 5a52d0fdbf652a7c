"""``run_multisteps`` and ``run_plan``: the documented driver's host loop of
the shallow-water solver as a public function, and the program's own count
of what one run of it is made of.

``run_multisteps`` is what ``solve()`` runs and what the benchmark times
(``chipbench/drivers/solver_loop.py``): it must advance what a
``fused_runner`` leg of the same steps advances, carry every call's state
into the next, and leave ``solve()`` the snapshots it gave.  ``run_plan`` is
held against the calls the two programs really make, counted while they are
traced.
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
from chipbench.reference import shallow_water_walls as walls_ref  # noqa: E402
from test_fused_runner import _Tally, _config, _unfused  # noqa: E402

NUM = 10     # upstream's and solve()'s default num_multisteps
N_ITERS = 2  # 1 + 2 x 10 steps

# (fast, mesh, periodic_x): what ``auto`` gives one chip on either side of
# its choice, and the wide-halo path on a mesh
CASES = [
    ("auto", (1, 1), True),     # pallas2
    ("auto", (1, 1), False),    # wide2
    ("wide2", (2, 2), True),
    ("wide2", (2, 2), False),
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
# One multistep fewer reads 1.7e-2 on u at the least.
ATOL = {("auto", (1, 1), True): 1e-6}
WIDE_ATOL = 3e-6


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
    fusion pass off (see tests/test_fused_runner.py) a run's three calls,
    the frame built and cropped in each, give the leg's bits."""
    cfg, comm, state, (first_step, multistep) = _stepper(fast, mesh,
                                                         periodic_x)
    fused, _ = sw.fused_runner(cfg, comm, fast)
    want = _unfused(lambda s: fused(s, N_ITERS * NUM), state)
    got = _unfused(lambda s: sw.run_multisteps(first_step, multistep, s,
                                               N_ITERS, NUM), state)
    for name, a, b in zip(want._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


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
    assert plan["first_step"] == {
        "steps": 1, "euler_calls": 1, "chunk_calls": 0,
        "single_step_calls": 0, "frames_built": 1, "band_refreshes": 0,
        "crops": 1}
    # the first kernel call of a multistep runs off the just-built frame
    assert plan["multistep"] == {
        "steps": 10, "euler_calls": 0, "chunk_calls": 5,
        "single_step_calls": 0, "frames_built": 1, "band_refreshes": 4,
        "crops": 1}
    with pytest.raises(ValueError, match="n_iters"):
        sw.run_plan(cfg, "auto", -1, NUM)
    with pytest.raises(ValueError, match="num_multisteps"):
        sw.run_plan(cfg, "auto", 1, 0)


@pytest.mark.parametrize("num", [1, 2, 3, 7, 10])
@pytest.mark.parametrize("fast,periodic_x", [("wide2", False),
                                             ("pallas2", True)])
def test_run_plan_counts_the_calls_a_run_makes(monkeypatch, fast,
                                               periodic_x, num):
    """Both programs traced once under the tally of
    tests/test_fused_runner.py: what each call makes is what ``run_plan``
    says of it, and a run is one of the first and ``n_iters`` of the
    other."""
    cfg = _config((1, 1), periodic_x)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    tally = _Tally(monkeypatch)
    tally.count("_wide_kernel_call",
                lambda wf, cfg, first, nsteps, *a: f"/{first}/{nsteps}")
    tally.count("model_step_pallas",
                lambda s, cfg, comm, first_step, interpret=None, nsteps=1:
                f"/{first_step}/{nsteps}")
    for name in ("_wide_exchange", "_wide_refresh", "_wide_crop"):
        tally.count(name)
    first_step, multistep = sw.make_stepper(cfg, comm, fast=fast)
    state = sw.initial_state(cfg, comm)
    kernel = "_wide_kernel_call" if fast == "wide2" else "model_step_pallas"
    plan = sw.run_plan(cfg, fast, 4, num)
    assert (plan["calls"], plan["steps"]) == (5, 1 + 4 * num)
    for program, args, first in ((first_step, (), True),
                                 (multistep, (num,), False)):
        tally.counts.clear()
        jax.eval_shape(lambda s: program(s, *args), state)
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
        assert got.get("_wide_crop", 0) == call["crops"]


@pytest.mark.parametrize("steps", [1, 2, 12, 71])
@pytest.mark.parametrize("fast,periodic_x", [("wide2", False),
                                             ("pallas2", True), (True, True)])
def test_a_leg_is_a_first_step_and_one_multistep_of_the_rest(fast,
                                                             periodic_x,
                                                             steps):
    """``leg_plan`` and ``run_plan`` read one schedule: the kernel calls of
    a leg are those of the first step and of one multistep of the rest; the
    run builds and crops a frame a call where the leg builds and crops
    one."""
    cfg = _config((1, 1), periodic_x)
    leg = sw.leg_plan(cfg, fast, steps)
    run = sw.run_plan(cfg, fast, int(steps > 1), max(steps - 1, 1))
    first, rest = run["first_step"], run["multistep"]
    assert run["steps"] == (steps if steps > 1 else 1)
    calls = 1 + int(steps > 1)
    for key in ("euler_calls", "chunk_calls", "single_step_calls"):
        assert leg[key] == first[key] + (rest[key] if steps > 1 else 0), key
    assert (first["frames_built"] + (rest["frames_built"] if steps > 1
                                     else 0)
            == calls * leg["frames_built"])
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
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, f"snapshot {i}")


def test_solve_without_snapshots_keeps_two_calls_in_flight(monkeypatch):
    """Benchmark mode: no field is read on the host; before call k + 1 goes
    the loop waits for call k - 1 (one call runs, one is queued), and one
    ``jax.block_until_ready`` on the last state closes the run (the warm-up
    before the clock has its own)."""
    cfg = sw.Config(nx=64, ny=32, periodic_x=False)
    waits, results = [], []
    block = jax.block_until_ready

    def waited(x):
        known = [i for i, r in enumerate(results, 1) if r is x]
        waits.append((len(results), known[0] if known else None))
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", waited)
    run_multisteps = sw.run_multisteps

    def counted(first_step, multistep, *args):
        def call(program):
            def called(*a):
                results.append(program(*a))
                return results[-1]
            return called
        return run_multisteps(call(first_step), call(multistep), *args)

    monkeypatch.setattr(sw, "run_multisteps", counted)
    snaps, wall, n_steps = sw.solve(cfg, 30 * cfg.dt, devices=jax.devices()[:1],
                                    collect=False, fast="auto")
    assert snaps == [] and n_steps == 31 and wall > 0
    # (calls dispatched so far, the call waited for): the warm-up's wait
    # before any; call 1 before call 3 goes, call 2 before call 4; the last
    assert waits == [(0, None), (2, 1), (3, 2), (4, 4)]


@pytest.mark.parametrize("seed", [2 ** 31 + 17, 5, 2 ** 31 + 4099])
def test_a_run_agrees_with_the_walled_reference(seed):
    """48 x 24 closed basin from the benchmark's own initial state: 21 steps
    through ``run_multisteps`` on what ``auto`` picks (``wide2``) against
    the plain reference, gaps scaled as the cell's check scales them.
    Largest reading over the three seeds: 3.8e-7 (u); the limit is 1e-6."""
    config = {"nx": 48, "ny": 24, "dx": 5e3, "dy": 5e3, "gravity": 9.81,
              "depth": 100.0, "coriolis_f": 2e-4, "coriolis_beta": 2e-11,
              "periodic_x": False, "ab_a": 1.6, "ab_b": -0.6,
              "scaled": {"ny": {"published": 24}}}
    p = walls_ref.params(config)
    h, u, v = walls_ref.initial_fields(p, seed)
    cfg = sw.Config(nx=48, ny=24, periodic_x=False)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    zero = jnp.zeros((1, 26, 50), jnp.float32)
    state = sw.State(h[None], u[None], v[None], zero, zero, zero)
    first_step, multistep = sw.make_stepper(cfg, comm, fast="auto")
    got = sw.run_multisteps(first_step, multistep, state, N_ITERS, NUM)
    want = dict(zip(walls_ref.FIELDS, (np.asarray(b) for b in
                                       walls_ref.make_run(p, 21)(h, u, v))))
    speed = max(np.abs(want["u"]).max(), np.abs(want["v"]).max())
    scale = {"h": np.abs(want["h"]).max(), "u": speed, "v": speed}
    for n in ("h", "u", "v"):
        scale["d" + n] = scale[n] / p["dt"]
    for name, a in zip(walls_ref.FIELDS, got):
        gap = np.abs(np.asarray(a)[0] - want[name]).max()
        assert gap <= 1e-6 * scale[name], (name, gap / scale[name])
    assert np.abs(want["h"] - np.asarray(h)).max() > 1.0  # it moved
