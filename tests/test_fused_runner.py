"""``fused_runner`` and ``leg_plan``: the whole-run region of the
shallow-water solver as a public function, and the program's own count of
what one leg of it is made of.

``fused_runner`` is what ``solve_fused`` runs and what the benchmark pins
(``chipbench/drivers/solver_runner.py``), so the two must give the same
bits; ``leg_plan`` is held against the calls a leg really makes, counted
while it is traced.
"""

import os
import sys
from functools import partial

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))

import mpi4jax_tpu as mpx  # noqa: E402
import shallow_water as sw  # noqa: E402

STEPS = 12  # the Euler step, five pairs and one step over


def _config(mesh, periodic_x):
    return sw.Config(nproc_y=mesh[0], nproc_x=mesh[1], nx=64, ny=32,
                     periodic_x=periodic_x)


def _frame_shape(cfg, m=16):
    """``(rows, columns)`` of the widened frame a rank carries in
    ``"wide2"``: the local field, ``m - 1`` cells of margin a side, then
    rows up to a multiple of 8 and columns to one of 128 (the dead cells
    of ``_wide_exchange``)."""
    rows, cols = cfg.ny_local + 2 * (m - 1), cfg.nx_local + 2 * (m - 1)
    return rows + -rows % 8, cols + -cols % 128


CASES = [
    ("pallas2", (1, 1), True),
    ("wide2", (1, 1), True),
    ("wide2", (1, 1), False),
    ("wide2", (2, 2), True),
    ("wide2", (2, 2), False),
    ("auto", (1, 1), False),
]
# Smallest ``atol`` beside ``rtol=1e-5`` that the stepper's two programs
# pass at against the leg, 12 steps at 64 x 32 on the CPU, largest over the
# six fields (the field), before / after ``_wide_run``'s loop ran two rounds
# an iteration: pallas2 0 / 0; wide2 periodic (1, 1) 0 / 0; wide2 walled
# (1, 1) and auto 3.2e-10 (dh) / 1.19e-6 (u; v 9.0e-7); wide2 periodic
# (2, 2) 2.9e-7 (v) / the same; wide2 walled (2, 2) 9.8e-7 (v) / the same.
# One step fewer reads 5.5e-1 on u.  With XLA's fusion pass off every one
# reads 0 (the test below), so what is read here is LLVM contracting
# multiply-adds across the instructions XLA fused, and which ones it fuses
# changes with the program around the interpreted kernel.  Read again on
# the aligned frame (PR 36: 64 x 128 cells a rank for 64 x 96, another
# program round the same kernels): wide2 walled (1, 1) and auto 9.1e-7 (v),
# walled (2, 2) 0, and wide2 periodic 2.00e-6 on (1, 1) and 2.01e-6 on
# (2, 2) (v): the limit there is the next round figure above.  Fusion off
# still reads 0 in every case, and ``nan`` in the frame's dead cells leaves
# every bit as it was (tests/test_wide_dead_cells.py): rounding, not reach.
STEPPER_ATOL = {("wide2", (1, 1), False): 2e-6, ("auto", (1, 1), False): 2e-6,
                ("wide2", (1, 1), True): 3e-6, ("wide2", (2, 2), True): 3e-6}


@pytest.mark.parametrize("fast,mesh,periodic_x", CASES)
def test_fused_runner_gives_what_solve_fused_gives(fast, mesh, periodic_x):
    """Bit for bit, called as a region and pinned with ``mpx.compile``.  The
    stepper's two programs (another route through the same kernels: the
    frame built twice, cropped twice) agree with it to rounding: XLA fuses
    two programs' arithmetic differently, an ulp here and there
    (``STEPPER_ATOL``: 1e-6 but on the walled single rank and the periodic
    ``wide2`` cases)."""
    cfg = _config(mesh, periodic_x)
    devices = jax.devices()[: cfg.nproc]
    _, n_steps, want = sw.solve_fused(
        cfg, STEPS * cfg.dt, num_multisteps=1, devices=devices, fast=fast,
        return_state=True)
    assert n_steps == STEPS

    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=devices)
    fused, chunk_size = sw.fused_runner(cfg, comm, fast)
    assert chunk_size == sw.leg_plan(cfg, fast, STEPS)[
        "steps_per_kernel_call"] == 2
    state = sw.initial_state(cfg, comm)
    first_step, multistep = sw.make_stepper(cfg, comm, fast=fast)
    runs = {"region": fused(state, STEPS - 1),
            "pinned": mpx.compile(fused, state, STEPS - 1)(state),
            "stepper": multistep(first_step(state), STEPS - 1)}
    atol = STEPPER_ATOL.get((fast, mesh, periodic_x), 1e-6)
    for how, got in runs.items():
        for name, a, b in zip(want._fields, got, want):
            a, b = np.asarray(a), np.asarray(b)
            if how == "stepper":
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=atol,
                                           err_msg=name)
            else:
                np.testing.assert_array_equal(a, b, f"{how}: {name}")


def _unfused(fn, *args):
    """``fn(*args)`` compiled with XLA's fusion pass off: every instruction
    is a loop of its own, LLVM contracts no multiply-add across two of them,
    and the arithmetic is the program's whatever stands around it."""
    compiled = jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_disable_hlo_passes": "fusion"})
    return compiled(*args)


@pytest.mark.parametrize("fast,mesh,periodic_x", CASES)
def test_the_leg_is_its_rounds_bit_for_bit_with_fusion_off(
        monkeypatch, fast, mesh, periodic_x):
    """The same kernels on the same operands in the same order, whatever
    the loop's form: with XLA's fusion pass off the leg gives, bit for
    bit, what the stepper's two programs give over the same rounds (the
    frame built twice, a call off the fresh frame, the loop split
    elsewhere), and what the leg gives with every loop run one call an
    iteration, the form before the carry was ping-ponged."""
    cfg = _config(mesh, periodic_x)
    _mesh, comm = sw.make_mesh_and_comm(cfg,
                                        devices=jax.devices()[: cfg.nproc])
    state = sw.initial_state(cfg, comm)
    fused, _ = sw.fused_runner(cfg, comm, fast)
    want = _unfused(lambda s: fused(s, STEPS - 1), state)

    first_step, multistep = sw.make_stepper(cfg, comm, fast=fast)
    runs = {"stepper": _unfused(lambda s: multistep(s, STEPS - 1),
                                _unfused(first_step, state))}

    fori_loop, unrolled = jax.lax.fori_loop, []

    def one_call_an_iteration(lower, upper, body, init, unroll=None):
        unrolled.append(unroll)
        return fori_loop(lower, upper, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", one_call_an_iteration)
    plain, _ = sw.fused_runner(cfg, comm, fast)  # traced anew
    runs["one call an iteration"] = _unfused(
        lambda s: plain(s, STEPS - 1), state)
    assert 2 in unrolled  # the leg's own loop was among those rewritten

    for how, got in runs.items():
        for name, a, b in zip(want._fields, got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          f"{how}: {name}")


class _Tally:
    """Counts the calls a leg makes while it is traced: a call inside a
    ``fori_loop``'s body counts once per trip."""

    def __init__(self, monkeypatch):
        self.counts = {}
        self.trips = []
        self.times = 1
        fori_loop = jax.lax.fori_loop

        def counting_loop(lower, upper, body, init, **kw):
            self.trips.append(upper - lower)
            before, self.times = self.times, self.times * (upper - lower)
            try:
                return fori_loop(lower, upper, body, init, **kw)
            finally:
                self.times = before

        monkeypatch.setattr(jax.lax, "fori_loop", counting_loop)
        self.monkeypatch = monkeypatch

    def count(self, name, key=lambda *a, **k: ""):
        inner = getattr(sw, name)

        def counted(*args, **kwargs):
            label = name + key(*args, **kwargs)
            self.counts[label] = self.counts.get(label, 0) + self.times
            return inner(*args, **kwargs)

        self.monkeypatch.setattr(sw, name, counted)


def _solver_tally(monkeypatch):
    """A tally of the kernel calls by ``/first_step/nsteps`` and of the
    widened frame's builds, refreshes and crops."""
    tally = _Tally(monkeypatch)
    # first_step, nsteps: positional in _wide_kernel_call, by keyword or
    # default in model_step_pallas
    tally.count("_wide_kernel_call",
                lambda wf, cfg, first, nsteps, *a: f"/{first}/{nsteps}")
    tally.count("model_step_pallas",
                lambda s, cfg, comm, first_step, interpret=None, nsteps=1:
                f"/{first_step}/{nsteps}")
    for name in ("_wide_exchange", "_wide_refresh", "_wide_crop"):
        tally.count(name)
    return tally


@pytest.mark.parametrize("steps", range(1, 24))
@pytest.mark.parametrize("fast,periodic_x", [("wide2", False),
                                             ("pallas2", True)])
def test_leg_plan_counts_the_calls_a_leg_makes(monkeypatch, fast,
                                               periodic_x, steps):
    cfg = _config((1, 1), periodic_x)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    tally = _solver_tally(monkeypatch)
    fused, _ = sw.fused_runner(cfg, comm, fast)
    state = sw.initial_state(cfg, comm)
    jax.eval_shape(lambda s: fused(s, steps - 1), state)

    plan = sw.leg_plan(cfg, fast, steps)
    kernel = "_wide_kernel_call" if fast == "wide2" else "model_step_pallas"
    got = tally.counts
    assert plan["steps"] == steps == (
        plan["euler_calls"] + 2 * plan["chunk_calls"]
        + plan["single_step_calls"])
    assert got.get(f"{kernel}/True/1", 0) == plan["euler_calls"] == 1
    assert got.get(f"{kernel}/False/2", 0) == plan["chunk_calls"]
    assert got.get(f"{kernel}/False/1", 0) == plan["single_step_calls"]
    assert got.get("_wide_exchange", 0) == plan["frames_built"]
    assert got.get("_wide_refresh", 0) == plan["band_refreshes"]
    assert got.get("_wide_crop", 0) == plan["crops"]
    # one loop, over the chunk calls (two a trip where ``_run_steps``
    # unrolls, which is its own to say), and none where there is no chunk
    assert tally.trips == ([plan["chunk_calls"]] if plan["chunk_calls"]
                           else [])


def test_leg_plan_of_a_mode_without_a_chunk_kernel():
    cfg = _config((1, 1), True)
    plan = sw.leg_plan(cfg, True, 7)
    assert (plan["euler_calls"], plan["chunk_calls"],
            plan["single_step_calls"]) == (1, 0, 6)
    assert not (plan["frames_built"] or plan["band_refreshes"]
                or plan["crops"])
    with pytest.raises(ValueError, match="Euler"):
        sw.leg_plan(cfg, "auto", 0)


# -- ``_run_steps`` handed the wide-halo pair (PR 38) ------------------------
#
# ``select_steps`` is "the single source of truth for every driver", and a
# driver that composes it with ``_run_steps`` itself (the benchmark's
# ``chipbench/drivers/solver.py`` does) hands ``_run_steps`` the pair
# ``(model_step_wide, model_step2_wide)`` wherever ``auto`` resolves to
# ``"wide2"`` — since PR 38 on one periodic chip too.  It must then run on
# the carried frame and not build and crop one a chunk.

WIDE_PAIR_CASES = [((1, 1), True), ((1, 1), False), ((2, 2), True)]


def _composed(cfg, comm, fast="auto"):
    """The two regions a driver builds from ``select_steps`` and
    ``_run_steps``: the steps after the first alone, and the whole leg as
    ``chipbench/drivers/solver.py`` builds it."""
    step, chunk, chunk_size = sw.select_steps(fast, cfg)

    @partial(mpx.spmd, comm=comm, static_argnums=(1,))
    def rest(state, num_steps):
        return sw._run_steps(state, num_steps, cfg, comm, step, chunk,
                             chunk_size)

    @partial(mpx.spmd, comm=comm, static_argnums=(1,))
    def leg(state, total):
        state = step(state, cfg, comm, first_step=True)
        return sw._run_steps(state, total, cfg, comm, step, chunk,
                             chunk_size)

    return rest, leg


@pytest.mark.parametrize("mesh,periodic_x", WIDE_PAIR_CASES)
def test_run_steps_given_the_wide_pair_is_the_leg_less_its_euler_step(
        mesh, periodic_x):
    """Bit for bit: ``_run_steps`` with the pair ``auto`` gives is
    ``_wide_run`` without the Euler call — the program of ``make_stepper``'s
    ``multistep``, the ``fused_runner`` leg less its first step — over an
    odd chunk count with a step over; and the leg a driver composes from
    it (its Euler step on a frame of its own, cropped, then the rest) has
    with XLA's fusion pass off the bits of ``fused_runner``'s."""
    cfg = _config(mesh, periodic_x)
    assert sw._resolve_mode("auto", cfg) == "wide2"
    _mesh, comm = sw.make_mesh_and_comm(cfg,
                                        devices=jax.devices()[: cfg.nproc])
    state = sw.initial_state(cfg, comm)
    rest, leg = _composed(cfg, comm)
    first_step, multistep = sw.make_stepper(cfg, comm, fast="auto")
    after_first = first_step(state)
    got, want = rest(after_first, STEPS - 1), multistep(after_first,
                                                        STEPS - 1)
    assert isinstance(got, sw.State)
    for name, a, b in zip(want._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    assert np.abs(np.asarray(got.u) - np.asarray(after_first.u)).max() > 1e-2

    fused, _ = sw.fused_runner(cfg, comm, "auto")
    want = _unfused(lambda s: fused(s, STEPS - 1), state)
    got = _unfused(lambda s: leg(s, STEPS - 1), state)
    for name, a, b in zip(want._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


@pytest.mark.parametrize("num_steps", [0, 1, 2, 3, 4, 5, 6, 7, 10, 11, 70])
def test_run_steps_given_the_wide_pair_builds_one_frame_and_crops_once(
        monkeypatch, num_steps):
    """One periodic rank, whatever ``num_steps`` — no chunk, even and odd
    chunk counts, a step over: one frame built, one crop, a refresh before
    every kernel call but the first (``_wide_schedule``), and never a
    frame a chunk, which is what a loop of ``model_step2_wide`` made."""
    cfg = _config((1, 1), True)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    tally = _solver_tally(monkeypatch)
    rest, _leg = _composed(cfg, comm)
    state = sw.initial_state(cfg, comm)
    out = jax.eval_shape(lambda s: rest(s, num_steps), state)
    assert out == jax.eval_shape(lambda s: s, state)
    got = tally.counts
    used = int(num_steps > 0)
    assert got.get("_wide_exchange", 0) == used
    assert got.get("_wide_crop", 0) == used
    assert got.get("_wide_kernel_call/False/2", 0) == num_steps // 2
    assert got.get("_wide_kernel_call/False/1", 0) == num_steps % 2
    assert not got.get("_wide_kernel_call/True/1", 0)
    head, trips, rem = sw._wide_schedule(num_steps, 2, False)
    assert head + trips == num_steps // 2 and rem == num_steps % 2
    # the first kernel call runs off the frame as it was built
    assert got.get("_wide_refresh", 0) == max(
        num_steps // 2 + num_steps % 2 - 1, 0)
    assert tally.trips == ([trips] if trips else [])


def test_run_steps_leaves_the_other_modes_their_loop(monkeypatch):
    """The branch reads the chunk function it is handed: the whole-step
    pair still loops over ``model_step2_pallas``, no frame anywhere."""
    cfg = _config((1, 1), True)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    tally = _solver_tally(monkeypatch)
    rest, _leg = _composed(cfg, comm, "pallas2")
    jax.eval_shape(lambda s: rest(s, 11), sw.initial_state(cfg, comm))
    assert tally.counts == {"model_step_pallas/False/2": 5,
                            "model_step_pallas/False/1": 1}
    assert tally.trips == [5]
