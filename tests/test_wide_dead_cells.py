"""The dead cells of the aligned widened frame reach nothing.

``_wide_exchange`` rounds the frame a rank carries in ``"wide2"`` up to a
whole number of ``(8, 128)`` tiles: *dead* columns beyond the east margin
and dead rows beyond the north margin, which no refresh ever writes and
every kernel call recomputes from themselves.  Its docstring argues that
what a call makes of them stays inside the ``m - 1``-deep margins on either
side (a ``roll`` wraps them onto the west and south ones), which the next
refresh overwrites.  Here that argument is a test: the dead cells of a
handed-on frame are overwritten with ``nan`` or ``1e30``, two more carried
multisteps and the crop follow, and the ``State`` has the bits of the run
that was left alone.  (The same proof is what a refresh that skipped its
zero bands would need: ROADMAP A2d.)
"""

import functools
import os
import sys
import types

import numpy as np
import pytest

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE), os.path.join(HERE, "..", "examples"),
             HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import shallow_water as sw  # noqa: E402
from test_fused_runner import _frame_shape  # noqa: E402

NUM = 10  # solve()'s default num_multisteps
M = 16    # the exchange depth of the two-step kernel call


@functools.lru_cache(maxsize=None)  # the two fills share the programs
def _carried(mesh, periodic_x, ny=32):
    # 64 x 32 a rank: 96 columns of frame and 32 dead; with ``ny`` = 29 a
    # rank, 61 rows and 3 dead
    cfg = sw.Config(nproc_y=mesh[0], nproc_x=mesh[1], nx=64 * mesh[1],
                    ny=ny * mesh[0], periodic_x=periodic_x)
    _mesh, comm = sw.make_mesh_and_comm(cfg,
                                        devices=jax.devices()[: cfg.nproc])
    first_step, multistep = sw.make_stepper(cfg, comm, fast="wide2")
    assert multistep.carried is not multistep
    state = sw.initial_state(cfg, comm)
    built = first_step.carried(state)  # the frame and the Euler step
    return cfg, state, multistep, built, multistep.carried(built, NUM)


def _dead(cfg):
    """``(first dead row, first dead column)`` of a rank's frame."""
    return cfg.ny_local + 2 * (M - 1), cfg.nx_local + 2 * (M - 1)


@pytest.mark.parametrize("fill", [np.nan, 1e30])
@pytest.mark.parametrize("ny", [32, 29])  # no dead row; three of them
@pytest.mark.parametrize("periodic_x", [True, False])
@pytest.mark.parametrize("mesh", [(1, 1), (2, 2)])
def test_what_the_dead_cells_hold_reaches_no_valid_cell(mesh, periodic_x, ny,
                                                        fill):
    cfg, state, multistep, _, frames = _carried(mesh, periodic_x, ny)
    row, col = _dead(cfg)
    rows, cols = frames[0].shape[1:]
    assert (rows, cols) == _frame_shape(cfg) == (64, 128)
    assert cols > col and (rows > row) == (ny == 29)

    def two_more(frames):
        return multistep.crop(
            multistep.carried(multistep.carried(frames, NUM), NUM))

    want = two_more(frames)
    spoiled = tuple(f.at[:, row:, :].set(fill).at[:, :, col:].set(fill)
                    for f in frames)
    got = two_more(spoiled)
    assert isinstance(got, sw.State)
    for name, a, b in zip(want._fields, got, want):
        assert a.shape == state.h.shape
        assert np.isfinite(np.asarray(b)).all(), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    # it moved: the two calls were not a no-op on what is compared
    assert np.abs(np.asarray(want.h) - np.asarray(state.h)).max() > 1e-3


@pytest.mark.parametrize("periodic_x", [True, False])
def test_the_dead_cells_are_built_finite_and_stay_so(periodic_x):
    """Built as a copy of the margin cell beside them (a zero depth is not
    data the stencil is finite on: ``jax.grad`` through a periodic
    multistep returned ``nan`` on a zero fill), and after 31 steps on
    their own still finite: on a periodic rank the masks advance them like
    any cell, at a wall they keep what they were built with."""
    cfg, _, multistep, built, frames = _carried((1, 1), periodic_x, ny=29)
    row, col = _dead(cfg)
    later = multistep.carried(multistep.carried(frames, NUM), NUM)
    for name, f0, f1, f in zip(sw.State._fields, built, frames, later):
        f0, f1, f = np.asarray(f0), np.asarray(f1), np.asarray(f)
        assert np.isfinite(f0).all() and np.isfinite(f).all(), name
        if not periodic_x:  # the dead columns, kept by the wall masks
            np.testing.assert_array_equal(f[:, M:row - M, col:],
                                          f1[:, M:row - M, col:], name)
    # the depth there is a depth (periodic: far-side data; at a wall the
    # beyond-wall zeros that the margin itself holds)
    depth = np.asarray(built[0])[:, M:row - M, col:]
    assert (depth > 0).all() == periodic_x


@pytest.mark.parametrize("fill", [np.nan, 1e30])
@pytest.mark.parametrize("ny", [32, 29])  # no dead row; three of them
def test_a_periodic_chips_host_loop_run_never_reads_its_dead_cells(ny, fill):
    """What ``auto`` gives one periodic rank since PR 38, over a run of the
    documented host loop — 1 + 10 x 10 steps through ``run_multisteps`` on
    the carried frame, whose x bands every refresh reads out of the frame
    itself and whose dead columns the periodic masks *advance* like any
    cell: with the dead cells of every frame a call hands on overwritten
    (eleven times a run), the cropped ``State`` has the bits of the run
    that was left alone."""
    cfg = sw.Config(nx=64, ny=ny)
    _mesh, comm = sw.make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    first_step, multistep = sw.make_stepper(cfg, comm, fast="auto")
    assert sw._resolve_mode("auto", cfg) == "wide2"
    assert multistep.carried is not multistep
    state = sw.initial_state(cfg, comm)
    row, col = _dead(cfg)
    planted = []

    def spoiled(frames):
        assert {f.shape[1:] for f in frames} == {_frame_shape(cfg)}
        planted.append(len(frames))
        return tuple(f.at[:, row:, :].set(fill).at[:, :, col:].set(fill)
                     for f in frames)

    # the pair as the loop sees it: the carried forms and the crop alone
    first = types.SimpleNamespace(
        carried=lambda s: spoiled(first_step.carried(s)))
    multi = types.SimpleNamespace(
        carried=lambda fr, n: spoiled(multistep.carried(fr, n)),
        crop=multistep.crop)

    want = sw.run_multisteps(first_step, multistep, state, 10, NUM)
    got = sw.run_multisteps(first, multi, state, 10, NUM)
    assert planted == [6] * 11
    assert isinstance(got, sw.State)
    for name, a, b in zip(want._fields, got, want):
        assert a.shape == state.h.shape
        assert np.isfinite(np.asarray(b)).all(), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    assert np.abs(np.asarray(want.h) - np.asarray(state.h)).max() > 1e-2
