"""Example-as-test: the shallow-water demo (SURVEY.md §4 "Example-as-test",
ref tests/test_examples.py:20-24 runs the demo and asserts snapshot count).

Beyond the reference's smoke test, the SPMD design enables a much stronger
property the reference cannot test in one process: *decomposition
invariance* — the same model run on a (2, 4) mesh and on a single device
must produce the same fields.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))

from shallow_water import (  # noqa: E402
    Config,
    initial_state,
    reassemble,
    solve,
    solve_fused,
)


def test_shallow_water_runs_and_snapshots():
    # ref tests/test_examples.py asserts >100 snapshots over 1 model day;
    # scaled down here (30 steps, multistep 10 -> 5 snapshots) to keep CI
    # fast while exercising the identical code path
    cfg = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    snaps, wall, n_steps = solve(cfg, 30 * cfg.dt, num_multisteps=10)
    assert n_steps >= 30
    assert len(snaps) >= 4
    final = reassemble(snaps[-2], cfg)
    # water height stays near the resting depth (stable integration)
    assert np.all(np.isfinite(final))
    assert 90 < final.mean() < 110


def test_shallow_water_decomposition_invariance():
    steps = 20
    cfg8 = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    s8, _, _ = solve(cfg8, steps * cfg8.dt, num_multisteps=5)
    cfg1 = Config(nproc_y=1, nproc_x=1, nx=48, ny=24)
    s1, _, _ = solve(cfg1, steps * cfg1.dt, num_multisteps=5,
                     devices=jax.devices()[:1])
    g8 = reassemble(s8[-2], cfg8)
    g1 = reassemble(s1[-2], cfg1)
    np.testing.assert_allclose(g8, g1, rtol=1e-5, atol=1e-4)


def test_shallow_water_gathered_solution_matches_stacked():
    cfg = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    snaps, _, _ = solve(cfg, 10 * cfg.dt, num_multisteps=5)
    # the last snapshot is the eager-gather copy of the final stacked state:
    # identical values in identical rank order (catches any gather
    # rank-ordering regression on the multi-axis comm)
    assert snaps[-1].shape == snaps[0].shape
    np.testing.assert_array_equal(snaps[-1], snaps[-2])


def test_solve_fused_matches_host_loop_step_count():
    # the fused (single-dispatch) benchmark path must run exactly the same
    # number of model steps as the host-loop path
    cfg = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    t1 = 23 * cfg.dt
    _, _, n_host = solve(cfg, t1, num_multisteps=5, collect=False)
    wall, n_fused = solve_fused(cfg, t1, num_multisteps=5)
    assert n_fused == n_host
    assert wall > 0


def test_initial_state_decomposition_independent():
    cfg8 = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    cfg1 = Config(nproc_y=1, nproc_x=1, nx=48, ny=24)
    g8 = reassemble(np.asarray(initial_state(cfg8).h), cfg8)
    g1 = reassemble(np.asarray(initial_state(cfg1).h), cfg1)
    np.testing.assert_array_equal(g8, g1)


@pytest.mark.parametrize("periodic", [True, False])
def test_shallow_water_boundary_modes(periodic):
    from dataclasses import replace

    cfg = replace(Config(nproc_y=2, nproc_x=4, nx=48, ny=24),
                  periodic_x=periodic)
    snaps, _, _ = solve(cfg, 10 * cfg.dt, num_multisteps=5)
    assert np.all(np.isfinite(reassemble(snaps[-2], cfg)))


@pytest.mark.parametrize("grid", [(1, 1), (2, 4)])
@pytest.mark.parametrize("periodic", [True, False])
def test_fast_step_matches_reference_step(grid, periodic):
    """model_step_fast must reproduce model_step field-for-field, on both a
    single-rank and a 2-D decomposition, in both boundary modes.

    Tolerance: the two programs deliberately differ in seam-halo freshness
    around the viscous substep (model_step_fast docstring) — an artifact of
    the same size as the *reference's own* decomposition variance (its
    (1,1)-vs-(2,4) results differ by ~5e-5; see the invariance tests
    below).  A halo-logic bug would produce O(field-scale) errors, far
    above this band."""
    from dataclasses import replace

    from shallow_water import make_mesh_and_comm, make_stepper

    ny_, nx_ = grid
    cfg = replace(
        Config(nproc_y=ny_, nproc_x=nx_, nx=48, ny=24), periodic_x=periodic
    )
    devices = jax.devices()[: cfg.nproc]
    _, comm = make_mesh_and_comm(cfg, devices=devices)
    first_ref, multi_ref = make_stepper(cfg, comm, fast=False)
    first_fast, multi_fast = make_stepper(cfg, comm, fast=True)

    s0 = initial_state(cfg)
    ref = multi_ref(first_ref(s0), 20)
    fast = multi_fast(first_fast(s0), 20)
    # On a (1,1) grid there are no subdomain seams, so the freshness
    # artifact is absent and the remaining divergence is pure
    # reordered-arithmetic rounding, bounded by f32 ulps at the stencil's
    # *intermediate* scale (g·h ≈ 1e3 → ~5e-5 absolute; measured flat from
    # step 1 to 20, i.e. non-accumulating).  Assert a 5×-tighter constant
    # term than the seam band so a small-field regression cannot hide
    # under the loose bound.
    single_rank = grid == (1, 1)
    for name, a, b in zip(ref._fields, ref, fast):
        a, b = np.asarray(a), np.asarray(b)
        if single_rank:
            bound = 2e-5 + 1e-5 * np.abs(a).max()
        else:
            bound = 1e-4 + 1e-5 * np.abs(a).max()
        assert np.abs(a - b).max() <= bound, (
            f"field {name} diverged beyond the freshness band "
            f"(grid={grid}, periodic={periodic}): "
            f"max abs {np.abs(a - b).max():.3e} > {bound:.3e}"
        )


def _pallas_grid_cases():
    """Grid sizes derived from the kernel's block size so coverage tracks
    _PBLK: a partial single block, exactly one full block, exactly two
    full blocks, and full blocks + a partial trailing block — the last
    two exercise the multi-block prev/next margin index maps and their
    clip-at-edge handling, the path the benchmark config (15 blocks) runs."""
    from shallow_water import _PBLK

    return [
        (_PBLK - 8, 48),        # single partial block
        (_PBLK - 2, 48),        # exactly one full block (ny_local == _PBLK)
        (2 * _PBLK - 2, 48),    # exactly two full blocks
        (2 * _PBLK + 14, 40),   # two full + partial trailing, nx_local=42
    ]


@pytest.mark.parametrize("mode,steps", [
    ("pallas2", (10, 11)),  # whole pairs; pair + odd single remainder
    ("pallas2", (70,)),     # 35 pairs: the loop's odd chunk count
])
@pytest.mark.slow
@pytest.mark.parametrize("ny,nx", _pallas_grid_cases())
def test_pallas_chunk_step_matches_fast_steps(ny, nx, mode, steps):
    """The chunk kernel (2 fused steps per call; margins of 8 rows per
    fused step: 16) must reproduce model_step_fast over runs that mix the
    single first step, whole chunk calls, and single-step remainders."""
    from shallow_water import make_mesh_and_comm, make_stepper

    cfg = Config(nproc_y=1, nproc_x=1, nx=nx, ny=ny)
    devices = jax.devices()[:1]
    _, comm = make_mesh_and_comm(cfg, devices=devices)
    first_fast, multi_fast = make_stepper(cfg, comm, fast=True)
    first_pal, multi_pal = make_stepper(cfg, comm, fast=mode)

    s0 = initial_state(cfg)
    for nsteps in steps:
        fast = multi_fast(first_fast(s0), nsteps)
        pal = multi_pal(first_pal(s0), nsteps)
        for name, a, b in zip(fast._fields, fast, pal):
            a, b = np.asarray(a), np.asarray(b)
            # pure reordered-arithmetic rounding (verified diffuse across
            # rows, not block-boundary-concentrated): observed max 7.6e-6
            # (h, scale 1e2) / 2.2e-6 (v, scale 5e-2) after 11 steps
            # The rounding grows with the steps: over the four grids the
            # largest gap (v) read 0.56 of this bound after 11 steps and
            # 1.85 (9.4e-6) after 70, so the longer run gets 1.5 x its own
            # reading.
            room = {70: 2.8}.get(nsteps, 1.0)
            bound = (5e-6 + 1e-6 * np.abs(a).max()) * room
            assert np.abs(a - b).max() <= bound, (
                f"field {name} diverged (ny={ny}, nx={nx}, nsteps={nsteps}): "
                f"max abs {np.abs(a - b).max():.3e} > {bound:.3e}"
            )


@pytest.mark.slow
@pytest.mark.parametrize("ny,nx", _pallas_grid_cases())
def test_pallas_step_matches_fast_step(ny, nx):
    """The fused whole-step Pallas kernel (interpret mode on CPU) must
    reproduce model_step_fast on the single-rank periodic-x configs it is
    restricted to, including row counts that are not multiples of the
    32-row block, at tight tolerance: same elementwise operand values, so
    the only divergence is fusion-order rounding (~1 ulp/step — observed
    max 1.1e-6 after 11 steps), far below the 1e-4 freshness band of the
    fast-vs-reference test."""
    from functools import partial

    import mpi4jax_tpu as mpx
    from shallow_water import (
        _run_steps,
        make_mesh_and_comm,
        make_stepper,
        model_step_pallas,
    )

    cfg = Config(nproc_y=1, nproc_x=1, nx=nx, ny=ny)
    devices = jax.devices()[:1]
    _, comm = make_mesh_and_comm(cfg, devices=devices)
    first_fast, multi_fast = make_stepper(cfg, comm, fast=True)

    # the one-step kernel alone, step after step: in "pallas2" it runs the
    # Euler step and an odd count's last step only
    @partial(mpx.spmd, comm=comm)
    def eleven_single_steps(state):
        state = model_step_pallas(state, cfg, comm, first_step=True)
        return _run_steps(state, 10, cfg, comm, model_step_pallas, None, 1)

    s0 = initial_state(cfg)
    fast = multi_fast(first_fast(s0), 10)
    pal = eleven_single_steps(s0)
    for name, a, b in zip(fast._fields, fast, pal):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5,
            err_msg=f"field {name} diverged (ny={ny}, nx={nx})",
        )


@pytest.mark.parametrize("num_steps", [2, 3, 4, 5, 6, 7, 70, 71])
def test_run_steps_makes_the_same_calls_in_the_same_order(num_steps):
    """``_run_steps`` advances two chunks per loop iteration.  For each
    shape that loop compiles to (1, 2, 3 and 35 chunks of two steps: no
    loop, one pair, a pair and a chunk, 17 pairs and a chunk), with and
    without a remainder step, the result is bit for bit that of ``chunk`` x
    ``num_steps // 2`` and then ``step`` x the remainder.  The stand-ins
    are affine maps on int32 that mix the fields and do not commute, so a
    call left out, doubled or out of order changes the result, and no
    rounding can hide it."""
    from shallow_water import State, _run_steps

    def affine(a, b):
        def apply(state, cfg, comm, first_step):
            assert (cfg, comm, first_step) == ("cfg", "comm", False)
            rolled = state[1:] + state[:1]
            return State(*[a * x + y + b + i for i, (x, y)
                           in enumerate(zip(state, rolled))])
        return apply

    step, chunk = affine(3, 1), affine(5, 3)
    s0 = State(*[jnp.arange(12, dtype=jnp.int32).reshape(3, 4) + 7 * i
                 for i in range(6)])
    want = s0
    for _ in range(num_steps // 2):
        want = chunk(want, "cfg", "comm", False)
    for _ in range(num_steps % 2):
        want = step(want, "cfg", "comm", False)

    got = jax.jit(lambda s: _run_steps(s, num_steps, "cfg", "comm", step,
                                       chunk, 2))(s0)
    for name, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def test_pallas_step_rejects_multirank_config():
    from shallow_water import make_mesh_and_comm, make_stepper

    cfg = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    _, comm = make_mesh_and_comm(cfg)
    with pytest.raises(ValueError, match="single-rank periodic-x"):
        first, _ = make_stepper(cfg, comm, fast="pallas2")
        first(initial_state(cfg))


def test_select_step_auto_picks_kernel_by_mesh():
    from dataclasses import replace

    from shallow_water import (
        model_step_pallas,
        model_step_pallas_halo,
        model_step_wide,
        select_steps,
    )

    def select_step(fast, cfg):
        return select_steps(fast, cfg)[0]

    # the wide-halo kernel wherever the local interior fits its 16-cell
    # exchange depth, whatever the mesh and the boundary (PR 38); below
    # that the whole-step kernel where every refresh is an in-register
    # periodic fix (one rank, periodic in x), split-phase for the rest
    single = Config(nproc_y=1, nproc_x=1, nx=48, ny=24)
    assert select_step("auto", single) is model_step_wide
    small_single = Config(nproc_y=1, nproc_x=1, nx=48, ny=12)
    assert select_step("auto", small_single) is model_step_pallas
    multi = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)  # 12x12 interior
    assert select_step("auto", multi) is model_step_pallas_halo
    big_multi = Config(nproc_y=2, nproc_x=4, nx=64, ny=32)  # 16x16 interior
    assert select_step("auto", big_multi) is model_step_wide
    walls = replace(single, periodic_x=False)  # 24x48 interior
    assert select_step("auto", walls) is model_step_wide
    small_walls = replace(Config(nproc_y=1, nproc_x=1, nx=48, ny=12),
                          periodic_x=False)
    assert select_step("auto", small_walls) is model_step_pallas_halo


@pytest.mark.parametrize("mesh,nx,ny,periodic_x,want", [
    ((1, 1), 48, 24, True, "wide2"),         # single periodic rank, fits
    ((1, 1), 3600, 28800, True, "wide2"),    # the benchmark's periodic chip
    ((1, 1), 48, 15, True, "pallas2"),       # ... interior under 16 rows
    ((1, 1), 15, 48, True, "pallas2"),       # ... under 16 columns
    ((1, 1), 48, 16, True, "wide2"),         # ... at the depth exactly
    ((1, 1), 48, 24, False, "wide2"),        # walled, fits
    ((1, 1), 48, 12, False, "pallas_halo"),  # walled, too small
    ((2, 2), 64, 32, True, "wide2"),         # 2x2 periodic, 16 x 32 a rank
    ((2, 2), 64, 32, False, "wide2"),
    ((2, 2), 48, 24, True, "pallas_halo"),   # 2x2, 12 rows a rank
    ((2, 2), 48, 24, False, "pallas_halo"),
])
def test_auto_resolves_by_what_fits_the_exchange_depth(mesh, nx, ny,
                                                       periodic_x, want):
    """``auto`` reads the local interior first and the mesh and boundary
    only below the wide-halo kernel's exchange depth; a mode named by the
    caller is left as it is, so ``"pallas2"`` stays a mode one can ask
    for."""
    from shallow_water import _margin_rows, _resolve_mode, select_steps

    cfg = Config(nproc_y=mesh[0], nproc_x=mesh[1], nx=nx, ny=ny,
                 periodic_x=periodic_x)
    assert _resolve_mode("auto", cfg) == want
    fits = min(cfg.ny_local, cfg.nx_local) - 2 >= _margin_rows(2)
    assert fits == (want == "wide2")
    assert select_steps("auto", cfg) == select_steps(want, cfg)
    for named in ("pallas2", "wide2", "pallas_halo", True, False):
        assert _resolve_mode(named, cfg) is named


@pytest.mark.parametrize("name", ["pallas3", "pallas", "wide", "fast"])
def test_select_steps_refuses_a_name_that_is_no_mode(name):
    """The modes are what ``auto`` can pick and the two references: a mode
    that has gone, or any other name, is refused with the modes named, and
    not read as ``True`` (``model_step_fast``) as an unknown string was."""
    from shallow_water import make_mesh_and_comm, make_stepper, select_steps

    cfg = Config(nproc_y=1, nproc_x=1, nx=48, ny=24)
    with pytest.raises(ValueError, match="'pallas2', 'wide2'"):
        select_steps(name, cfg)
    _, comm = make_mesh_and_comm(cfg, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match=repr(name)):
        make_stepper(cfg, comm, fast=name)


@pytest.mark.slow
@pytest.mark.parametrize("grid", [(1, 1), (2, 4), (2, 2)])
@pytest.mark.parametrize("periodic", [True, False])
def test_wide_step_matches_fast_step(grid, periodic):
    """The communication-avoiding wide-halo path (``wide2``: pair kernel +
    16-deep exchange) must reproduce ``model_step_fast`` on every mesh and
    boundary mode, over a run mixing the single first step, whole pair
    calls, and a single-step remainder (11 steps).  Seam cells recomputed
    in the widened frame use the identical expression tree on the
    identical operand values the owning rank uses, so the only divergence
    is fusion-order (FMA-grouping) rounding from the differently-shaped
    program — ~1 ulp/step, the same class and bound as the single-rank
    chunk-kernel tests (measured worst 0.47x this bound after 11 steps)."""
    from dataclasses import replace

    from shallow_water import make_mesh_and_comm, make_stepper

    ny_, nx_ = grid
    cfg = replace(
        Config(nproc_y=ny_, nproc_x=nx_, nx=64, ny=32), periodic_x=periodic
    )
    devices = jax.devices()[: cfg.nproc]
    _, comm = make_mesh_and_comm(cfg, devices=devices)
    first_fast, multi_fast = make_stepper(cfg, comm, fast=True)
    first_wide, multi_wide = make_stepper(cfg, comm, fast="wide2")

    s0 = initial_state(cfg)
    fast = multi_fast(first_fast(s0), 11)
    wide = multi_wide(first_wide(s0), 11)
    for name, a, b in zip(fast._fields, fast, wide):
        a, b = np.asarray(a), np.asarray(b)
        bound = 5e-6 + 1e-6 * np.abs(a).max()
        assert np.abs(a - b).max() <= bound, (
            f"field {name} diverged (grid={grid}, periodic={periodic}): "
            f"max abs {np.abs(a - b).max():.3e} > {bound:.3e}"
        )


@pytest.mark.slow
def test_wide_step_decomposition_invariance_ulp():
    """Decomposition invariance of the wide-halo path, to ~1 ulp: the
    carried widened frame's shape depends on the decomposition (local
    interior + 2x15 margins), so XLA's FMA grouping can differ between
    the (1,1) and (2,4) programs — unlike the fast/split-phase paths,
    whose per-rank arrays it keeps bit-exact.  Measured: exactly 1 f32
    ulp of the field scale after 20 steps (7.6e-6 at h ~ 100); a halo
    or mask bug would be O(field-scale)."""
    steps = 20
    cfg8 = Config(nproc_y=2, nproc_x=4, nx=64, ny=32)
    s8, _, _ = solve(cfg8, steps * cfg8.dt, num_multisteps=5, fast="wide2")
    cfg1 = Config(nproc_y=1, nproc_x=1, nx=64, ny=32)
    s1, _, _ = solve(cfg1, steps * cfg1.dt, num_multisteps=5, fast="wide2",
                     devices=jax.devices()[:1])
    g8 = reassemble(s8[-2], cfg8)
    g1 = reassemble(s1[-2], cfg1)
    bound = 2e-6 * max(1.0, float(np.abs(g1).max()))
    assert np.abs(g8 - g1).max() <= bound, (
        f"{np.abs(g8 - g1).max():.3e} > {bound:.3e}"
    )


@pytest.mark.slow
def test_wide_fused_driver_matches_fast_end_state():
    """``solve_fused``'s wide modes run a dedicated carried-frame program
    (widen once, margin-band refresh per pair, crop once): its end state
    must match the fast path's fused program over a run with first step,
    whole pairs and a remainder (26 steps; bound scaled for the longer
    accumulation, measured worst 1.01x the 11-step band)."""
    cfg = Config(nproc_y=2, nproc_x=4, nx=64, ny=32)
    t1 = 23 * cfg.dt
    _, n_a, sa = solve_fused(cfg, t1, num_multisteps=5, fast=True,
                             return_state=True)
    _, n_b, sb = solve_fused(cfg, t1, num_multisteps=5, fast="wide2",
                             return_state=True)
    assert n_a == n_b
    for name, a, b in zip(sa._fields, sa, sb):
        a, b = np.asarray(a), np.asarray(b)
        bound = 1e-5 + 2e-6 * np.abs(a).max()
        assert np.abs(a - b).max() <= bound, (
            f"field {name} diverged: {np.abs(a - b).max():.3e} > {bound:.3e}"
        )


@pytest.mark.slow
def test_wide_standalone_step_matches_stepper():
    """The standalone per-step form (``model_step_wide``: exchange + one
    kernel call + crop, at its own exchange depth 8) must agree with the
    carried-frame stepper's first step (depth 16) — same arithmetic on
    differently-sized frames, so up to ~1 ulp of fusion-order rounding."""
    from functools import partial

    import mpi4jax_tpu as mpx
    from shallow_water import (
        make_mesh_and_comm,
        make_stepper,
        model_step_wide,
    )

    cfg = Config(nproc_y=2, nproc_x=4, nx=64, ny=32)
    _, comm = make_mesh_and_comm(cfg)
    s0 = initial_state(cfg)

    @partial(mpx.spmd, comm=comm)
    def one(state):
        return model_step_wide(state, cfg, comm, first_step=True)

    a = make_stepper(cfg, comm, fast="wide2")[0](s0)
    b = one(s0)
    for name, x, y in zip(a._fields, a, b):
        x, y = np.asarray(x), np.asarray(y)
        bound = 5e-6 + 1e-6 * np.abs(x).max()
        assert np.abs(x - y).max() <= bound, (
            f"field {name}: {np.abs(x - y).max():.3e} > {bound:.3e}"
        )


def test_wide_step_rejects_small_interior():
    from shallow_water import make_mesh_and_comm, make_stepper

    cfg = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)  # 12x12 < 16
    _, comm = make_mesh_and_comm(cfg)
    # the carried frame is sized for the pair chunk (exchange depth 16),
    # which a 12-cell interior cannot supply from its immediate neighbor
    first, _ = make_stepper(cfg, comm, fast="wide2")
    with pytest.raises(ValueError, match="local interior"):
        first(initial_state(cfg))


@pytest.mark.slow
@pytest.mark.parametrize("grid", [(1, 1), (2, 4)])
@pytest.mark.parametrize("periodic", [True, False])
def test_pallas_halo_step_matches_fast_step(grid, periodic):
    """The split-phase path (``model_step_pallas_halo``) must reproduce
    ``model_step_fast`` bit-for-bit on every mesh/boundary combination: its
    interpret path evaluates the same window arithmetic (identical
    expression order) on the full local array with the identical exchange
    sequence, so there is no rounding divergence at all."""
    from dataclasses import replace

    from shallow_water import make_mesh_and_comm, make_stepper

    ny_, nx_ = grid
    cfg = replace(
        Config(nproc_y=ny_, nproc_x=nx_, nx=48, ny=24), periodic_x=periodic
    )
    devices = jax.devices()[: cfg.nproc]
    _, comm = make_mesh_and_comm(cfg, devices=devices)
    first_fast, multi_fast = make_stepper(cfg, comm, fast=True)
    first_halo, multi_halo = make_stepper(cfg, comm, fast="pallas_halo")

    s0 = initial_state(cfg)
    fast = multi_fast(first_fast(s0), 12)
    halo = multi_halo(first_halo(s0), 12)
    for name, a, b in zip(fast._fields, fast, halo):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"field {name} diverged (grid={grid}, periodic={periodic})",
        )


def test_pallas_halo_decomposition_invariance_exact():
    """Like the fast step, the split-phase path is exactly decomposition-
    invariant: same bits on one device and on a (2, 4) mesh."""
    steps = 20
    cfg8 = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    s8, _, _ = solve(cfg8, steps * cfg8.dt, num_multisteps=5,
                     fast="pallas_halo")
    cfg1 = Config(nproc_y=1, nproc_x=1, nx=48, ny=24)
    s1, _, _ = solve(cfg1, steps * cfg1.dt, num_multisteps=5,
                     fast="pallas_halo", devices=jax.devices()[:1])
    g8 = reassemble(s8[-2], cfg8)
    g1 = reassemble(s1[-2], cfg1)
    np.testing.assert_array_equal(g8, g1)


def test_fast_step_decomposition_invariance_exact():
    """The fast step's coherent-halo design makes it *exactly*
    decomposition-invariant (the reference's stale-halo seams make its own
    (1,1)-vs-(2,4) runs differ by ~5e-5): same bits on a single device and
    on a (2,4) mesh."""
    steps = 20
    cfg8 = Config(nproc_y=2, nproc_x=4, nx=48, ny=24)
    s8, _, _ = solve(cfg8, steps * cfg8.dt, num_multisteps=5, fast=True)
    cfg1 = Config(nproc_y=1, nproc_x=1, nx=48, ny=24)
    s1, _, _ = solve(cfg1, steps * cfg1.dt, num_multisteps=5, fast=True,
                     devices=jax.devices()[:1])
    g8 = reassemble(s8[-2], cfg8)
    g1 = reassemble(s1[-2], cfg1)
    np.testing.assert_array_equal(g8, g1)


@pytest.mark.slow
@pytest.mark.parametrize("fast", [True, "pallas_halo", "wide2"])
def test_grad_through_full_multistep(fast):
    """Reverse-mode through the WHOLE flagship workload — first step +
    fori_loop multistep with all halo sendrecvs inside — the composition
    analog of the reference's NetKet-grade allreduce acceptance
    (ref tests/collective_ops/test_allreduce.py:254-324): the gradient must
    match finite differences on the (1, 1) mesh and be decomposition-
    invariant on (2, 4).  Runs for both the fused-jnp step and the
    split-phase path (whose interpret form is plain differentiable jnp)."""
    from shallow_water import make_mesh_and_comm, make_stepper

    steps = 6
    # wide2 needs a 16-cell local interior on the (2, 4) mesh
    gny, gnx = (32, 64) if fast == "wide2" else (8, 16)
    # ONE decomposition-independent perturbation field, shared by both mesh
    # configurations (drawn once — the gradients can only be compared if
    # both losses perturb the same global field)
    bump_global = np.random.RandomState(0).randn(gny + 2, gnx + 2).astype(
        np.float32)

    def make_loss(cfg):
        devices = jax.devices()[: cfg.nproc]
        _, comm = make_mesh_and_comm(cfg, devices=devices)
        first, multi = make_stepper(cfg, comm, fast=fast)
        s0 = initial_state(cfg)

        def cut(arr):
            blocks = []
            sy, sx = cfg.ny_local - 2, cfg.nx_local - 2
            for py in range(cfg.nproc_y):
                for px in range(cfg.nproc_x):
                    blocks.append(arr[py * sy:py * sy + cfg.ny_local,
                                      px * sx:px * sx + cfg.nx_local])
            return jnp.asarray(np.stack(blocks))

        bump = cut(bump_global)

        def loss(amp):
            state = s0._replace(h=s0.h + amp * bump)
            state = multi(first(state), steps)
            # interior-only: stacked interiors tile the global domain
            # disjointly, so the loss is decomposition-invariant
            inner = state.h[:, 1:-1, 1:-1]
            return jnp.sum((inner - 100.0) ** 2)

        return loss

    cfg1 = Config(nproc_y=1, nproc_x=1, nx=gnx, ny=gny)
    loss1 = make_loss(cfg1)
    g1 = jax.grad(loss1)(0.0)

    # finite differences (f32: central difference at a scale-matched eps)
    eps = 1e-2
    fd = (loss1(eps) - loss1(-eps)) / (2 * eps)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(fd), rtol=2e-2)

    cfg8 = Config(nproc_y=2, nproc_x=4, nx=gnx, ny=gny)
    g8 = jax.grad(make_loss(cfg8))(0.0)
    # the fast path is exactly decomposition-invariant, so its gradient is
    # too (up to f32 reduction-order rounding in the loss sum)
    np.testing.assert_allclose(np.asarray(g8), np.asarray(g1), rtol=1e-4)


def test_long_context_training_matches_single_device_grads():
    """The dp x sp training example: the distributed step's allreduced
    loss and parameter update must match a single-device model run on the
    gathered batch/sequence with full attention — the end-to-end pin that
    sequence-parallel training (ring attention under value_and_grad,
    world-allreduced gradients) is exact, not approximate."""
    from long_context_training import (
        block_forward, init_params, make_train_step,
    )

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu.attention import reference_attention

    n, n_dp, n_sp = 8, 2, 4
    mesh = mpx.make_world_mesh((n_dp, n_sp), ("dp", "sp"))
    world = mpx.Comm(("dp", "sp"), mesh=mesh)
    sp = world.sub("sp")

    b_loc, t_loc, d_model, d_ff, heads = 1, 16, 32, 64, 4
    lr = 0.05
    params = init_params(jax.random.PRNGKey(0), d_model, d_ff)
    params_g = {k: jnp.broadcast_to(v, (n, *v.shape))
                for k, v in params.items()}
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (n, b_loc, t_loc, d_model), jnp.float32)
    y = jax.random.normal(ky, (n, b_loc, t_loc), jnp.float32)

    step = make_train_step(world, sp, heads, lr=lr)
    new_params, loss = step(params_g, x, y)

    # single-device reference: rank r = dp * n_sp + sp holds batch row dp,
    # sequence chunk sp — gather to (n_dp * b_loc, T_global, ...)
    def gather(a):
        rows = [jnp.concatenate([a[dp * n_sp + s] for s in range(n_sp)],
                                axis=1) for dp in range(n_dp)]
        return jnp.concatenate(rows, axis=0)

    xg, yg = gather(x), gather(y)

    def loss_full(p):
        pred = block_forward(
            p, xg, heads=heads,
            attend=lambda q, k, v: reference_attention(q, k, v, causal=True),
        )
        return jnp.mean((pred - yg) ** 2)

    l_full, g_full = jax.value_and_grad(loss_full)(params)
    np.testing.assert_allclose(
        float(jnp.asarray(loss)[0]), float(l_full), rtol=1e-5)
    for name in params:
        g_dist = (np.asarray(params_g[name][0])
                  - np.asarray(new_params[name][0])) / lr
        np.testing.assert_allclose(
            g_dist, np.asarray(g_full[name]), rtol=2e-3, atol=2e-5,
            err_msg=f"grad {name}",
        )
