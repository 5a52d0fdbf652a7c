"""Shallow-water demo — the flagship end-to-end workload.

A nonlinear shallow-water solver on an Arakawa C-grid (energy-conserving
Sadourny scheme, the same physics as the reference demo, which adapts
https://github.com/dionhaefner/shallow-water), re-designed TPU-native.

Where the reference runs one MPI process per subdomain and threads tokens
through per-process ``send``/``recv``/``sendrecv`` calls
(ref /root/reference/examples/shallow_water.py:57-67, 173-271), this version
traces ONE SPMD program over a 2-D device mesh ``("py", "px")``:

- the state lives in *stacked-block* global arrays of shape
  ``(nproc, ny_local, nx_local)`` — rank ``r``'s subdomain (1-cell halo
  included) is ``state[r]`` — sharded over the mesh;
- each halo exchange is a ``sendrecv`` with a static ``shift`` routing on a
  row/column sub-communicator, lowering to a single CollectivePermute over
  ICI per direction (4 per field update vs the reference's ~4 p2p calls,
  but with no host round-trip and no descriptor marshalling);
- the time loop is a ``lax.fori_loop`` *inside* the region, so a whole
  multistep (10 model steps ≈ 40 collectives) is one XLA program that the
  compiler schedules and overlaps.

Usage:

    python shallow_water.py                     # demo, all local devices
    python shallow_water.py --benchmark         # reference benchmark config
    python shallow_water.py --save-animation    # write shallow-water.gif

(plain ``python`` — no ``mpirun``; multi-host pods via
``mpi4jax_tpu.init_distributed()``.)
"""

import argparse
import itertools
import math
import os
import sys
import time
from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import mpi4jax_tpu as mpx
from mpi4jax_tpu import shift

DAY_IN_SECONDS = 86_400


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Config:
    """Model configuration (defaults = the reference demo's parameters,
    ref examples/shallow_water.py:69-135)."""

    # interior grid points (without the 1-cell overlap border)
    nx: int = 360
    ny: int = 180
    # grid spacing [m]
    dx: float = 5e3
    dy: float = 5e3
    # physics
    gravity: float = 9.81
    depth: float = 100.0
    coriolis_f: float = 2e-4
    coriolis_beta: float = 2e-11
    periodic_x: bool = True
    # Adams-Bashforth coefficients
    ab_a: float = 1.5 + 0.1
    ab_b: float = -(0.5 + 0.1)
    # process grid
    nproc_y: int = 1
    nproc_x: int = 1

    @property
    def lateral_viscosity(self) -> float:
        return 1e-3 * self.coriolis_f * self.dx**2

    @property
    def dt(self) -> float:
        # CFL-limited gravity-wave time step
        return 0.125 * min(self.dx, self.dy) / math.sqrt(self.gravity * self.depth)

    @property
    def nproc(self) -> int:
        return self.nproc_y * self.nproc_x

    @property
    def ny_local(self) -> int:
        assert self.ny % self.nproc_y == 0, "nproc_y must divide ny"
        return self.ny // self.nproc_y + 2  # +2 halo cells

    @property
    def nx_local(self) -> int:
        assert self.nx % self.nproc_x == 0, "nproc_x must divide nx"
        return self.nx // self.nproc_x + 2

    @property
    def length_x(self) -> float:
        return self.nx * self.dx

    @property
    def length_y(self) -> float:
        return self.ny * self.dy


class State(NamedTuple):
    """Stacked-block model state: every field is ``(nproc, ny_l, nx_l)``
    globally / ``(ny_l, nx_l)`` rank-local inside the region."""

    h: jax.Array
    u: jax.Array
    v: jax.Array
    dh: jax.Array
    du: jax.Array
    dv: jax.Array


def make_mesh_and_comm(cfg: Config, devices=None):
    """2-D device mesh ``(py, px)`` + communicator over both axes."""
    mesh = mpx.make_world_mesh(
        (cfg.nproc_y, cfg.nproc_x), ("py", "px"), devices=devices
    )
    return mesh, mpx.Comm(("py", "px"), mesh=mesh)


# ---------------------------------------------------------------------------
# initial conditions (host-side, decomposition-independent)
# ---------------------------------------------------------------------------


def initial_state(cfg: Config, comm: mpx.Comm = None) -> State:
    """Geostrophically-balanced zonal jet + perturbation (the reference's
    IC, ref examples/shallow_water.py:138-170), computed globally on the
    host with numpy — identical for every decomposition — then cut into
    stacked local blocks.

    With ``comm`` the blocks go straight from the host to the mesh layout
    the region programs take (block ``r`` on rank ``r``'s device), so no
    later call has to re-shard them off the first device; without it they
    are plain arrays on the default device."""
    # global coordinates including the 1-cell border, cell (1,1) at (0,0)
    x = (np.arange(cfg.nx + 2) - 1.0) * cfg.dx
    y = (np.arange(cfg.ny + 2) - 1.0) * cfg.dy
    yy, xx = np.meshgrid(y, x, indexing="ij")

    u0 = 10 * np.exp(-((yy - 0.5 * cfg.length_y) ** 2) / (0.02 * cfg.length_x) ** 2)
    v0 = np.zeros_like(u0)
    # approximate geostrophic balance: h_y = -(f/g) u
    f = cfg.coriolis_f + yy * cfg.coriolis_beta
    h_geo = np.cumsum(-cfg.dy * u0 * f / cfg.gravity, axis=0)
    h0 = (
        cfg.depth
        + h_geo
        - h_geo.mean()
        + 0.2
        * np.sin(xx / cfg.length_x * 10 * np.pi)
        * np.cos(yy / cfg.length_y * 8 * np.pi)
    )

    def cut(arr):
        blocks = []
        step_y, step_x = cfg.ny_local - 2, cfg.nx_local - 2
        for py in range(cfg.nproc_y):
            for px in range(cfg.nproc_x):
                blocks.append(
                    arr[
                        py * step_y : py * step_y + cfg.ny_local,
                        px * step_x : px * step_x + cfg.nx_local,
                    ]
                )
        return np.stack(blocks).astype(np.float32)

    zeros = np.zeros((cfg.nproc, cfg.ny_local, cfg.nx_local), np.float32)
    state = State(h=cut(h0), u=cut(u0), v=cut(v0), dh=zeros, du=zeros,
                  dv=zeros)
    if comm is None:
        return State(*(jnp.asarray(f) for f in state))
    return mpx.shard_global(state, comm)


def reassemble(stacked: np.ndarray, cfg: Config) -> np.ndarray:
    """Stacked local blocks ``(nproc, ny_l, nx_l)`` → global interior
    ``(ny, nx)`` (the analog of the reference's vmapped ``reassemble_array``,
    ref examples/shallow_water.py:475-490)."""
    interior = np.asarray(stacked)[:, 1:-1, 1:-1]
    ny_i, nx_i = interior.shape[1:]
    grid = interior.reshape(cfg.nproc_y, cfg.nproc_x, ny_i, nx_i)
    return grid.transpose(0, 2, 1, 3).reshape(cfg.nproc_y * ny_i, cfg.nproc_x * nx_i)


# ---------------------------------------------------------------------------
# halo exchange (runs inside the parallel region)
# ---------------------------------------------------------------------------


def enforce_boundaries(arr, kind: str, cfg: Config, comm: mpx.Comm, token):
    """Exchange the 1-cell halo with the four neighbors + apply physical
    boundary conditions.

    Replaces the reference's per-process send/recv/sendrecv ladder
    (ref examples/shallow_water.py:173-271): each direction is one
    ``sendrecv`` with a ``shift`` routing on the row (px) or column (py)
    sub-communicator — a single CollectivePermute over ICI, with edge ranks
    (``wrap=False``) keeping their current halo (MPI_PROC_NULL semantics).
    """
    assert kind in ("h", "u", "v")
    commx = comm.sub("px")
    commy = comm.sub("py")
    wrap_x = cfg.periodic_x

    # (what to send, where received data lands, sub-comm, routing)
    exchanges = (
        # west-to-east halo fill: rank r sends col 1 to r-1, writes col -1
        (np.s_[:, 1], np.s_[:, -1], commx, shift(-1, wrap=wrap_x)),
        # south-to-north: rank r sends row -2 to r+1, writes row 0
        (np.s_[-2, :], np.s_[0, :], commy, shift(+1, wrap=False)),
        # east-to-west: rank r sends col -2 to r+1, writes col 0
        (np.s_[:, -2], np.s_[:, 0], commx, shift(+1, wrap=wrap_x)),
        # north-to-south: rank r sends row 1 to r-1, writes row -1
        (np.s_[1, :], np.s_[-1, :], commy, shift(-1, wrap=False)),
    )
    for send_sel, recv_sel, c, route in exchanges:
        if c.Get_size() == 1 and not route.wrap:
            continue  # no neighbor anywhere along this direction
        received, token = mpx.sendrecv(
            arr[send_sel], arr[recv_sel], dest=route, comm=c, token=token
        )
        arr = arr.at[recv_sel].set(received)

    # physical (non-periodic) walls: no normal flow through the boundary
    if not cfg.periodic_x and kind == "u":
        on_east_wall = jax.lax.axis_index("px") == cfg.nproc_x - 1
        arr = arr.at[:, -2].set(jnp.where(on_east_wall, 0.0, arr[:, -2]))
    if kind == "v":
        on_north_wall = jax.lax.axis_index("py") == cfg.nproc_y - 1
        arr = arr.at[-2, :].set(jnp.where(on_north_wall, 0.0, arr[-2, :]))

    return arr, token


# ---------------------------------------------------------------------------
# model physics (runs inside the parallel region)
# ---------------------------------------------------------------------------


def local_coriolis(cfg: Config):
    """Coriolis parameter on this rank's rows, from the mesh coordinate
    (traced): y = (py * (ny_local-2) + j - 1) * dy."""
    py = jax.lax.axis_index("py")
    j = jnp.arange(cfg.ny_local)
    y = (py * (cfg.ny_local - 2) + j - 1.0) * cfg.dy
    return (cfg.coriolis_f + y * cfg.coriolis_beta)[:, None]


def model_step(state: State, cfg: Config, comm: mpx.Comm, first_step: bool) -> State:
    """One shallow-water step (Sadourny energy-conserving scheme +
    Adams-Bashforth 2), rank-local view.  Physics parity with ref
    examples/shallow_water.py:277-412."""
    token = mpx.create_token()
    h, u, v, dh, du, dv = state
    inner = np.s_[1:-1, 1:-1]
    dx, dy, g = cfg.dx, cfg.dy, cfg.gravity

    # cell-centered height with refreshed halo
    hc = jnp.pad(h[inner], 1, "edge")
    hc, token = enforce_boundaries(hc, "h", cfg, comm, token)

    # volume fluxes through east and north cell faces
    fe = jnp.zeros_like(u).at[inner].set(
        0.5 * (hc[1:-1, 1:-1] + hc[1:-1, 2:]) * u[inner]
    )
    fn = jnp.zeros_like(v).at[inner].set(
        0.5 * (hc[1:-1, 1:-1] + hc[2:, 1:-1]) * v[inner]
    )
    fe, token = enforce_boundaries(fe, "u", cfg, comm, token)
    fn, token = enforce_boundaries(fn, "v", cfg, comm, token)

    # continuity: dh/dt = -div(flux)
    dh_new = dh.at[inner].set(
        -(fe[1:-1, 1:-1] - fe[1:-1, :-2]) / dx - (fn[1:-1, 1:-1] - fn[:-2, 1:-1]) / dy
    )

    # potential vorticity q = (f + rel. vorticity) / interpolated depth
    coriolis = local_coriolis(cfg)
    rel_vort = (v[1:-1, 2:] - v[1:-1, 1:-1]) / dx - (u[2:, 1:-1] - u[1:-1, 1:-1]) / dy
    depth_q = 0.25 * (hc[1:-1, 1:-1] + hc[1:-1, 2:] + hc[2:, 1:-1] + hc[2:, 2:])
    q = jnp.zeros_like(h).at[inner].set(
        (coriolis[inner[0]] + rel_vort) / depth_q
    )
    q, token = enforce_boundaries(q, "h", cfg, comm, token)

    # momentum tendencies: pressure gradient + vorticity flux
    du_new = du.at[inner].set(
        -g * (h[1:-1, 2:] - h[1:-1, 1:-1]) / dx
        + 0.5
        * (
            q[1:-1, 1:-1] * 0.5 * (fn[1:-1, 1:-1] + fn[1:-1, 2:])
            + q[:-2, 1:-1] * 0.5 * (fn[:-2, 1:-1] + fn[:-2, 2:])
        )
    )
    dv_new = dv.at[inner].set(
        -g * (h[2:, 1:-1] - h[1:-1, 1:-1]) / dy
        - 0.5
        * (
            q[1:-1, 1:-1] * 0.5 * (fe[1:-1, 1:-1] + fe[2:, 1:-1])
            + q[1:-1, :-2] * 0.5 * (fe[1:-1, :-2] + fe[2:, :-2])
        )
    )

    # kinetic-energy gradient (C-grid average)
    ke = jnp.zeros_like(h).at[inner].set(
        0.5
        * (
            0.5 * (u[1:-1, 1:-1] ** 2 + u[1:-1, :-2] ** 2)
            + 0.5 * (v[1:-1, 1:-1] ** 2 + v[:-2, 1:-1] ** 2)
        )
    )
    ke, token = enforce_boundaries(ke, "h", cfg, comm, token)
    du_new = du_new.at[inner].add(-(ke[1:-1, 2:] - ke[1:-1, 1:-1]) / dx)
    dv_new = dv_new.at[inner].add(-(ke[2:, 1:-1] - ke[1:-1, 1:-1]) / dy)

    # time integration: forward Euler on the first step, AB-2 after
    if first_step:
        h = h.at[inner].add(cfg.dt * dh_new[inner])
        u = u.at[inner].add(cfg.dt * du_new[inner])
        v = v.at[inner].add(cfg.dt * dv_new[inner])
    else:
        h = h.at[inner].add(cfg.dt * (cfg.ab_a * dh_new[inner] + cfg.ab_b * dh[inner]))
        u = u.at[inner].add(cfg.dt * (cfg.ab_a * du_new[inner] + cfg.ab_b * du[inner]))
        v = v.at[inner].add(cfg.dt * (cfg.ab_a * dv_new[inner] + cfg.ab_b * dv[inner]))

    h, token = enforce_boundaries(h, "h", cfg, comm, token)
    u, token = enforce_boundaries(u, "u", cfg, comm, token)
    v, token = enforce_boundaries(v, "v", cfg, comm, token)

    # lateral friction on u and v
    if cfg.lateral_viscosity > 0:
        visc = cfg.lateral_viscosity
        for name, field in (("u", u), ("v", v)):
            gx = jnp.zeros_like(field).at[inner].set(
                visc * (field[1:-1, 2:] - field[1:-1, 1:-1]) / dx
            )
            gy = jnp.zeros_like(field).at[inner].set(
                visc * (field[2:, 1:-1] - field[1:-1, 1:-1]) / dy
            )
            gx, token = enforce_boundaries(gx, "u", cfg, comm, token)
            gy, token = enforce_boundaries(gy, "v", cfg, comm, token)
            field = field.at[inner].add(
                cfg.dt
                * (
                    (gx[1:-1, 1:-1] - gx[1:-1, :-2]) / dx
                    + (gy[1:-1, 1:-1] - gy[:-2, 1:-1]) / dy
                )
            )
            if name == "u":
                u = field
            else:
                v = field

    return State(h, u, v, dh_new, du_new, dv_new)


def model_step_fast(state: State, cfg: Config, comm: mpx.Comm,
                    first_step: bool) -> State:
    """One shallow-water step, numerically equivalent to ``model_step`` but
    restructured for the TPU memory system (see tests/test_examples.py for
    the step-for-step equality check).

    Why ``model_step`` is slow on TPU: every derived field is built as
    ``zeros_like(x).at[inner].set(expr)`` (a misaligned interior
    dynamic-update-slice where an aligned full-field op would do) and is
    halo-exchanged (13 exchange rounds per step), splitting the step into
    ~13 tiny fusion regions.

    This version exploits an algebraic fact: with *coherent halos* on the
    inputs (each halo cell holds exactly its neighbor's current interior
    value), a derived field computed **full-field** with periodic rolls
    reproduces, operand for operand, the halo values the reference would
    have *received from its neighbor* — because the neighbor computes its
    edge from the very same values that our halo cells already hold.  So
    ``fe``/``fn``/``q``/``ke`` and the viscous fluxes need **no exchange at
    all**; only the state (``h``, ``u``, ``v``) is exchanged — 5 rounds
    instead of 13 — and ``hc`` becomes a fused ``where`` (wall-rank edge
    replication), not an exchange.  Wall semantics (``wrap=False``
    directions keep a zero halo; no-flux wall rows) become iota masks that
    fuse into the arithmetic for free.  Everything between exchanges is one
    large, aligned, fusion-friendly XLA region.

    To keep the coherent-halo invariant, ``u``/``v`` are re-exchanged after
    the viscous update (the reference instead lets seam halos lag the
    viscous substep by one step).  The two programs therefore differ at
    subdomain seams by one viscosity substep of halo freshness — the same
    order as the reference's own decomposition variance (its results on
    (1,1) vs (2,4) grids differ by exactly this class of artifact).  The
    fast path's *own* decomposition invariance is exact to rounding; see
    tests/test_examples.py.
    """
    token = mpx.create_token()
    h, u, v, dh, du, dv = state
    dx, dy, g = cfg.dx, cfg.dy, cfg.gravity
    ny, nx = cfg.ny_local, cfg.nx_local

    # stencil reads as aligned full-field rolls: rm1x(a)[j,i] == a[j,i+1] …
    rm1x = lambda a: jnp.roll(a, -1, 1)  # noqa: E731
    rp1x = lambda a: jnp.roll(a, 1, 1)  # noqa: E731
    rm1y = lambda a: jnp.roll(a, -1, 0)  # noqa: E731
    rp1y = lambda a: jnp.roll(a, 1, 0)  # noqa: E731

    iy = jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 0)
    on_south = jax.lax.axis_index("py") == 0
    on_north = jax.lax.axis_index("py") == cfg.nproc_y - 1
    # y-halo rows that enforce_boundaries would NOT fill (wrap=False edge
    # ranks keep the zeros of zeros_like): these must be 0 in every derived
    # field, exactly as in the reference
    kept_y_halo = (on_south & (iy == 0)) | (on_north & (iy == ny - 1))
    interior = (iy > 0) & (iy < ny - 1)
    ix = jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 1)
    interior &= (ix > 0) & (ix < nx - 1)
    u_wall = None  # kind-"u" no-flow wall column (ref enforce_boundaries)
    if not cfg.periodic_x:
        on_west = jax.lax.axis_index("px") == 0
        on_east = jax.lax.axis_index("px") == cfg.nproc_x - 1
        kept_y_halo |= (on_west & (ix == 0)) | (on_east & (ix == nx - 1))
        u_wall = on_east & (ix == nx - 2)

    def derived(expr, extra_zero=None):
        """Mask a full-field derived quantity to reference halo semantics."""
        zero = kept_y_halo if extra_zero is None else (kept_y_halo | extra_zero)
        return jnp.where(zero, 0.0, expr)

    # cell-centered height: with h's halos coherent (the end-of-step
    # exchanges maintain this; the initial state ships it), the reference's
    # pad-then-exchange of hc reduces to edge replication at wall ranks —
    # a fused where, no exchange, no update-slice
    hc = jnp.where(
        on_south & (iy == 0),
        rm1y(h),  # rm1y(h)[0] == h[1]: the "edge" pad row
        jnp.where(on_north & (iy == ny - 1), rp1y(h), h),
    )
    if not cfg.periodic_x:
        hc = jnp.where(
            on_west & (ix == 0),
            rm1x(hc),
            jnp.where(on_east & (ix == nx - 1), rp1x(hc), hc),
        )

    # ---- derived fields: full-field, no exchanges (see docstring) -------
    fe = derived(0.5 * (hc + rm1x(hc)) * u, u_wall)
    # fn additionally gets the no-flux wall row (kind "v": row -2 zeroed on
    # the north rank, ref enforce_boundaries)
    fn = derived(0.5 * (hc + rm1y(hc)) * v, on_north & (iy == ny - 2))

    coriolis = local_coriolis(cfg)  # (ny, 1), all rows
    rel_vort = (rm1x(v) - v) / dx - (rm1y(u) - u) / dy
    depth_q = 0.25 * (hc + rm1x(hc) + rm1y(hc) + rm1y(rm1x(hc)))
    q = derived((coriolis + rel_vort) / depth_q)

    # roll/elementwise-commutation rewrites, bit-identical to the canonical
    # stencils — MUST stay in lockstep with _phase1_window (the halo-path
    # equality tests pin exactness between the two)
    u_sq, v_sq = u * u, v * v
    ke = derived(
        0.5 * (0.5 * (u_sq + rp1x(u_sq)) + 0.5 * (v_sq + rp1y(v_sq)))
    )

    # ---- tendencies (halos zeroed: matches zeros-initialized dh/du/dv) --
    dh_new = jnp.where(
        interior,
        -(fe - rp1x(fe)) / dx - (fn - rp1y(fn)) / dy,
        0.0,
    )
    fn_e = 0.5 * (fn + rm1x(fn))
    fe_n = 0.5 * (fe + rm1y(fe))
    du_new = jnp.where(
        interior,
        -g * (rm1x(h) - h) / dx
        + 0.5 * (q * fn_e + rp1y(q) * rp1y(fn_e))
        - (rm1x(ke) - ke) / dx,
        0.0,
    )
    dv_new = jnp.where(
        interior,
        -g * (rm1y(h) - h) / dy
        - 0.5 * (q * fe_n + rp1x(q) * rp1x(fe_n))
        - (rm1y(ke) - ke) / dy,
        0.0,
    )

    # ---- time integration (tendency halos are 0, so full-field adds
    # preserve the state halos exactly) --------------------------------
    if first_step:
        h = h + cfg.dt * dh_new
        u = u + cfg.dt * du_new
        v = v + cfg.dt * dv_new
    else:
        h = h + cfg.dt * (cfg.ab_a * dh_new + cfg.ab_b * dh)
        u = u + cfg.dt * (cfg.ab_a * du_new + cfg.ab_b * du)
        v = v + cfg.dt * (cfg.ab_a * dv_new + cfg.ab_b * dv)

    h, token = enforce_boundaries(h, "h", cfg, comm, token)
    u, token = enforce_boundaries(u, "u", cfg, comm, token)
    v, token = enforce_boundaries(v, "v", cfg, comm, token)

    # ---- lateral friction: viscous fluxes with locally-computed ghosts.
    # The flux across a subdomain face is computable on both sides from the
    # (valid) field halos with identical operands, so no gx/gy exchange is
    # needed — another 4 exchange rounds saved vs the reference.
    if cfg.lateral_viscosity > 0:
        visc = cfg.lateral_viscosity
        for name in ("u", "v"):
            field = u if name == "u" else v
            gx = derived(visc * (rm1x(field) - field) / dx, u_wall)
            gy = derived(
                visc * (rm1y(field) - field) / dy,
                on_north & (iy == ny - 2),  # kind "v" wall row
            )
            field = field + jnp.where(
                interior,
                cfg.dt * ((gx - rp1x(gx)) / dx + (gy - rp1y(gy)) / dy),
                0.0,
            )
            if name == "u":
                u = field
            else:
                v = field

        # restore the coherent-halo invariant for the next step (the
        # docstring's one deliberate divergence from the reference, which
        # leaves seam halos one viscous substep stale).  Kind "h": pure
        # halo refresh — the no-flow wall rows were already applied once
        # above and must not be re-zeroed after the viscous update.
        u, token = enforce_boundaries(u, "h", cfg, comm, token)
        v, token = enforce_boundaries(v, "h", cfg, comm, token)

    return State(h, u, v, dh_new, du_new, dv_new)


# ---------------------------------------------------------------------------
# Pallas single-kernel step (single-rank hot path)
# ---------------------------------------------------------------------------

_PBLK = 128  # output rows per grid step (multiple of 8: f32 sublane tile)
# margin rows each side are 8 * nsteps (one sublane tile per fused step;
# the per-step recompute chain depth, with viscosity, is ~5 rows)


def _margin_rows(nsteps: int) -> int:
    """Margin / exchange depth for ``nsteps`` fused steps: 8 rows/cols of
    validity per step (chain depth ~5), rounded up to a divisor of
    ``_PBLK`` (the block-margin index maps need ``mrg | _PBLK``).  The
    single source of this invariant for both the whole-step chunk kernels
    and the wide-halo path."""
    if not 1 <= nsteps <= 2:  # deeper fusion exceeds VMEM/compiler
        raise ValueError(f"fused step windows support 1..2 steps, got {nsteps}")
    m = 8 * nsteps
    while _PBLK % m:
        m += 8
    return m


def _window_fields(ins, nfields: int):
    """Assemble ``nfields`` row windows from [prev-margin, main,
    next-margin] block-ref triples — shared by every blocked kernel
    body."""
    return tuple(
        jnp.concatenate(
            [ins[3 * k][:], ins[3 * k + 1][:], ins[3 * k + 2][:]], axis=0
        )
        for k in range(nfields)
    )


def _rolls(roll, nr: int, nx: int):
    """The four stencil shifts as positive-shift rolls (``roll`` is
    ``pltpu.roll`` inside kernels, ``jnp.roll`` on the direct path — the
    two agree for positive shifts)."""
    rm1x = lambda a: roll(a, nx - 1, 1)  # noqa: E731  a[j, i+1]
    rp1x = lambda a: roll(a, 1, 1)  # noqa: E731      a[j, i-1]
    rm1y = lambda a: roll(a, nr - 1, 0)  # noqa: E731  a[j+1, i]
    rp1y = lambda a: roll(a, 1, 0)  # noqa: E731       a[j-1, i]
    return rm1x, rp1x, rm1y, rp1y


def _window_masks(cfg: Config, iy, ix, giy, gix, wide=False):
    """Shared wall/update masks for the phase windows (single source of
    truth — must mirror ``model_step_fast``'s mask algebra, which the
    equality tests pin): ``(derived, u_wall, wall_v, interior)``.

    ``derived(expr, extra=None)`` zeroes the halo rows/cols a real exchange
    would leave untouched; ``u_wall``/``wall_v`` are the no-flow wall
    masks; ``interior`` is the update mask.

    ``wide`` selects the wide-halo frame (``_wide_run``):
    there every cell is computed exactly as its *owning* rank computes it,
    so the update mask tests DOMAIN-GLOBAL interiority (a seam cell is
    some rank's interior and is updated in place — the recomputed value is
    bit-identical to what an exchange would deliver), and the kept masks
    use inequalities so the beyond-wall garbage rows of the widened frame
    are zeroed in every derived field.  In the default frame the update
    mask tests LOCAL indices: the rank's own halo ring is excluded and
    later refreshed by a real exchange (or the periodic in-register fix).
    """
    nyl, nxl = cfg.ny_local, cfg.nx_local
    gy_n, gx_n = cfg.ny + 2, cfg.nx + 2

    u_wall = None  # kind-"u" no-flow wall column
    wall_v = giy == gy_n - 2  # kind-"v" no-flux row (extra mask)
    if wide:
        # kept uses inequalities so beyond-wall garbage rows of the widened
        # frame are zeroed too; for periodic x the widened columns beyond
        # the global extent are wrap images of far-side interior columns —
        # their owner updates them, so no x constraint enters the masks
        kept = (giy <= 0) | (giy >= gy_n - 1)
        interior = (giy >= 1) & (giy <= gy_n - 2)
        if not cfg.periodic_x:
            kept |= (gix <= 0) | (gix >= gx_n - 1)
            interior &= (gix >= 1) & (gix <= gx_n - 2)
            u_wall = gix == gx_n - 2
    else:
        kept = (giy == 0) | (giy == gy_n - 1)
        if not cfg.periodic_x:
            kept |= (gix == 0) | (gix == gx_n - 1)
            u_wall = gix == gx_n - 2
        interior = (iy > 0) & (iy < nyl - 1) & (ix > 0) & (ix < nxl - 1)

    def derived(expr, extra=None):
        mask = kept if extra is None else (kept | extra)
        return jnp.where(mask, 0.0, expr)

    return derived, u_wall, wall_v, interior


def _phase1_window(cfg: Config, first_step: bool, iy, ix, giy, gix, fields,
                   roll, wide=False):
    """Integration phase of one model step (hc, fluxes, q, ke, tendencies,
    AB-2/Euler update) on a ``(nr, nx)`` row window, no exchanges.

    ``iy``/``ix`` are the cells' *rank-local* row/column indices (window
    margins included, so ``iy`` may exceed the local bounds); ``giy``/
    ``gix`` are the *domain-global* indices (``local + rank offset``) that
    all wall masks test against — on a single-rank decomposition the two
    coincide.  Requires the coherent-halo invariant on the input state
    (each halo cell holds its neighbor's current interior value); returns
    ``(h1, u1, v1, dh_new, du_new, dv_new)`` whose *local-interior* cells
    are valid — halo cells keep their (now stale) input values, exactly
    like ``model_step_fast`` before its mid-step exchange.  Margin rows
    within the recompute chain depth (~5) of the window edge are garbage
    that the caller's stored-slice keeps out.
    """
    h, u, v, dh, du, dv = fields
    nr, nx = h.shape
    gy_n, gx_n = cfg.ny + 2, cfg.nx + 2  # domain-global array heights
    dx, dy, g, dt = cfg.dx, cfg.dy, cfg.gravity, cfg.dt
    rm1x, rp1x, rm1y, rp1y = _rolls(roll, nr, nx)

    # wall masks test GLOBAL indices (on non-wall ranks a halo row/col maps
    # to a neighbor's interior index, so they are false there — its value
    # is then computed via rolls, valid by halo coherence); the update mask
    # tests LOCAL indices (every rank's own halo ring is excluded)
    derived, u_wall, wall_v, interior = _window_masks(
        cfg, iy, ix, giy, gix, wide
    )

    # hc: edge-replicated pad rows/cols at the physical walls; elsewhere
    # the (coherent) halo value is already the neighbor's interior
    hc = jnp.where(giy == 0, rm1y(h), jnp.where(giy == gy_n - 1, rp1y(h), h))
    if not cfg.periodic_x:
        hc = jnp.where(
            gix == 0, rm1x(hc), jnp.where(gix == gx_n - 1, rp1x(hc), hc)
        )

    fe = derived(0.5 * (hc + rm1x(hc)) * u, u_wall)
    fn = derived(0.5 * (hc + rm1y(hc)) * v, wall_v)

    cor = cfg.coriolis_f + (giy - 1).astype(jnp.float32) * cfg.dy * cfg.coriolis_beta
    rel_vort = (rm1x(v) - v) / dx - (rm1y(u) - u) / dy
    depth_q = 0.25 * (hc + rm1x(hc) + rm1y(hc) + rm1y(rm1x(hc)))
    q = derived((cor + rel_vort) / depth_q)
    # rolls are permutations, so they commute BIT-EXACTLY with elementwise
    # math: rp1x(u)**2 == rp1x(u*u), rp1y(a) + rp1y(b) == rp1y(a + b).
    # Rewriting the vorticity-flux and KE stencils through that identity
    # removes three rolls and two squarings per step at identical results
    # (roll is the most expensive VPU op here — see docs/shallow_water.md).
    u_sq, v_sq = u * u, v * v
    ke = derived(
        0.5 * (0.5 * (u_sq + rp1x(u_sq)) + 0.5 * (v_sq + rp1y(v_sq)))
    )

    dh_new = jnp.where(
        interior, -(fe - rp1x(fe)) / dx - (fn - rp1y(fn)) / dy, 0.0
    )
    fn_e = 0.5 * (fn + rm1x(fn))  # east-face vorticity-flux average
    fe_n = 0.5 * (fe + rm1y(fe))  # north-face average
    du_new = jnp.where(
        interior,
        -g * (rm1x(h) - h) / dx
        + 0.5 * (q * fn_e + rp1y(q) * rp1y(fn_e))
        - (rm1x(ke) - ke) / dx,
        0.0,
    )
    dv_new = jnp.where(
        interior,
        -g * (rm1y(h) - h) / dy
        - 0.5 * (q * fe_n + rp1x(q) * rp1x(fe_n))
        - (rm1y(ke) - ke) / dy,
        0.0,
    )

    if first_step:
        h1 = h + dt * dh_new
        u1 = u + dt * du_new
        v1 = v + dt * dv_new
    else:
        h1 = h + dt * (cfg.ab_a * dh_new + cfg.ab_b * dh)
        u1 = u + dt * (cfg.ab_a * du_new + cfg.ab_b * du)
        v1 = v + dt * (cfg.ab_a * dv_new + cfg.ab_b * dv)

    return h1, u1, v1, dh_new, du_new, dv_new


def _phase2_window(cfg: Config, iy, ix, giy, gix, u, v, roll, wide=False):
    """Viscosity phase of one model step on a window: lateral friction on
    ``u`` and ``v``, which must enter with *coherent halos* (the mid-step
    exchange / periodic fix).  Index conventions as ``_phase1_window``;
    recompute chain depth is 2 rows."""
    nr, nx = u.shape
    dx, dy, dt = cfg.dx, cfg.dy, cfg.dt
    rm1x, rp1x, rm1y, rp1y = _rolls(roll, nr, nx)
    derived, u_wall, wall_v, interior = _window_masks(
        cfg, iy, ix, giy, gix, wide
    )

    visc = cfg.lateral_viscosity
    out = []
    for f in (u, v):
        gx = derived(visc * (rm1x(f) - f) / dx, u_wall)
        gy = derived(visc * (rm1y(f) - f) / dy, wall_v)
        out.append(
            f
            + jnp.where(
                interior,
                dt * ((gx - rp1x(gx)) / dx + (gy - rp1y(gy)) / dy),
                0.0,
            )
        )
    return out[0], out[1]


def _step_window(cfg: Config, first_step: bool, n_rows: int, iy, ix, fields):
    """One WHOLE model step on a ``(nr, nx)`` row window, entirely in
    registers/VMEM: ``_phase1_window`` + in-register halo refreshes +
    ``_phase2_window``.

    Valid only for the single-rank, periodic-x decomposition (so global
    and local indices coincide — ``giy = iy``): x stencil reads use true
    periodic lane rolls, and every halo refresh (mid-step and end-of-step)
    becomes an in-register periodic column fix.  Multi-rank meshes use the
    split-phase path (``model_step_pallas_halo``), where the refreshes are
    real ``sendrecv`` exchanges between the phase kernels.
    """
    from jax.experimental.pallas import tpu as pltpu

    nx = fields[0].shape[1]

    def pc_fix(a):
        # periodic column refresh: col 0 <- col -2, col -1 <- col 1 (what
        # the single-rank wrap exchange delivers), fully in-register
        return jnp.where(
            ix == 0,
            pltpu.roll(a, 2, 1),
            jnp.where(ix == nx - 1, pltpu.roll(a, nx - 2, 1), a),
        )

    h1, u1, v1, dh_new, du_new, dv_new = _phase1_window(
        cfg, first_step, iy, ix, iy, ix, fields, pltpu.roll
    )

    # mid-step halo refresh (the jnp path's enforce_boundaries between
    # integration and viscosity): periodic column fix + kind-"v" wall row
    u1 = pc_fix(u1)
    v1 = jnp.where(iy == n_rows - 2, 0.0, pc_fix(v1))

    if cfg.lateral_viscosity > 0:
        u1, v1 = _phase2_window(cfg, iy, ix, iy, ix, u1, v1, pltpu.roll)

    # end-of-step halo refresh, in-register: on the single-rank periodic-x
    # decomposition the three enforce_boundaries(·, "h") exchanges reduce
    # exactly to the periodic column fix (col 0 <- col nx-2, col nx-1 <-
    # col 1, from the pre-fix array — bit-identical to the sendrecv pair),
    # so storing fixed ghosts saves three full-field HBM round-trips/step
    h1 = pc_fix(h1)
    u1 = pc_fix(u1)
    v1 = pc_fix(v1)

    return h1, u1, v1, dh_new, du_new, dv_new


def _sw_steps_kernel(cfg: Config, first_step: bool, n_rows: int, mrg: int,
                     nsteps: int, refs):
    """Kernel body: ``nsteps`` whole model steps on a
    ``(_PBLK + 2 * mrg, nx_local)`` row window, margins recomputed so no
    intermediate field — nor, for ``nsteps > 1``, the intermediate *state* —
    ever round-trips through HBM.  Each step consumes ~5 margin rows of
    validity (recompute chain depth), so ``mrg`` must be at least
    ``8 * nsteps`` (one sublane tile per step is ample).

    ``refs`` is 18 input refs (6 fields x [prev-margin, main, next-margin]
    blocks, field order h,u,v,dh,du,dv) followed by the 6 output refs; the
    unpacking below is positional by that structure.
    """
    import jax.experimental.pallas as pl

    ins, outs = refs[:18], refs[18:]
    nx = cfg.nx_local
    nr = _PBLK + 2 * mrg
    fields = _window_fields(ins, 6)

    pid = pl.program_id(0)
    iy = (
        jax.lax.broadcasted_iota(jnp.int32, (nr, nx), 0)
        + pid * _PBLK
        - mrg
    )
    ix = jax.lax.broadcasted_iota(jnp.int32, (nr, nx), 1)

    first = first_step
    for _ in range(nsteps):
        fields = _step_window(cfg, first, n_rows, iy, ix, fields)
        first = False

    sl = slice(mrg, mrg + _PBLK)
    for o, f in zip(outs, fields):
        o[:] = f[sl]


def _resolve_interpret(comm: mpx.Comm) -> bool:
    """Whether Pallas must run in interpret mode: decided by the devices of
    the mesh the step runs on, not the process default backend (the two
    differ when a driver places the mesh on a non-default platform's
    devices).  On TPU devices the kernels are always Mosaic-compiled."""
    return comm.mesh.devices.flat[0].platform != "tpu"


def _blocked_specs(ny: int, nx: int, mrg: int):
    """``(grid, main_spec, prev_spec, next_spec)`` for ``_PBLK``-row output
    blocks with ``mrg``-row recompute margins, clipped (duplicated) at the
    array edges — the margin-row mislabeling this causes only ever reaches
    rows that the wall masks zero or that no stored row reads (the same
    one-sided-read discipline that makes ``model_step_fast`` exchange-free
    for derived fields)."""
    import jax.experimental.pallas as pl

    grid = ((ny + _PBLK - 1) // _PBLK,)
    n_hblocks = (ny + mrg - 1) // mrg  # mrg-row halo block count
    r = _PBLK // mrg

    main = pl.BlockSpec((_PBLK, nx), lambda i: (i, 0))
    prev = pl.BlockSpec(
        (mrg, nx), lambda i: (jnp.clip(i * r - 1, 0, n_hblocks - 1), 0)
    )
    nxt = pl.BlockSpec(
        (mrg, nx), lambda i: (jnp.clip(i * r + r, 0, n_hblocks - 1), 0)
    )
    return grid, main, prev, nxt


def _kernel_name(kind: str, nsteps: int, first_step: bool) -> str:
    """The name a Pallas kernel carries into HLO and the profiler's trace
    (``pallas_call(name=)``): the kind, the steps one call advances, and
    ``_euler`` for the first-step variant, as ``sw_steps_x2``."""
    return f"{kind}_x{nsteps}" + ("_euler" if first_step else "")


def _tpu_compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    # at benchmark width (nx_local=3602) the 24 window blocks plus
    # kernel intermediates need most of the 100 MB granted here
    # (measured: _PBLK=256 needs 165 MB and overflows the chip's
    # 128 MB VMEM — raising _PBLK further requires shrinking the
    # working set first); Mosaic's default scoped limit is 16 MB
    return pltpu.CompilerParams(
        vmem_limit_bytes=100 * 1024 * 1024,
        dimension_semantics=("parallel",),
    )


def model_step_pallas(state: State, cfg: Config, comm: mpx.Comm,
                      first_step: bool, interpret=None,
                      nsteps: int = 1) -> State:
    """``nsteps`` applications of ``model_step_fast`` as ONE fused Pallas
    kernel — including every halo refresh, which on this path reduces to
    the in-register periodic column fix (see ``_step_window``), so there
    are no exchanges at all.

    Every intermediate (hc, fe, fn, q, ke, viscous fluxes) — and, for
    ``nsteps=2``, the mid-pair state itself — lives in VMEM only: per
    kernel call the state is read and written once (plus an
    ``8 * nsteps``-row margin per ``_PBLK``-row block), instead of
    materializing ~10 intermediate full fields through HBM per step.
    Single-rank periodic-x decompositions only (the benchmark
    configuration); multi-rank meshes use ``model_step_pallas_halo``, which
    keeps the same kernels but splices real exchanges between the phases.
    Equality with
    the jnp step is pinned by
    tests/test_examples.py::test_pallas_step_matches_fast_step and
    ::test_pallas_chunk_step_matches_fast_steps (interpret mode on CPU,
    compiled on TPU).

    ``interpret=None`` resolves at trace time to "the comm's mesh is not
    on TPU devices", so the same call sites run the Mosaic-compiled kernel
    on the chip and the interpret path everywhere else (CPU CI, the
    driver's compile check).
    """
    if not (cfg.nproc == 1 and cfg.periodic_x):
        raise ValueError(
            "model_step_pallas: single-rank periodic-x only; use "
            "model_step_fast"
        )
    # one sublane tile of validity per fused step, rounded up to a divisor
    # of _PBLK — the prev/next margin index maps address mrg-row blocks as
    # i * (_PBLK // mrg), which only lands on block starts when mrg
    # divides _PBLK; more than two steps exceed the chip's VMEM/compiler
    # limits at benchmark width (checked in _margin_rows)
    mrg = _margin_rows(nsteps)
    import jax.experimental.pallas as pl

    if interpret is None:
        interpret = _resolve_interpret(comm)

    ny, nx = cfg.ny_local, cfg.nx_local
    fields = state
    # inside shard_map with VMA checking the outputs must be typed as
    # varying over the mesh axes, like the (sharded) inputs
    vma = frozenset(getattr(jax.typeof(state.h), "vma", frozenset()))
    if interpret and vma:
        # interpret mode inlines the kernel jaxpr under shard_map's
        # varying-manual-axes checking, where kernel-created iotas and
        # literals (unvarying) cannot mix with varying operands.  The
        # kernel only ever runs on a 1x1 mesh (nproc == 1), so the axes
        # are size-1 and a psum is an exact identity that makes every
        # operand axis-invariant; the outputs are re-varied below.
        axes = tuple(vma)
        fields = State(*(jax.lax.psum(f, axes) for f in state))
        out_vma = frozenset()
    else:
        out_vma = vma
    h, u, v, dh, du, dv = fields

    grid, main_spec, prev_spec, next_spec = _blocked_specs(ny, nx, mrg)

    in_specs = []
    operands = []
    for f in (h, u, v, dh, du, dv):
        in_specs += [prev_spec, main_spec, next_spec]
        operands += [f, f, f]

    out_shape = [
        jax.ShapeDtypeStruct((ny, nx), jnp.float32, vma=out_vma)
    ] * 6
    outs = pl.pallas_call(
        lambda *refs: _sw_steps_kernel(cfg, first_step, ny, mrg, nsteps, refs),
        name=_kernel_name("sw_steps", nsteps, first_step),
        grid=grid,
        in_specs=in_specs,
        out_specs=[main_spec for _ in range(6)],
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=None if interpret else _tpu_compiler_params(),
    )(*operands)
    if interpret and vma:
        outs = [jax.lax.pcast(o, axes, to="varying") for o in outs]
    h1, u1, v1, dh_new, du_new, dv_new = outs

    # end-of-step exchanges: none — on this (single-rank, periodic-x) path
    # they reduce to the periodic column fix, which the kernel applies
    # in-register before storing, saving three full-field HBM round-trips
    return State(h1, u1, v1, dh_new, du_new, dv_new)


def model_step2_pallas(state: State, cfg: Config, comm: mpx.Comm,
                       first_step: bool, interpret=None) -> State:
    """TWO model steps in one Pallas kernel call (``model_step_pallas``
    with ``nsteps=2``): halves the per-step HBM traffic and the grid
    dispatch count.  The chunk kernel of ``"pallas2"``: what it reaches on
    the chip is in PERF.md (``sw_steps_x2``)."""
    return model_step_pallas(state, cfg, comm, first_step,
                             interpret=interpret, nsteps=2)


# ---------------------------------------------------------------------------
# Pallas split-phase step (any mesh: kernel compute + real halo exchanges)
# ---------------------------------------------------------------------------


def _rank_offsets(cfg: Config):
    """This rank's domain-global (row, col) offset as a ``(2,)`` int32
    vector — the SMEM scalar operand that lets ONE compiled kernel serve
    every rank position (all wall masks test ``local index + offset``)."""
    row = jax.lax.axis_index("py") * (cfg.ny_local - 2)
    col = jax.lax.axis_index("px") * (cfg.nx_local - 2)
    return jnp.stack([row.astype(jnp.int32), col.astype(jnp.int32)])


def _sw_phase_kernel(cfg: Config, mrg: int, nfields: int, window, refs):
    """Kernel body shared by the two phase kernels: assemble ``nfields``
    row windows from [prev-margin, main, next-margin] block triples, label
    them with local + global indices (rank offsets from the leading SMEM
    operand), apply ``window``, store the main rows."""
    import jax.experimental.pallas as pl

    meta = refs[0]
    ins, outs = refs[1:1 + 3 * nfields], refs[1 + 3 * nfields:]
    nx = cfg.nx_local
    nr = _PBLK + 2 * mrg
    fields = _window_fields(ins, nfields)

    pid = pl.program_id(0)
    iy = jax.lax.broadcasted_iota(jnp.int32, (nr, nx), 0) + pid * _PBLK - mrg
    ix = jax.lax.broadcasted_iota(jnp.int32, (nr, nx), 1)
    giy = iy + meta[0]
    gix = ix + meta[1]

    out_fields = window(iy, ix, giy, gix, fields)
    sl = slice(mrg, mrg + _PBLK)
    for o, f in zip(outs, out_fields):
        o[:] = f[sl]


def _phase_pallas_call(cfg: Config, name: str, window, meta, fields,
                       n_out: int, out_vma):
    """Run ``window`` (a ``_phase*_window`` closure) as a compiled blocked
    Pallas kernel called ``name`` over the rank-local arrays in
    ``fields``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mrg = 8  # one sublane tile covers both phases' recompute chain depths
    ny, nx = cfg.ny_local, cfg.nx_local
    grid, main_spec, prev_spec, next_spec = _blocked_specs(ny, nx, mrg)

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    operands = [meta]
    for f in fields:
        in_specs += [prev_spec, main_spec, next_spec]
        operands += [f, f, f]

    out_shape = [
        jax.ShapeDtypeStruct((ny, nx), jnp.float32, vma=out_vma)
    ] * n_out
    return pl.pallas_call(
        lambda *refs: _sw_phase_kernel(cfg, mrg, len(fields), window, refs),
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=[main_spec for _ in range(n_out)],
        out_shape=out_shape,
        compiler_params=_tpu_compiler_params(),
    )(*operands)


def model_step_pallas_halo(state: State, cfg: Config, comm: mpx.Comm,
                           first_step: bool, interpret=None) -> State:
    """One model step on ANY mesh decomposition: fused Pallas compute with
    real ``sendrecv`` halo exchanges spliced between the phases.

    Where the whole-step kernel (``model_step_pallas``) folds every halo
    refresh into an in-register periodic column fix — possible only when
    one rank owns the whole domain — this path keeps ``model_step_fast``'s
    exchange structure (integrate → exchange h,u,v → viscosity → exchange
    u,v; see its docstring for why the derived fields need no exchange at
    all) and replaces the two *compute* regions with blocked Pallas
    kernels: ``_phase1_window`` (hc, fluxes, q, ke, tendencies, AB update
    — every intermediate stays in VMEM) and ``_phase2_window`` (viscous
    fluxes).  Per step the state round-trips HBM twice (once per phase)
    instead of once (whole-step kernel) but far under the jnp path's ~10
    intermediate full fields.  Rank position enters the compiled kernel as
    an SMEM scalar pair (``_rank_offsets``), so one kernel serves all
    ranks of the SPMD program.

    On non-TPU backends (``interpret`` resolves true) the same window
    functions are evaluated directly on the full local array with
    ``jnp.roll`` — identical arithmetic, no Pallas machinery — because
    Mosaic cannot compile there and Pallas interpret mode cannot inline
    kernel jaxprs under shard_map's varying-axes checking on a real
    multi-rank mesh (the single-rank psum identity used by
    ``model_step_pallas`` has no multi-rank analog).  Equality with
    ``model_step_fast`` on a (2, 4) mesh is pinned in
    tests/test_examples.py; the compiled kernels are exercised on-chip by
    the (1, 1)-mesh TPU path, which shares every line of kernel code.
    """
    if interpret is None:
        interpret = _resolve_interpret(comm)

    token = mpx.create_token()
    meta = _rank_offsets(cfg)
    nyl, nxl = cfg.ny_local, cfg.nx_local
    vma = frozenset(getattr(jax.typeof(state.h), "vma", frozenset()))

    if interpret:
        iy = jax.lax.broadcasted_iota(jnp.int32, (nyl, nxl), 0)
        ix = jax.lax.broadcasted_iota(jnp.int32, (nyl, nxl), 1)
        giy, gix = iy + meta[0], ix + meta[1]
        outs = _phase1_window(
            cfg, first_step, iy, ix, giy, gix, tuple(state), jnp.roll
        )
    else:
        outs = _phase_pallas_call(
            cfg, _kernel_name("sw_phase1", 1, first_step),
            lambda iy, ix, giy, gix, fs: _phase1_window(
                cfg, first_step, iy, ix, giy, gix, fs, _pltpu_roll()
            ),
            meta, tuple(state), 6, vma,
        )
    h1, u1, v1, dh_new, du_new, dv_new = outs

    h1, token = enforce_boundaries(h1, "h", cfg, comm, token)
    u1, token = enforce_boundaries(u1, "u", cfg, comm, token)
    v1, token = enforce_boundaries(v1, "v", cfg, comm, token)

    if cfg.lateral_viscosity > 0:
        if interpret:
            u1, v1 = _phase2_window(cfg, iy, ix, giy, gix, u1, v1, jnp.roll)
        else:
            u1, v1 = _phase_pallas_call(
                cfg, "sw_phase2",
                lambda iy, ix, giy, gix, fs: _phase2_window(
                    cfg, iy, ix, giy, gix, fs[0], fs[1], _pltpu_roll()
                ),
                meta, (u1, v1), 2, vma,
            )
        # restore the coherent-halo invariant for the next step (pure halo
        # refresh, kind "h" — see model_step_fast)
        u1, token = enforce_boundaries(u1, "h", cfg, comm, token)
        v1, token = enforce_boundaries(v1, "h", cfg, comm, token)

    return State(h1, u1, v1, dh_new, du_new, dv_new)


# ---------------------------------------------------------------------------
# Pallas wide-halo step (any mesh: communication-avoiding fused kernel)
# ---------------------------------------------------------------------------


def _strip_exch(payload, route, c, token):
    """Exchange one batched halo strip along a direction: a single
    ``sendrecv``, with a zeros recv template (``MPI_PROC_NULL``: edge
    ranks of non-wrapping directions keep zeros).  Size-1 axes resolve
    without any collective — identity for a wrapping route, zeros for a
    non-wrapping one.  Every strip exchange gets the CALLER's token, not
    a chain: the exchanges of one widening/refresh are mutually
    independent (the x -> y phase ordering is a data dependency already),
    and chaining would serialize what XLA can overlap."""
    if c.Get_size() == 1:
        return payload if route.wrap else jnp.zeros_like(payload)
    out, _ = mpx.sendrecv(payload, jnp.zeros_like(payload), dest=route,
                          comm=c, token=token)
    return out


def _wide_exchange(fields, cfg: Config, comm: mpx.Comm, m: int, token):
    """Build the widened frame for ``_wide_run``: every side gains
    ``m - 1`` rows/cols of neighbor data beyond the existing 1-cell halo,
    so ``nsteps`` whole model steps can be recomputed locally with no
    further exchange (a communication-avoiding halo exchange).

    Exchanges ``m``-deep strips of all six fields, batched as ONE
    ``sendrecv`` per direction — 4 messages per multi-step kernel call,
    where the split-phase path sends 4 messages per ``enforce_boundaries``
    round and needs 5 rounds per step.  Corner (diagonal-neighbor) data
    arrives via the standard two-phase trick: x strips first, then y
    strips *of the x-widened arrays*.

    Assembly differs by field class, preserving each class's invariant:

    - state (``h``/``u``/``v``): the local array is kept whole — its halo
      ring already holds the correct value everywhere (coherent at seams;
      the *initial-condition* value at physical walls, which an exchanged
      strip could not supply) — and the strips contribute only the
      ``m - 1`` extra rows/cols beyond it;
    - tendencies (``dh``/``du``/``dv``): their local halo ring is zero by
      invariant, but in the widened frame the seam position must hold the
      *owning* rank's value (the AB-2 update reads it there), so the full
      ``m``-deep strip replaces the halo position; at walls the zeros
      template reproduces the invariant exactly.

    Edge ranks of non-wrapping directions get a zeros template
    (``MPI_PROC_NULL`` semantics); those cells are beyond-wall garbage
    that the wide masks keep out of every valid cell.

    The frame is then **aligned**: beyond the east margin come
    ``-nx_w % 128`` *dead* columns and beyond the north margin
    ``-ny_w % 8`` dead rows (80 columns and no row at 3600 x 28800:
    3712 columns, 29 lane tiles), so that a frame's shape is a whole
    number of ``(8, 128)`` tiles.  A ``T(8,128)`` array of 3632 columns
    occupies 3712 in HBM anyway; as part of the *shape* they make the
    layout XLA:TPU gives a frame at a program's boundary the row-major one
    the kernel reads and writes, so a host loop that carries the frame
    from call to call (``run_multisteps``) copies none at either end
    (twelve whole-frame copies a call otherwise, 12.7 % of the walled
    3600 x 28800 run: PERF.md section 6, PR 36).  Two things hold of the
    dead cells, which no refresh ever writes and every kernel call
    recomputes from themselves:

    - *they reach nothing.*  What a call makes of them moves ``m - 1``
      cells at the most (the depth ``_margin_rows`` is sized for): through
      the east (north) margin, which is ``m - 1`` deep and refreshed before
      the next call, and — a ``roll`` wraps — onto the west (south) margin,
      ``m - 1`` deep and refreshed as well.  It is the argument that keeps
      the frame's own edge out of the crop region, one band further out;
      all of them lie on the high side, so every index below them is what
      it was (tests/test_wide_dead_cells.py overwrites them with ``nan``
      and finds the same bits);
    - *they are filled with data the stencil is finite on*: each repeats
      the last margin column (row) beside it.  At a wall the masks keep
      them as built, but on a periodic or an interior rank nothing in the
      masks knows them and they are advanced like any cell; on a zero fill
      (a depth of 0) the unselected branch of a ``where`` is not finite
      and ``jax.grad`` through a multistep returns ``nan``.  Masking them
      in the kernel would cost a select a field a step of the vector work
      that bounds it.
    """
    nyl, nxl = cfg.ny_local, cfg.nx_local
    commx, commy = comm.sub("px"), comm.sub("py")
    wrap_x = cfg.periodic_x
    # dead cells: what the margins lack to a whole (8, 128) tile
    dead_y, dead_x = -(nyl + 2 * (m - 1)) % 8, -(nxl + 2 * (m - 1)) % 128

    # ---- x phase: (6, nyl, m) strips --------------------------------
    lo = jnp.stack([f[:, 1:m + 1] for f in fields])
    hi = jnp.stack([f[:, nxl - 1 - m:nxl - 1] for f in fields])
    # high-side strips travel east (shift +1): each rank receives its WEST
    # neighbor's easternmost interior columns, and vice versa
    from_west = _strip_exch(hi, shift(+1, wrap=wrap_x), commx, token)
    from_east = _strip_exch(lo, shift(-1, wrap=wrap_x), commx, token)
    wx = []
    for k, f in enumerate(fields):
        w, e = from_west[k], from_east[k]
        # dead columns ride in the same concatenate: no second pass
        dead = [jnp.broadcast_to(e[:, -1:], (nyl, dead_x))] if dead_x else []
        if k < 3:  # state: local halo ring kept in place
            parts = [w[:, :m - 1], f, e[:, 1:]]
        else:  # tendency: the strip supplies the halo position
            parts = [w, f[:, 1:-1], e]
        wx.append(jnp.concatenate(parts + dead, axis=1))

    # ---- y phase: (6, m, nx_w) strips of the x-widened arrays -------
    lo = jnp.stack([f[1:m + 1] for f in wx])
    hi = jnp.stack([f[nyl - 1 - m:nyl - 1] for f in wx])
    from_south = _strip_exch(hi, shift(+1, wrap=False), commy, token)
    from_north = _strip_exch(lo, shift(-1, wrap=False), commy, token)
    out = []
    for k, f in enumerate(wx):
        s, n = from_south[k], from_north[k]
        dead = ([jnp.broadcast_to(n[-1:], (dead_y, f.shape[1]))]
                if dead_y else [])
        if k < 3:
            parts = [s[:m - 1], f, n[1:]]
        else:
            parts = [s, f[1:-1], n]
        out.append(jnp.concatenate(parts + dead, axis=0))
    return tuple(out), token


def _wide_step_window(cfg: Config, first_step: bool, giy, gix, fields, roll):
    """One WHOLE model step on the widened frame: ``_phase1_window`` with
    the wide masks, the post-integration wall conditions as global-index
    ``where``s (the only thing the mid-step exchange does *beyond* halo
    refresh — which the wide frame gets by recompute), then
    ``_phase2_window``.  No exchanges and no periodic fixes: x wrap data
    is real far-side data sitting in the widened margins.  Validity
    shrinks by the recompute chain depth (~5 cells) per step from the
    widened edges inward."""
    gy_n, gx_n = cfg.ny + 2, cfg.nx + 2
    h1, u1, v1, dh_n, du_n, dv_n = _phase1_window(
        cfg, first_step, giy, gix, giy, gix, fields, roll, wide=True
    )
    # post-integration wall conditions (enforce_boundaries kinds "u"/"v";
    # global-index masks, so a rank whose widened frame reaches a wall row
    # applies the same zeroing the wall rank applies)
    if not cfg.periodic_x:
        u1 = jnp.where(gix == gx_n - 2, 0.0, u1)
    v1 = jnp.where(giy == gy_n - 2, 0.0, v1)
    if cfg.lateral_viscosity > 0:
        u1, v1 = _phase2_window(
            cfg, giy, gix, giy, gix, u1, v1, roll, wide=True
        )
    # end-of-step kind-"h" refreshes are pure halo refresh: nothing to do
    return h1, u1, v1, dh_n, du_n, dv_n


def _sw_wide_kernel(cfg: Config, first_step: bool, mrg: int, nsteps: int,
                    refs):
    """Kernel body for the wide-halo step: like ``_sw_steps_kernel`` but on
    the widened frame — global indices come from the SMEM offset pair (one
    compiled kernel serves every rank) and the step windows use the wide
    masks, so there are no periodic fixes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    meta = refs[0]
    ins, outs = refs[1:19], refs[19:]
    nx_w = ins[1].shape[1]
    nr = _PBLK + 2 * mrg
    fields = _window_fields(ins, 6)

    pid = pl.program_id(0)
    wy = (
        jax.lax.broadcasted_iota(jnp.int32, (nr, nx_w), 0)
        + pid * _PBLK
        - mrg
    )
    wx = jax.lax.broadcasted_iota(jnp.int32, (nr, nx_w), 1)
    giy = wy + meta[0]
    gix = wx + meta[1]

    first = first_step
    for _ in range(nsteps):
        fields = _wide_step_window(cfg, first, giy, gix, fields, pltpu.roll)
        first = False

    sl = slice(mrg, mrg + _PBLK)
    for o, f in zip(outs, fields):
        o[:] = f[sl]


def _wide_kernel_call(wfields, cfg: Config, first_step: bool, nsteps: int,
                      m: int, interpret: bool):
    """``nsteps`` step windows on the widened frame: the compiled blocked
    Pallas kernel, or direct ``jnp.roll`` evaluation where Mosaic cannot
    compile (same rationale as ``model_step_pallas_halo``)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ny_w, nx_w = wfields[0].shape
    off = _rank_offsets(cfg) - (m - 1)  # widened-frame global offsets

    if interpret:
        iy = jax.lax.broadcasted_iota(jnp.int32, (ny_w, nx_w), 0)
        ix = jax.lax.broadcasted_iota(jnp.int32, (ny_w, nx_w), 1)
        giy, gix = iy + off[0], ix + off[1]
        outs = tuple(wfields)
        first = first_step
        for _ in range(nsteps):
            outs = _wide_step_window(cfg, first, giy, gix, outs, jnp.roll)
            first = False
        return outs

    grid, main_spec, prev_spec, next_spec = _blocked_specs(ny_w, nx_w, m)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    operands = [off]
    # Frames in and out stay in HBM.  Left to itself XLA:TPU parks a frame
    # that fits (27 MB at 3600 x 1800) in VMEM as the kernel's operand or
    # result, beside a kernel that fills 96 MB of the 128; on the aligned
    # frame the first such call never ended, at 1800 and at 900 rows, and
    # took the chip with it, while the same program with no frame of the
    # kernel's in VMEM ran (PERF.md section 6, PR 36; why is not known).
    for f in wfields:
        f = pltpu.with_memory_space_constraint(f, pltpu.HBM)
        in_specs += [prev_spec, main_spec, next_spec]
        operands += [f, f, f]
    out_shape = [pltpu.HBM((ny_w, nx_w), jnp.float32)] * 6
    return pl.pallas_call(
        lambda *refs: _sw_wide_kernel(cfg, first_step, m, nsteps, refs),
        name=_kernel_name("sw_wide", nsteps, first_step),
        grid=grid,
        in_specs=in_specs,
        out_specs=[main_spec for _ in range(6)],
        out_shape=out_shape,
        compiler_params=_tpu_compiler_params(),
    )(*operands)


def _wide_crop(outs, cfg: Config, m: int) -> State:
    """Crop a widened frame back to the local layout: the state's halo
    ring lands coherent (seam cells were updated exactly as their owner
    updates them; wall halo cells kept their values — update masked,
    tendency position zero), and the tendency ring is re-zeroed (in the
    widened frame it holds the neighbor's values at seams)."""
    nyl, nxl = cfg.ny_local, cfg.nx_local
    sl = (slice(m - 1, m - 1 + nyl), slice(m - 1, m - 1 + nxl))
    h1, u1, v1 = (o[sl] for o in outs[:3])
    liy = jax.lax.broadcasted_iota(jnp.int32, (nyl, nxl), 0)
    lix = jax.lax.broadcasted_iota(jnp.int32, (nyl, nxl), 1)
    ring = (liy == 0) | (liy == nyl - 1) | (lix == 0) | (lix == nxl - 1)
    dh_n, du_n, dv_n = (jnp.where(ring, 0.0, o[sl]) for o in outs[3:])
    return State(h1, u1, v1, dh_n, du_n, dv_n)


def _wide_refresh(wf, cfg: Config, comm: mpx.Comm, m: int, token):
    """Refresh the margin bands of a CARRIED widened frame between
    multi-step kernel calls.

    After a kernel call the local frame (crop region, halo ring included)
    is valid but the ``m - 1``-deep margins are recompute garbage.
    ``_wide_run`` therefore never crops between calls: it exchanges just
    the margin bands — four messages of ``(6, ·, m-1)`` — and writes them
    over the margins, which XLA does in place (a ``dynamic-update-slice``
    of the band alone, the interior untouched), so the full-array
    concat/crop copies of building and cropping the frame happen once per
    RUN instead of once per pair of steps.  The margins are the ``m - 1``
    cells either side of the local field: the east band is columns
    ``[e + nxl, e + nxl + e)`` and the north one rows ``[e + nyl, e + nyl
    + e)``, not "to the end" — beyond them lie the dead cells that align
    the frame (``_wide_exchange``), which no band writes.  The update is
    ``lax.dynamic_update_slice`` itself and not ``.at[].set``: a scatter's
    out-of-bounds rule comes out of XLA:TPU as a ``select`` between the
    band and the cells it replaces, and where the band is a slice of the
    same frame (one rank, periodic in x) and does not end the array, that
    ``select`` keeps the frame as it was alive beside the updated one —
    a whole-frame copy a field a refresh (tests/test_solver_loop_hlo.py).

    Two-phase for corners: x bands first (their corner rows are the
    sender's own garbage y-margins), then y bands at full widened width —
    sliced *after* the x update, so their corner columns carry the
    y-neighbor's freshly refreshed x margins (= diagonal-neighbor data);
    a band's dead cells are whatever the neighbor's were, and never read.
    In the carried frame the state/tendency assembly distinction of
    ``_wide_exchange`` disappears: the halo-position ring is valid
    post-kernel (computed as the owner computes it) and is not touched.
    """
    e = m - 1
    nyl, nxl = cfg.ny_local, cfg.nx_local
    commx, commy = comm.sub("px"), comm.sub("py")
    wrap_x = cfg.periodic_x

    # ---- x bands: (6, ny_w, e) ----
    # west margin <- west neighbor's easternmost interior (its widened
    # cols [nxl-2, nxl-2+e)); east margin <- east neighbor's westernmost
    # (its widened cols [e+2, 2e+2))
    from_west = _strip_exch(
        jnp.stack([f[:, nxl - 2:nxl - 2 + e] for f in wf]),
        shift(+1, wrap=wrap_x), commx, token,
    )
    from_east = _strip_exch(
        jnp.stack([f[:, e + 2:2 * e + 2] for f in wf]),
        shift(-1, wrap=wrap_x), commx, token,
    )
    put = jax.lax.dynamic_update_slice
    wf = tuple(
        put(put(f, from_west[k], (0, 0)), from_east[k], (0, e + nxl))
        for k, f in enumerate(wf)
    )

    # ---- y bands: (6, e, nx_w), full width (corners now valid) ----
    from_south = _strip_exch(
        jnp.stack([f[nyl - 2:nyl - 2 + e] for f in wf]),
        shift(+1, wrap=False), commy, token,
    )
    from_north = _strip_exch(
        jnp.stack([f[e + 2:2 * e + 2] for f in wf]),
        shift(-1, wrap=False), commy, token,
    )
    return tuple(
        put(put(f, from_south[k], (0, 0)), from_north[k], (e + nyl, 0))
        for k, f in enumerate(wf)
    )


def _wide_schedule(num_steps: int, chunk_size: int, euler_first: bool):
    """``(head, trips, rem)`` of ``_wide_run``'s kernel calls after the
    optional Euler call: ``head`` chunk call (0 or 1) straight off the
    frame as it came, ``trips`` rounds of one band refresh and one chunk
    call (``_wide_run``'s loop runs two rounds an iteration), ``rem``
    single-step calls.  Shared with ``leg_plan`` and ``run_plan``."""
    nchunks, rem = divmod(num_steps - int(euler_first), chunk_size)
    # the margins are the just-exchanged ones (or the ones the call before
    # left refreshed) until a kernel call invalidates them, so the first
    # call on them needs no refresh
    head = int(bool(nchunks) and not euler_first)
    return head, nchunks - head, rem


def _wide_run(state, num_steps: int, cfg: Config, comm: mpx.Comm,
              chunk_size: int, m: int, interpret: bool, euler_first: bool,
              carried_in: bool = False, handed_on: bool = False):
    """Advance ``num_steps`` model steps on ANY mesh on the CARRIED
    widened frame: build the frame once (``_wide_exchange``), run
    ``chunk_size``-step kernel calls with only a margin-band refresh
    between them (``_wide_refresh``), crop once at the end
    (``_wide_crop``).  ``euler_first`` makes the first advanced step the
    forward-Euler one (a 1-step kernel call).  This is the one wide-halo
    path: every driver (``make_stepper``, ``fused_runner``) and the
    standalone steps ``model_step_wide`` / ``model_step2_wide`` go through
    it.

    A host loop that carries the frame from call to call
    (``run_multisteps``) runs the same three pieces a piece a call:
    ``carried_in`` takes the six frames an earlier call handed on in the
    ``State``'s place and builds nothing; ``handed_on`` returns the six
    frames, their margins refreshed behind the last kernel call, and crops
    nothing.  So a frame handed on has valid margins as a just-built one
    has, and the next call's first kernel call runs straight off its
    parameters, with no copy between: the frame is aligned to ``(8, 128)``
    tiles (``_wide_exchange``), so the layout it has at a call's boundary
    is the kernel's own (PERF.md section 6, PR 36).  The refresh goes
    behind the last kernel call and not before the first for what XLA:TPU
    made of the other order at 3600 x 28800 while the boundary still
    copied the frames (PR 34): a refresh in place as the first instruction
    on a parameter's row-major copy cost a third set of six frames (7.71
    GB of temporaries for 5.14: the copy's buffers were never reused).
    The flags live here, not in a function of their own between the
    region and the kernel calls: one Python frame more put 0.8 s on the
    walled leg's warm ``setup_s`` (PERF.md section 6, PR 30 and PR 34).

    Where ``model_step_pallas_halo`` splices a real 1-cell exchange
    between the two phase kernels of every step (5 exchange rounds and two
    state HBM round-trips per step), here every halo value a step would
    have received is *recomputed locally* from the widened margins,
    bit-identical to the exchange (``_window_masks(wide=True)``), so the
    cropped result equals ``model_step_fast``, which tests/test_examples.py
    pins on (1,1), (2,2) and (2,4) meshes in both boundary modes.  This
    brings the single-rank pair kernel's economics (state reads HBM once
    per ``chunk_size`` steps, all intermediates in VMEM) to multi-rank
    meshes: the reference's scaling story (ref
    docs/shallow-water.rst:56-94) with the fused-kernel per-chip speed.
    Requires a local interior of at least ``m`` cells per dimension (strips
    must come from the immediate neighbor only); ``select_steps("auto")``
    falls back below that (the whole-step kernel on one periodic rank, the
    split-phase path elsewhere).

    The chunk loop advances two refresh-and-call rounds per iteration (an
    odd count's last round follows the loop), as ``_run_steps``' does and
    for its reason.  The kernel reads each frame through three overlapping
    block specs and so cannot write in place; with one call per iteration
    XLA's while loop copies all six new frames back into the carry's
    buffers (26.4 % of device time at 3600 x 28800, walled, on a v5e), with
    two the second call writes into the buffers the first has just read and
    no frame is copied, on one chip or on a mesh
    (tests/test_solver_loop_hlo.py)."""
    if cfg.ny_local - 2 < m or cfg.nx_local - 2 < m:
        # ValueError, not assert: user-facing eligibility that must
        # survive `python -O` (an undersized interior would silently
        # exchange out-of-range strips)
        raise ValueError(
            "wide-halo path: local interior must be >= the exchange depth "
            f"({m}) in both dimensions; use model_step_pallas_halo"
        )
    if num_steps <= 0:
        return state
    token = mpx.create_token()
    if carried_in:
        wf = tuple(state)
    else:
        wf, token = _wide_exchange(tuple(state), cfg, comm, m, token)
    head, trips, rem = _wide_schedule(num_steps, chunk_size, euler_first)
    if euler_first:
        wf = _wide_kernel_call(wf, cfg, True, 1, m, interpret)

    def body(_, wf):
        wf = _wide_refresh(wf, cfg, comm, m, token)
        return _wide_kernel_call(wf, cfg, False, chunk_size, m, interpret)

    if head:
        wf = _wide_kernel_call(wf, cfg, False, chunk_size, m, interpret)
    if trips:  # fori_loop(0, 0) would still trace the chunk kernel
        wf = jax.lax.fori_loop(0, trips, body, tuple(wf), unroll=2)
    # no kernel call yet: the margins are still the ones the frame came with
    fresh = not (euler_first or head or trips)
    for i in range(rem):
        if i or not fresh:
            wf = _wide_refresh(wf, cfg, comm, m, token)
        wf = _wide_kernel_call(wf, cfg, False, 1, m, interpret)
    if handed_on:
        return tuple(_wide_refresh(wf, cfg, comm, m, token))
    return _wide_crop(wf, cfg, m)


def model_step_wide(state: State, cfg: Config, comm: mpx.Comm,
                    first_step: bool) -> State:
    """One model step on the wide-halo path, standalone: ``_wide_run`` for
    one step at its own exchange depth (8) — frame, one kernel call, crop."""
    return _wide_run(state, 1, cfg, comm, 1, _margin_rows(1),
                     _resolve_interpret(comm), euler_first=first_step)


def model_step2_wide(state: State, cfg: Config, comm: mpx.Comm,
                     first_step: bool) -> State:
    """TWO model steps on the wide-halo path, standalone: ``_wide_run``
    for one two-step chunk (exchange depth 16); as a first step, the Euler
    call and a one-step call."""
    return _wide_run(state, 2, cfg, comm, 2, _margin_rows(2),
                     _resolve_interpret(comm), euler_first=first_step)


def _pltpu_roll():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll


def _resolve_mode(fast, cfg: Config = None):
    """``fast`` as one of the five concrete modes of ``select_steps``:
    ``"auto"`` decided from ``cfg``, an unknown name refused."""
    if fast == "auto":
        if cfg is None:
            raise ValueError(
                "select_steps('auto') needs the Config to decide kernel "
                "eligibility — pass cfg"
            )
        # the wide-halo pair kernel on the carried, lane-aligned frame
        # wherever the local interior fits its exchange depth, whatever the
        # mesh and the boundary: on whole lane tiles the same window
        # functions run about 1.5 x faster than on a field's own width
        # (PERF.md section 6, PR 36 and 38), and a single periodic rank's
        # x bands are slices of its own frame.  Below that depth what each
        # got before: the whole-step kernel for a single periodic rank (no
        # exchanges at all), the split-phase kernels for the rest.
        # Pair depth: three steps a call do not compile at benchmark width.
        if min(cfg.ny_local, cfg.nx_local) - 2 >= _margin_rows(2):
            return "wide2"
        if cfg.nproc == 1 and cfg.periodic_x:
            return "pallas2"
        return "pallas_halo"
    if isinstance(fast, str) and fast not in ("pallas2", "wide2",
                                              "pallas_halo"):
        raise ValueError(
            f"unknown step mode {fast!r}: one of False, True, 'pallas2', "
            "'wide2', 'pallas_halo', 'auto'"
        )
    return fast


def select_steps(fast, cfg: Config = None):
    """``(single_step, chunk_step_or_None, chunk_size)`` behind ``fast``:
    the single source of truth for every driver (``make_stepper``,
    ``fused_runner``, the benchmark's).  ``chunk_step`` advances
    ``chunk_size`` model steps per call and is only offered for the fused
    Pallas chunk modes; callers use it for whole chunks and fall back to
    ``single_step`` for the first (Euler) step and remainders.

    ``fast`` is one of (anything else raises ``ValueError``):

    - ``False`` — the reference-structured step (parity oracle);
    - ``True`` — ``model_step_fast`` (works on any mesh);
    - ``"pallas2"`` — the fused whole-step Pallas kernel, two steps per
      kernel call (single-rank periodic-x only; raises otherwise);
    - ``"wide2"`` — the communication-avoiding wide-halo kernel, two steps
      per exchange (any mesh with a local interior of 16 cells or more per
      dimension, ``_wide_run``);
    - ``"pallas_halo"`` — the split-phase Pallas kernels with real halo
      exchanges between them (any mesh, ``model_step_pallas_halo``);
    - ``"auto"`` — ``"wide2"`` wherever the local interior fits its
      exchange depth (16 cells a dimension), whatever the mesh and the
      boundary: the benchmark configuration (one rank, periodic in x, 3600
      columns) included, whose frame is then 29 whole lane tiles wide;
      below that ``"pallas2"`` for a single-rank periodic-x decomposition
      and ``"pallas_halo"`` for the rest.
    """
    mode = _resolve_mode(fast, cfg)
    if mode == "wide2":
        return model_step_wide, model_step2_wide, 2
    if mode == "pallas2":
        return model_step_pallas, model_step2_pallas, 2
    if mode == "pallas_halo":
        return model_step_pallas_halo, None, 1
    return (model_step_fast if mode else model_step), None, 1


def _advancer(cfg: Config, comm: mpx.Comm, fast):
    """``(advance, schedule, chunk_size, carried)`` behind ``fast``: the one
    place that knows the wide-halo mode from the others.

    ``advance(state, num_steps, euler_first=...)`` traces ``num_steps``
    model steps inside a region, the first of them the forward-Euler step
    where ``euler_first``.  In ``"wide2"`` it is ``_wide_run`` on the
    carried widened frame (a margin-band refresh between kernel calls
    instead of a crop and a re-widening per call); otherwise the step
    function for the Euler step and ``_run_steps`` for the rest.
    ``schedule(num_steps, euler_first=True, handed_on=False)`` is what a
    call of ``num_steps`` steps is made of after its Euler call (if it has
    one), by the schedule ``advance`` itself follows: ``(chunk calls,
    single-step calls, band refreshes, wide)``; ``handed_on`` is the call
    of a host loop that carries the frame on to the next.  ``carried`` is
    what such a loop carries where it is not the ``State``: in ``"wide2"``
    the three pieces of ``_wide_run`` as per-rank functions ``(start,
    carry_on, crop)`` — ``State`` -> frames with the Euler step, frames ->
    frames for ``num_steps``, frames -> ``State`` — and ``None`` in every
    other mode, whose steps take and leave a ``State``.  ``leg_plan`` and ``run_plan``
    have no mesh and pass ``comm=None``: they take the schedule and never
    call ``advance``."""
    mode = _resolve_mode(fast, cfg)
    step, chunk, chunk_size = select_steps(mode, cfg)

    if mode == "wide2":
        # partials, not nested functions: they bind the arguments without
        # a Python frame of their own between the region and ``_wide_run``.
        # With a nested function here the trace of the walled 71-step leg
        # took 0.6-0.8 s longer in the benchmark's process on the chip's
        # host (PERF.md section 6, PR 30): the same program, a tenth more
        # of its ``setup_s``.
        m = _margin_rows(chunk_size)
        bound = dict(cfg=cfg, comm=comm, chunk_size=chunk_size, m=m,
                     interpret=comm is not None and _resolve_interpret(comm))
        advance = partial(_wide_run, **bound)
        carried = (partial(_wide_run, num_steps=1, euler_first=True,
                           handed_on=True, **bound),
                   partial(_wide_run, euler_first=False, carried_in=True,
                           handed_on=True, **bound),
                   partial(_wide_crop, cfg=cfg, m=m))

        def schedule(num_steps, euler_first=True, handed_on=False):
            head, trips, rem = _wide_schedule(num_steps, chunk_size,
                                              euler_first)
            # a refresh before every call but the first on the frame as it
            # came, and one behind the last where the frame is handed on
            fresh = bool(rem) and not (euler_first or head or trips)
            return (head + trips, rem,
                    trips + rem - int(fresh) + int(handed_on), True)

    else:
        carried = None

        def advance(state, num_steps, euler_first):
            if euler_first:
                state = step(state, cfg, comm, first_step=True)
            return _run_steps(state, num_steps - int(euler_first), cfg,
                              comm, step, chunk, chunk_size)

        def schedule(num_steps, euler_first=True, handed_on=False):
            nchunks, rem = _steps_schedule(num_steps - int(euler_first),
                                           chunk, chunk_size)
            return nchunks, rem, 0, False

    return advance, schedule, chunk_size, carried


def _carried_stepper(start, carry_on, crop, comm: mpx.Comm):
    """The regions of a run that carries the widened frame, under the names
    of the two they stand in for (a trace reads ``first_step`` and
    ``multistep`` whichever form ran), and the crop: a plain ``jax.jit``
    over the stacked frames — slices and a ``where`` on axes no mesh axis
    shards, so no collective and no region."""

    @partial(mpx.spmd, comm=comm)
    def first_step(state: State):
        return start(state)

    @partial(mpx.spmd, comm=comm, static_argnums=(1,))
    def multistep(frames, num_steps: int):
        return carry_on(frames, num_steps)

    return first_step, multistep, jax.jit(jax.vmap(crop))


def make_stepper(cfg: Config, comm: mpx.Comm, *, fast=True):
    """Compile the two region programs: the first (Euler) step and an
    n-step AB-2 multistep (``lax.fori_loop`` inside the region — one XLA
    program per multistep, ref examples/shallow_water.py:415-420).

    ``fast`` selects the TPU-restructured step (``model_step_fast``,
    default); ``fast=False`` keeps the reference-structured step;
    ``"pallas2"``/``"wide2"``/``"pallas_halo"``/``"auto"`` select the
    Pallas kernels (see ``select_steps``) — all verified equal in
    tests/test_examples.py.  ``multistep`` advances exactly ``num_steps``
    steps in every mode (the chunk kernel handles whole chunks; the
    remainder falls back to single-step calls).

    Both take and return a ``State``, for whoever steps by hand.  A host
    loop that hands every call's result to the next call (``run_multisteps``)
    finds beside them what it may carry instead:
    ``first_step.carried(state) -> carry``, ``multistep.carried(carry,
    num_steps) -> carry`` and ``multistep.crop(carry) -> State``.  In
    ``"wide2"`` the carry is the widened frame (six stacked arrays), built
    by the first, advanced with neither build nor crop by the second and
    cropped by the third, where ``multistep(state, n)`` builds and crops a
    frame in every call; in every other mode (``_advancer`` decides) the
    carry is the ``State``, the carried forms are the two programs
    themselves and ``crop`` returns what it is given.
    """
    advance, _, _, carried = _advancer(cfg, comm, fast)

    @partial(mpx.spmd, comm=comm)
    def first_step(state: State) -> State:
        return advance(state, 1, euler_first=True)

    @partial(mpx.spmd, comm=comm, static_argnums=(1,))
    def multistep(state: State, num_steps: int) -> State:
        return advance(state, num_steps, euler_first=False)

    first_step.carried, multistep.carried, multistep.crop = (
        (first_step, multistep, _same) if carried is None
        else _carried_stepper(*carried, comm))
    return first_step, multistep


def _same(state):
    return state


def _steps_schedule(num_steps: int, chunk, chunk_size: int):
    """``(chunk calls, single-step calls)`` of ``_run_steps``; shared with
    ``leg_plan``."""
    return divmod(num_steps, chunk_size) if chunk is not None else (0, num_steps)


def _run_steps(state: State, num_steps: int, cfg, comm, step, chunk,
               chunk_size: int) -> State:
    """Advance ``num_steps`` non-first steps, using the chunk kernel for
    whole ``chunk_size``-step runs when available (``num_steps`` is
    static; the remainder is at most ``chunk_size - 1`` single steps).

    The chunk loop advances two chunks per iteration (an odd count's last
    chunk follows the loop).  The kernel reads each field through three
    overlapping block specs and so cannot write in place; with one call per
    iteration XLA's while loop copies all six new fields back into the
    carry's buffers (28.6 % of device time at 3600 x 28800 on a v5e), with
    two the second call writes into the buffers the first has just read and
    no field is copied (tests/test_solver_loop_hlo.py).

    Handed the wide-halo pair (``chunk is model_step2_wide``: what
    ``select_steps`` gives for ``"wide2"``), the steps run on the carried
    widened frame — ``_wide_run``: one frame built, a band refresh and a
    kernel call a chunk, one crop — and not as a loop of standalone chunk
    steps, each of which would build and crop a frame of its own; the
    ``State`` that ``make_stepper``'s ``multistep`` returns, bit for bit
    (tests/test_fused_runner.py).  So a driver that
    composes ``select_steps`` with this function gets what
    ``fused_runner`` gives, less the frame its own first step builds.
    ``_wide_run`` is called here, with no function between (PERF.md
    section 6, PR 30 and 34: a Python frame more above it is paid in
    tracing time)."""
    if chunk is model_step2_wide:
        return _wide_run(state, num_steps, cfg, comm, chunk_size,
                         _margin_rows(chunk_size), _resolve_interpret(comm),
                         euler_first=False)
    nchunks, rem = _steps_schedule(num_steps, chunk, chunk_size)
    if chunk is not None:
        if nchunks:  # fori_loop(0, 0) would still trace the chunk kernel
            state = jax.lax.fori_loop(
                0, nchunks, lambda _, s: chunk(s, cfg, comm, False), state,
                unroll=2,
            )
        for _ in range(rem):
            state = step(state, cfg, comm, False)
        return state
    if not rem:  # fori_loop(0, 0) would still trace the step
        return state
    return jax.lax.fori_loop(
        0, rem, lambda _, s: step(s, cfg, comm, False), state
    )


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def n_multisteps(cfg: Config, t1: float, num_multisteps: int) -> int:
    """How many ``num_multisteps``-step calls follow the first step of a run
    to model time ``t1`` [s]: the first multiple that reaches it."""
    return max(0, math.ceil((t1 - cfg.dt) / (cfg.dt * num_multisteps)))


def run_multisteps(first_step, multistep, state: State, n_iters: int,
                   num_multisteps: int, on_multistep=None) -> State:
    """The documented driver's host loop (ref examples/shallow_water.py:
    solve_shallow_water): one call of ``first_step``, then ``n_iters`` calls
    of ``multistep(state, num_multisteps)``, every call's result the next
    call's input.  ``first_step`` / ``multistep`` are ``make_stepper``'s.

    What goes from call to call is ``make_stepper``'s carry: in ``"wide2"``
    the widened frame, built by the first call, advanced by every later
    one, each leaving its margin bands refreshed, and cropped to the
    ``State`` this returns once, after the last — the frame a crop and a
    rebuild between two calls would have left, bit for bit, and what
    ``fused_runner``'s leg carries through its loop; in every other mode
    the ``State`` itself.  A pair without the
    carried forms (a caller's own two functions) is called as it is.

    The caller closes the run with ``jax.block_until_ready`` on what this
    returns.  ``on_multistep(state)``, where given, is called with the
    finished ``State`` after each of the ``1 + n_iters`` calls (``solve``
    takes its snapshots there): a run with a hook waits call by call, as
    reading a field on the host makes it anyway, and pays a crop of the
    carry a call, dispatched only where the hook exists.

    Two dispatches are in flight at the most: before call k + 1 goes (or
    the crop behind the last call) the loop waits for call k - 1, so one
    call runs and one is queued behind it, which is all the device needs
    to stay busy.  A third cannot be allocated beside them at a
    chip-filling size: at 3600 x 28800 a call holds 2.5 GB of input, 2.5 GB
    of results and 5.1 GB of temporaries beside the caller's retained
    state, and with every call dispatched at once the runtime allocated
    results until 77 MB of the chip's 16.9 GB were left, held the host
    inside each launch until memory came free, and in 3 of 16 windows one
    launch of 135 stalled for 1.6-2.5 s (PERF.md section 6, PR 32).  The
    wait is on a result the next call reads anyway, so it keeps nothing
    alive.

    What a run costs beside a ``fused_runner`` leg of the same steps is
    the entry and exit of a region once a *call* (``run_plan`` counts
    them)."""
    forms = (getattr(first_step, "carried", None),
             getattr(multistep, "carried", None),
             getattr(multistep, "crop", None))
    start, carry_on, crop = (forms if all(forms)
                             else (first_step, multistep, _same))
    carry = start(state)
    if on_multistep is not None:
        on_multistep(jax.block_until_ready(crop(carry)))
    before = None  # the newest call's input: the result of the call before
    for _ in range(n_iters):
        if before is not None:
            jax.block_until_ready(before)
        before = carry
        carry = carry_on(carry, num_multisteps)
        if on_multistep is not None:
            on_multistep(jax.block_until_ready(crop(carry)))
    if before is not None:
        jax.block_until_ready(before)
    return crop(carry)


def run_plan(cfg: Config, fast, n_iters: int, num_multisteps: int = 10) -> dict:
    """What one run of ``run_multisteps`` over ``make_stepper(cfg, comm,
    fast=fast)`` is made of, by the schedule its programs are built from
    (``_advancer``'s, as ``leg_plan``): per run ``calls`` (region calls),
    ``steps``, ``frames_built`` and ``crops`` (1 and 1 in ``"wide2"``, whose
    run carries the frame from call to call and crops it once behind the
    last, outside any region; 0 and 0 elsewhere); ``steps_per_kernel_call``;
    and under ``first_step`` and ``multistep`` what *one call* of each
    carried form holds, in ``leg_plan``'s keys (``steps``, ``euler_calls``,
    ``chunk_calls``, ``single_step_calls``, ``frames_built``,
    ``band_refreshes``, ``crops``): the first builds the frame, every call
    refreshes the bands between its kernel calls and once more behind the
    last, for the call that follows (one refresh a run more than a leg of
    the same steps: the crop's), none crops."""
    if n_iters < 0 or num_multisteps < 1:
        raise ValueError("a run is its first step and n_iters >= 0 calls of "
                         f"num_multisteps >= 1 steps, got {n_iters} of "
                         f"{num_multisteps}")
    _, schedule, chunk_size, _ = _advancer(cfg, None, fast)

    def call_plan(steps, euler_first):
        nchunks, rem, refreshes, wide = schedule(steps, euler_first,
                                                 handed_on=True)
        return {"steps": steps, "euler_calls": int(euler_first),
                "chunk_calls": nchunks, "single_step_calls": rem,
                "frames_built": int(wide and euler_first),
                "band_refreshes": refreshes, "crops": 0}

    first = call_plan(1, True)
    framed = first["frames_built"]  # the run's one frame, and its one crop
    return {"calls": 1 + n_iters, "steps": 1 + n_iters * num_multisteps,
            "steps_per_kernel_call": chunk_size,
            "frames_built": framed, "crops": framed,
            "first_step": first,
            "multistep": call_plan(num_multisteps, False)}


def solve(cfg: Config, t1: float, *, num_multisteps: int = 10, devices=None,
          collect: bool = True, verbose: bool = False, fast=True):
    """Iterate the model to time ``t1`` [s] by the host loop
    ``run_multisteps``.  Returns ``(snapshots, wall_time_s, n_steps)``;
    ``snapshots`` is a list of stacked-block h fields (empty when
    ``collect=False``): the initial state, the state after the first step
    and after every multistep, and the root-gathered final state."""
    mesh, comm = make_mesh_and_comm(cfg, devices=devices)
    first_step, multistep = make_stepper(cfg, comm, fast=fast)
    n_iters = n_multisteps(cfg, t1, num_multisteps)

    state = initial_state(cfg, comm)
    snapshots = [np.asarray(state.h)] if collect else []

    # warm-up compile of the programs the loop runs (excluded from timing,
    # like the reference's pre-compilation at examples/shallow_water.py:
    # 449-450): a run of one multistep; dispatch is asynchronous, so wait
    # for the device before the clock starts
    jax.block_until_ready(run_multisteps(first_step, multistep, state,
                                         min(n_iters, 1), num_multisteps))

    multisteps_done = itertools.count()

    def on_multistep(state):
        if collect:
            snapshots.append(np.asarray(state.h))  # device->host sync
        if verbose:
            t = cfg.dt * (1 + next(multisteps_done) * num_multisteps)
            print(f"  t = {t / DAY_IN_SECONDS:.3f} days", end="\r")

    start = time.perf_counter()
    state = run_multisteps(first_step, multistep, state, n_iters,
                           num_multisteps,
                           on_multistep if collect or verbose else None)
    # without snapshots: pipelined throughput mode, two calls in flight and
    # the run closed by one wait on the last state
    jax.block_until_ready(state)
    wall = time.perf_counter() - start

    # collect the full solution at rank 0 — exercises the eager gather path
    # (ref examples/shallow_water.py:588 uses mpi4jax.gather the same way);
    # appended as an extra snapshot, so the last two entries hold the same
    # final state (stacked view, then root-gathered view)
    if collect:
        gathered, _ = mpx.gather(state.h, root=0, comm=comm)
        snapshots.append(np.asarray(gathered[0]))

    return snapshots, wall, 1 + n_iters * num_multisteps


def fused_runner(cfg: Config, comm: mpx.Comm, fast="auto"):
    """The whole-run region behind ``solve_fused``, for every caller that
    times or pins it: ``(fused, chunk_size)``.

    ``fused(state, total)`` is an ``mpx.spmd`` function (``total`` static)
    that advances the forward-Euler first step and ``total`` Adams-Bashforth
    steps in one program — a *leg* of ``total + 1`` steps.  In
    ``"wide2"`` (``_advancer`` decides) it is ``_wide_run`` on the carried
    widened frame (the frame built once, a margin-band refresh and one
    kernel call per chunk, one crop at the end); otherwise the first step
    and ``_run_steps``.  ``chunk_size`` is the number of steps one call of
    the loop's kernel advances.  Pin it with ``mpx.compile(fused, state,
    total)``; ``leg_plan`` says what the leg is made of."""
    advance, _, chunk_size, _ = _advancer(cfg, comm, fast)

    @partial(mpx.spmd, comm=comm, static_argnums=(1,))
    def fused(state: State, total: int) -> State:
        return advance(state, total + 1, euler_first=True)

    return fused, chunk_size


def leg_plan(cfg: Config, fast, steps: int) -> dict:
    """What one leg of ``steps`` model steps (the Euler step included) of
    ``fused_runner(cfg, comm, fast)`` is made of, as counts per rank, by
    the schedule the leg itself is built from (``_advancer``'s:
    ``_wide_schedule`` in ``"wide2"``, ``_steps_schedule`` otherwise):

    - ``euler_calls``: calls of the first-step kernel (or step function);
    - ``chunk_calls``: calls of the ``steps_per_kernel_call``-step kernel;
    - ``single_step_calls``: one-step calls for what the chunks leave over
      (every step after the first where the mode has no chunk kernel);
    - ``frames_built``, ``band_refreshes``, ``crops``: the widened frame's
      life in ``"wide2"`` (``_wide_exchange`` once, ``_wide_refresh``
      before every call after the Euler one, ``_wide_crop`` once); 0
      elsewhere."""
    if steps < 1:
        raise ValueError(f"a leg has at least its Euler step, got {steps}")
    _, schedule, chunk_size, _ = _advancer(cfg, None, fast)
    nchunks, rem, refreshes, wide = schedule(steps)
    return {"steps": steps, "steps_per_kernel_call": chunk_size,
            "euler_calls": 1, "chunk_calls": nchunks,
            "single_step_calls": rem, "frames_built": int(wide),
            "band_refreshes": refreshes, "crops": int(wide)}


def solve_fused(cfg: Config, t1: float, *, num_multisteps: int = 10,
                devices=None, fast=True, return_state=False,
                pinned: bool = False):
    """Benchmark-mode solve: the ENTIRE simulation is one XLA program
    (first Euler step + a ``fori_loop`` over all remaining steps), so the
    host dispatches once instead of once per multistep.  Runs the same
    number of steps as ``solve(collect=False)``; returns
    ``(wall_time_s, n_steps)`` with compile excluded (reference protocol,
    ref examples/shallow_water.py:449-450), plus the final stacked state
    when ``return_state`` is set (equality tests).

    The program is ``fused_runner``'s (in ``"wide2"`` the state is
    carried in WIDENED form across the whole run: ``_wide_run``).

    ``pinned=True`` runs the timed calls through an ``mpx.compile``-pinned
    artifact of the whole-run program (docs/aot.md).  A pin that fails
    raises: the run never quietly continues on another program.

    The state is committed to the mesh once and re-passed to the warm-up
    and the timed run; every wait is ``jax.block_until_ready`` on the
    program's outputs.
    """
    mesh, comm = make_mesh_and_comm(cfg, devices=devices)
    n_steps = 1 + n_multisteps(cfg, t1, num_multisteps) * num_multisteps
    fused, _ = fused_runner(cfg, comm, fast)

    state = initial_state(cfg, comm)
    total = n_steps - 1
    if pinned:
        # AOT-pin the whole-run program (docs/aot.md): the timed call
        # executes a compiled artifact with no per-call key work.  The
        # step-count static folds at pin time.
        runner = mpx.compile(fused, state, total)
    else:
        def runner(s):
            return fused(s, total)

    jax.block_until_ready(runner(state))  # compile + warm-up
    start = time.perf_counter()
    out = jax.block_until_ready(runner(state))
    wall = time.perf_counter() - start
    if return_state:
        return wall, n_steps, out
    return wall, n_steps


def save_animation(snapshots, cfg: Config, path: str = "shallow-water.gif"):
    try:
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        from matplotlib import animation
    except ImportError:
        print("matplotlib not available; skipping animation")
        return
    fig, ax = plt.subplots(figsize=(8, 4))
    frames = [reassemble(s, cfg) - cfg.depth for s in snapshots]
    vmax = np.abs(frames[-1]).max()
    im = ax.imshow(frames[0], origin="lower", cmap="RdBu_r", vmin=-vmax, vmax=vmax)
    fig.colorbar(im, label="height anomaly [m]")

    def update(i):
        im.set_data(frames[i])
        ax.set_title(f"step {i}")
        return (im,)

    anim = animation.FuncAnimation(fig, update, frames=len(frames), interval=50)
    anim.save(path, writer=animation.PillowWriter(fps=20))
    print(f"wrote {path}")


def pick_process_grid(n: int):
    """Same decomposition rule as the reference: nproc_y = min(n, 2), and
    even device counts only above 1 (ref examples/shallow_water.py:57-64
    validates against its supported process counts the same way)."""
    nproc_y = min(n, 2)
    if n % nproc_y != 0:
        raise ValueError(
            f"Got invalid number of devices: {n}. Use 1 or an even count "
            "(the domain is decomposed over a (2, n//2) grid)."
        )
    return nproc_y, n // nproc_y


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--benchmark", action="store_true",
                   help="reference benchmark config: 100x domain, 0.1 days, "
                        "no output (ref docs/shallow-water.rst:44-55)")
    p.add_argument("--t1-days", type=float, default=None,
                   help="simulated model days (default: 1.0; benchmark: 0.1)")
    p.add_argument("--scale", type=float, default=None,
                   help="linear domain scale factor (benchmark default: 10)")
    p.add_argument("--save-animation", action="store_true")
    p.add_argument("--n-devices", type=int, default=None,
                   help="use the first N local devices (default: all)")
    args = p.parse_args()

    from mpi4jax_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    devices = jax.devices()
    if args.n_devices:
        devices = devices[: args.n_devices]
    nproc_y, nproc_x = pick_process_grid(len(devices))

    scale = args.scale if args.scale is not None else (10.0 if args.benchmark else 1.0)
    cfg = Config(nproc_y=nproc_y, nproc_x=nproc_x)
    cfg = replace(cfg, nx=int(cfg.nx * scale), ny=int(cfg.ny * scale))
    t1 = (args.t1_days if args.t1_days is not None
          else (0.1 if args.benchmark else 1.0)) * DAY_IN_SECONDS

    print(f"shallow water: {cfg.ny}x{cfg.nx} interior on a "
          f"({nproc_y}, {nproc_x}) mesh of {len(devices)} "
          f"{devices[0].platform.upper()} device(s), dt={cfg.dt:.1f}s")

    if args.benchmark:
        # one fused XLA program for the whole run (no snapshots)
        wall, n_steps = solve_fused(cfg, t1, devices=devices, fast="auto")
        snapshots = []
    else:
        snapshots, wall, n_steps = solve(cfg, t1, devices=devices,
                                         verbose=True, fast="auto")
    print(f"\nSolution took {wall:.2f}s "
          f"({n_steps} steps, {n_steps / wall:.1f} steps/s)")

    if args.save_animation and snapshots:
        save_animation(snapshots, cfg)


if __name__ == "__main__":
    main()
