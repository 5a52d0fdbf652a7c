"""Plain float32 reference of the shallow-water step in a closed basin
(``periodic_boundary_x`` off), and the initial state on the walled domain.

Written from the equations of the reference demo (nonlinear shallow water
on an Arakawa C-grid, Sadourny's energy-conserving scheme, Adams-Bashforth 2
with a forward-Euler first step, lateral friction as a second substep;
dionhaefner/shallow-water as adapted in mpi4jax's
``examples/shallow_water.py``, whose ``enforce_boundaries`` supplies the
walls).  It shares no code with this repository's
``examples/shallow_water.py`` or with the periodic reference beside it and
imports nothing of the program: plain ``jax.numpy`` on whole fields, slices
for the stencil, no kernel, no widened frame.

Layout: every field is ``(ny + 2, nx + 2)``, the physical cells with one
ring of border cells, as the source holds one process's block.  With walls
on all four sides nothing ever fills the ring: in ``h``, ``u``, ``v`` it
holds the initial state's values for good, in every derived field and in
the tendencies it is zero.  The walls, as the source applies them:

- the cell-centred height is edge-replicated across each wall;
- the volume flux and the viscous flux through the east wall (the last
  physical column of an east-face quantity) and through the north wall
  (the last physical row of a north-face quantity) are zero; through the
  west and south walls they are the ring's zeros;
- after each integration ``u`` is set to zero on the east wall column and
  ``v`` on the north wall row.  The friction substep follows and is *not*
  followed by the wall condition again (the source's order), so a final
  state carries at most one friction increment there: ``dt * viscosity /
  dx**2`` (4.0e-6 at the published numbers) times the neighbouring speed.

``precision`` is the dtype every field and every operation is carried in:
float32 as the configuration states, or bfloat16 for the control that has
to come out as not correct.  There is no matrix product in a step; the one
in the initial state's assembly runs at ``highest`` precision.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

FIELDS = ("h", "u", "v", "dh", "du", "dv")
PUBLISHED_PERTURBATION_M = 0.2

_C = np.s_[1:-1, 1:-1]   # a[j, i], the physical cells
_E = np.s_[1:-1, 2:]     # a[j, i + 1]
_W = np.s_[1:-1, :-2]    # a[j, i - 1]
_N = np.s_[2:, 1:-1]     # a[j + 1, i]
_S = np.s_[:-2, 1:-1]    # a[j - 1, i]
_NE = np.s_[2:, 2:]
_NW = np.s_[2:, :-2]
_SE = np.s_[:-2, 2:]


def params(config: dict) -> dict:
    """The solver's numbers from a configuration file, with the derived
    time step and viscosity of the published model."""
    if config["periodic_x"]:
        raise ValueError("this reference is the closed basin's; the periodic "
                         "one is chipbench/reference/shallow_water.py")
    p = {k: config[k] for k in ("nx", "ny", "dx", "dy", "gravity", "depth",
                                "coriolis_f", "coriolis_beta", "ab_a",
                                "ab_b")}
    p["ny_published"] = config.get("scaled", {}).get("ny", {}).get(
        "published", config["ny"])
    p["dt"] = 0.125 * min(p["dx"], p["dy"]) / math.sqrt(
        p["gravity"] * p["depth"])
    p["viscosity"] = 1e-3 * p["coriolis_f"] * p["dx"] ** 2
    return p


def friction_increment(p: dict) -> float:
    """The largest share of a neighbour's speed that one friction substep
    can put on a wall cell that the wall condition has just zeroed."""
    return p["dt"] * p["viscosity"] / min(p["dx"], p["dy"]) ** 2


# ---------------------------------------------------------------------------
# initial state
# ---------------------------------------------------------------------------


def initial_factors(p: dict, seed: int, n_modes: int = 4,
                    amplitude=(0.02, 0.05)) -> dict:
    """The one-dimensional factors of the initial state, float64 on the
    host (``ny + 2`` and ``nx + 2`` numbers, not a grid), the border cells
    included: cell ``(1, 1)`` sits at ``(0, 0)``.

    The published state in its published place: a zonal jet at the middle
    of the *published* domain (``ny_published`` rows) in approximate
    geostrophic balance, plus the published 0.2 m perturbation.  Rows
    beyond the published domain continue its northern edge.  The walls are
    left to the first step, as the source leaves them: the jet blows
    through the east wall column until the first integration zeroes it
    there.  ``seed`` adds ``n_modes`` low-wavenumber modes whose amplitudes
    sum to at most the published perturbation."""
    nx, ny = p["nx"], p["ny"]
    len_x = nx * p["dx"]
    len_y_pub = p["ny_published"] * p["dy"]
    x = (np.arange(nx + 2) - 1.0) * p["dx"]
    y = (np.arange(ny + 2) - 1.0) * p["dy"]

    u_y = 10.0 * np.exp(-((y - 0.5 * len_y_pub) ** 2) / (0.02 * len_x) ** 2)
    f_y = p["coriolis_f"] + y * p["coriolis_beta"]
    h_geo = np.cumsum(-p["dy"] * u_y * f_y / p["gravity"])
    h_y = p["depth"] + h_geo - h_geo[: p["ny_published"] + 2].mean()

    rng = np.random.default_rng(seed)
    amp = rng.uniform(amplitude[0], amplitude[1], n_modes)
    assert amp.sum() <= PUBLISHED_PERTURBATION_M + 1e-12
    kx = rng.integers(1, 7, n_modes)
    ky = rng.integers(1, 9, n_modes)
    phase = rng.uniform(0.0, 2.0 * np.pi, n_modes)
    mode_x = np.concatenate([
        np.sin(x / len_x * 10.0 * np.pi)[None] * PUBLISHED_PERTURBATION_M,
        amp[:, None] * np.sin(
            2.0 * np.pi * kx[:, None] * x[None] / len_x + phase[:, None]),
    ])
    mode_y = np.concatenate([
        np.cos(y / len_y_pub * 8.0 * np.pi)[None],
        np.cos(np.pi * ky[:, None] * y[None] / len_y_pub),
    ])
    return {"h_y": h_y, "u_y": u_y, "mode_x": mode_x, "mode_y": mode_y}


@jax.jit
def _assemble(h_y, u_y, mode_x, mode_y):
    with jax.default_matmul_precision("highest"):
        h = h_y[:, None] + jnp.einsum("mj,mi->ji", mode_y, mode_x)
    u = jnp.broadcast_to(u_y[:, None], h.shape)
    return h, u, jnp.zeros_like(h)


def initial_fields(p: dict, seed: int, n_modes: int = 4,
                   amplitude=(0.02, 0.05)):
    """``(h, u, v)`` float32, each ``(ny + 2, nx + 2)``, assembled on the
    default device in one jitted call.  The seed enters as data, so every
    seed runs the same compiled program."""
    fac = initial_factors(p, seed, n_modes, amplitude)
    return _assemble(*(jnp.asarray(fac[k], jnp.float32)
                       for k in ("h_y", "u_y", "mode_x", "mode_y")))


def with_halo_columns(field):
    """The program's ``(ny + 2, nx + 2)`` layout: a walled field carries
    its border columns already (they are cells of the initial state, not
    images of the far side), so this is the field itself."""
    return field


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def _ringed(mid):
    """Physical cells with a ring of zeros around them."""
    return jnp.pad(mid, 1)


def _walled_east(mid):
    """An east-face quantity with nothing through the east wall."""
    return mid.at[:, -1].set(0)


def _walled_north(mid):
    """A north-face quantity with nothing through the north wall."""
    return mid.at[-1, :].set(0)


def step(fields, p: dict, first: bool):
    """One model step on ``(h, u, v, dh, du, dv)``."""
    h, u, v, dh, du, dv = fields
    dtype = h.dtype
    c = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    dx, dy, g, dt = c(p["dx"]), c(p["dy"]), c(p["gravity"]), c(p["dt"])
    half, quarter = c(0.5), c(0.25)
    ny = h.shape[0] - 2

    # cell-centred height, edge-replicated across the four walls
    hc = jnp.pad(h[_C], 1, mode="edge")

    # volume fluxes through the east and north faces
    fe = _ringed(_walled_east(half * (hc[_C] + hc[_E]) * u[_C]))
    fn = _ringed(_walled_north(half * (hc[_C] + hc[_N]) * v[_C]))

    dh_new = -(fe[_C] - fe[_W]) / dx - (fn[_C] - fn[_S]) / dy

    # potential vorticity
    rows = jnp.arange(ny, dtype=jnp.float32)
    cor = (p["coriolis_f"] + rows * p["dy"] * p["coriolis_beta"]).astype(
        dtype)[:, None]
    rel_vort = (v[_E] - v[_C]) / dx - (u[_N] - u[_C]) / dy
    depth_q = quarter * (hc[_C] + hc[_E] + hc[_N] + hc[_NE])
    q = _ringed((cor + rel_vort) / depth_q)

    ke = _ringed(half * (half * (u[_C] * u[_C] + u[_W] * u[_W])
                         + half * (v[_C] * v[_C] + v[_S] * v[_S])))

    du_new = (
        -g * (h[_E] - h[_C]) / dx
        + half * (q[_C] * half * (fn[_C] + fn[_E])
                  + q[_S] * half * (fn[_S] + fn[_SE]))
        - (ke[_E] - ke[_C]) / dx
    )
    dv_new = (
        -g * (h[_N] - h[_C]) / dy
        - half * (q[_C] * half * (fe[_C] + fe[_N])
                  + q[_W] * half * (fe[_W] + fe[_NW]))
        - (ke[_N] - ke[_C]) / dy
    )

    if first:
        hm = h[_C] + dt * dh_new
        um = u[_C] + dt * du_new
        vm = v[_C] + dt * dv_new
    else:
        a, b = c(p["ab_a"]), c(p["ab_b"])
        hm = h[_C] + dt * (a * dh_new + b * dh[_C])
        um = u[_C] + dt * (a * du_new + b * du[_C])
        vm = v[_C] + dt * (a * dv_new + b * dv[_C])
    # no normal flow through the east and the north wall
    um = _walled_east(um)
    vm = _walled_north(vm)

    def put(full, mid):
        return full.at[_C].set(mid)

    # lateral friction
    visc = c(p["viscosity"])
    out = []
    for full in (put(u, um), put(v, vm)):
        gx = _ringed(_walled_east(visc * (full[_E] - full[_C]) / dx))
        gy = _ringed(_walled_north(visc * (full[_N] - full[_C]) / dy))
        out.append(put(full, full[_C] + dt * (
            (gx[_C] - gx[_W]) / dx + (gy[_C] - gy[_S]) / dy)))
    u, v = out

    return (put(h, hm), u, v,
            _ringed(dh_new), _ringed(du_new), _ringed(dv_new))


def make_run(p: dict, steps: int, precision=jnp.float32):
    """A jitted ``(h, u, v) -> six fields`` that runs one leg: a
    forward-Euler step and ``steps - 1`` Adams-Bashforth steps."""

    @jax.jit
    def run(h, u, v):
        h, u, v = (a.astype(precision) for a in (h, u, v))
        zero = jnp.zeros_like(h)
        with jax.default_matmul_precision("highest"):
            fields = step((h, u, v, zero, zero, zero), p, True)
            fields = jax.lax.fori_loop(
                0, steps - 1, lambda _, f: step(f, p, False), fields)
        return tuple(a.astype(jnp.float32) for a in fields)

    return run
