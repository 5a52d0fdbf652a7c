"""Plain float32 reference of the shallow-water step, and the initial state.

Written from the equations of the reference demo (nonlinear shallow water
on an Arakawa C-grid, Sadourny's energy-conserving scheme, Adams-Bashforth 2
with a forward-Euler first step, lateral friction as a second substep;
dionhaefner/shallow-water as adapted in mpi4jax's docs/shallow-water.rst).
It shares no code with ``examples/shallow_water.py`` and imports nothing of
the program: plain ``jax.numpy``, one full field per intermediate, no
kernel, no halo columns.

Layout: every field is ``(ny + 2, nx)``.  x is periodic and has no halo
(``jnp.roll``); y has one boundary row on each side.  The boundary rows of
``h``, ``u``, ``v`` hold the initial state's values and never change (the
domain has walls in y: no exchange ever fills them); the boundary rows of
derived fields and tendencies are zero.  ``precision`` is the dtype every
field and every operation is carried in: float32 as the configuration
states, or bfloat16 for the control that has to come out as not correct.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

FIELDS = ("h", "u", "v", "dh", "du", "dv")
PUBLISHED_PERTURBATION_M = 0.2


def params(config: dict) -> dict:
    """The solver's numbers from a configuration file, with the derived
    time step and viscosity of the published model."""
    p = {k: config[k] for k in ("nx", "ny", "dx", "dy", "gravity", "depth",
                                "coriolis_f", "coriolis_beta", "ab_a",
                                "ab_b")}
    p["ny_published"] = config.get("scaled", {}).get("ny", {}).get(
        "published", config["ny"])
    p["dt"] = 0.125 * min(p["dx"], p["dy"]) / math.sqrt(
        p["gravity"] * p["depth"])
    p["viscosity"] = 1e-3 * p["coriolis_f"] * p["dx"] ** 2
    return p


# ---------------------------------------------------------------------------
# initial state
# ---------------------------------------------------------------------------


def initial_factors(p: dict, seed: int, n_modes: int = 4,
                    amplitude=(0.02, 0.05)) -> dict:
    """The one-dimensional factors of the initial state, float64 on the
    host (``ny + 2`` and ``nx`` numbers, not a grid).

    The published state in its published place: a zonal jet at the middle
    of the *published* domain (``ny_published`` rows) in approximate
    geostrophic balance, plus the published 0.2 m perturbation.  Rows
    beyond the published domain continue its northern edge.  ``seed`` adds
    ``n_modes`` low-wavenumber modes whose amplitudes sum to at most the
    published perturbation."""
    nx, ny = p["nx"], p["ny"]
    len_x = nx * p["dx"]
    len_y_pub = p["ny_published"] * p["dy"]
    x = np.arange(nx) * p["dx"]
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
    h = h_y[:, None] + jnp.einsum("mj,mi->ji", mode_y, mode_x)
    u = jnp.broadcast_to(u_y[:, None], h.shape)
    return h, u, jnp.zeros_like(h)


def initial_fields(p: dict, seed: int, n_modes: int = 4,
                   amplitude=(0.02, 0.05)):
    """``(h, u, v)`` float32, each ``(ny + 2, nx)``, assembled on the
    default device in one jitted call.  The seed enters as data, so every
    seed runs the same compiled program."""
    fac = initial_factors(p, seed, n_modes, amplitude)
    return _assemble(*(jnp.asarray(fac[k], jnp.float32)
                       for k in ("h_y", "u_y", "mode_x", "mode_y")))


def with_halo_columns(field):
    """``(ny + 2, nx)`` to the program's ``(ny + 2, nx + 2)``: one periodic
    image column on each side, bit-equal to the column it mirrors."""
    return jnp.concatenate([field[:, -1:], field, field[:, :1]], axis=1)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def _east(a):
    return jnp.roll(a, -1, axis=1)   # a[j, i + 1]


def _west(a):
    return jnp.roll(a, 1, axis=1)    # a[j, i - 1]


def _framed(mid):
    """Interior rows with a zero boundary row on each side."""
    zero = jnp.zeros_like(mid[:1])
    return jnp.concatenate([zero, mid, zero], axis=0)


def step(fields, p: dict, first: bool):
    """One model step on ``(h, u, v, dh, du, dv)``."""
    h, u, v, dh, du, dv = fields
    dtype = h.dtype
    c = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    dx, dy, g, dt = c(p["dx"]), c(p["dy"]), c(p["gravity"]), c(p["dt"])
    half, quarter = c(0.5), c(0.25)
    ny = h.shape[0] - 2

    hm, um, vm = h[1:-1], u[1:-1], v[1:-1]
    # cell-centred height, edge-replicated across the two walls
    hc = jnp.concatenate([h[1:2], hm, h[-2:-1]], axis=0)
    hcm, hcn = hc[1:-1], hc[2:]

    # volume fluxes through the east and north faces; none through the
    # north wall
    fe_m = half * (hcm + _east(hcm)) * um
    fn_m = half * (hcm + hcn) * vm
    fn_m = fn_m.at[-1].set(0)
    fe, fn = _framed(fe_m), _framed(fn_m)

    dh_new = -(fe_m - _west(fe_m)) / dx - (fn_m - fn[:-2]) / dy

    # potential vorticity
    rows = jnp.arange(ny, dtype=jnp.float32)
    cor = (p["coriolis_f"] + rows * p["dy"] * p["coriolis_beta"]).astype(
        dtype)[:, None]
    rel_vort = (_east(vm) - vm) / dx - (u[2:] - um) / dy
    depth_q = quarter * (hcm + _east(hcm) + hcn + _east(hcn))
    q_m = (cor + rel_vort) / depth_q
    q = _framed(q_m)

    ke_m = half * (half * (um * um + _west(um) ** 2)
                   + half * (vm * vm + v[:-2] ** 2))
    ke = _framed(ke_m)

    du_new = (
        -g * (_east(hm) - hm) / dx
        + half * (q_m * half * (fn_m + _east(fn_m))
                  + q[:-2] * half * (fn[:-2] + _east(fn[:-2])))
        - (_east(ke_m) - ke_m) / dx
    )
    dv_new = (
        -g * (h[2:] - hm) / dy
        - half * (q_m * half * (fe_m + fe[2:])
                  + _west(q_m) * half * (_west(fe_m) + _west(fe[2:])))
        - (ke[2:] - ke_m) / dy
    )

    if first:
        hm = hm + dt * dh_new
        um = um + dt * du_new
        vm = vm + dt * dv_new
    else:
        a, b = c(p["ab_a"]), c(p["ab_b"])
        hm = hm + dt * (a * dh_new + b * dh[1:-1])
        um = um + dt * (a * du_new + b * du[1:-1])
        vm = vm + dt * (a * dv_new + b * dv[1:-1])
    vm = vm.at[-1].set(0)  # no flow through the north wall

    # lateral friction
    visc = c(p["viscosity"])
    out = []
    for mid, full in ((um, u), (vm, v)):
        north = jnp.concatenate([mid[1:], full[-1:]], axis=0)
        gx = visc * (_east(mid) - mid) / dx
        gy = visc * (north - mid) / dy
        gy = gy.at[-1].set(0)
        gy_south = jnp.concatenate([jnp.zeros_like(gy[:1]), gy[:-1]], axis=0)
        out.append(mid + dt * ((gx - _west(gx)) / dx + (gy - gy_south) / dy))
    um, vm = out

    def put(full, mid):
        return jnp.concatenate([full[:1], mid, full[-1:]], axis=0)

    return (put(h, hm), put(u, um), put(v, vm),
            _framed(dh_new), _framed(du_new), _framed(dv_new))


def make_run(p: dict, steps: int, precision=jnp.float32):
    """A jitted ``(h, u, v) -> six fields`` that runs one leg: a
    forward-Euler step and ``steps - 1`` Adams-Bashforth steps."""

    @jax.jit
    def run(h, u, v):
        h, u, v = (a.astype(precision) for a in (h, u, v))
        zero = jnp.zeros_like(h)
        fields = step((h, u, v, zero, zero, zero), p, True)
        fields = jax.lax.fori_loop(
            0, steps - 1, lambda _, f: step(f, p, False), fields)
        return tuple(a.astype(jnp.float32) for a in fields)

    return run
