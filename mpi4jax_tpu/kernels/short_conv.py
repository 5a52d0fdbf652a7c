"""The gated short convolution of a hybrid decoder's convolution layers, in
Pallas.

``u`` (the layer's normalised input) goes through one projection to ``[B |
C | x] = u W_in``, a ``(T, 3D)`` array; the operator is

    z_t = B_t x_t
    c_t = w[0] z_{t-2} + w[1] z_{t-1} + w[2] z_t     (z = 0 before t = 0)
    y_t = C_t c_t

a depthwise causal convolution of three taps (``w``: ``(3, D)``, tap 2 on the
current token) between two gates, all elementwise along the channels.  The
kernels read the projection's output as one operand, through three block
views (its ``B``, ``C`` and ``x`` thirds), so the thirds are never split
into copies of their own:

- ``short_conv_fwd`` — ``y`` from ``[B | C | x]`` and ``w``;
- ``short_conv_bwd`` — ``d[B | C | x]`` (written into one ``(T, 3D)``
  array) and ``dw``.

A tile of ``rows`` positions reads the 8 positions before it too (the
forward's two earlier taps) and, in the backward, the 8 after it (the
convolution's transpose looks two positions ahead).  Operands are
bfloat16 and every product and sum is float32, as in the package's other
kernels; ``y`` and ``d[B | C | x]`` are written in the operand's dtype and
``dw`` in float32.  Off the TPU the same arithmetic runs as a ``jax.numpy``
path (``force_jnp``, or any other backend), differentiated by jax itself,
as ``flash_attention.py`` does.

The convolution runs along the whole sequence it is given: in a sequence
that packs several documents, the first two positions of a document see
the last two of the one before.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TAPS = 3
HALO = 8         # rows read beyond a tile: one sublane tile of float32
_ROWS = 512      # positions a grid step
_COLS = 512      # channels a grid step
_VMEM_LIMIT = 100 * 1024 * 1024

__all__ = ["TAPS", "short_conv"]


def _use_kernel(interpret: bool, force_jnp: bool) -> bool:
    return not force_jnp and (interpret or jax.default_backend() == "tpu")


def _tiles(t: int, d: int):
    """``(rows, cols)`` of a grid step: whole tiles of the sequence and of
    the channels (a dimension that does not divide is taken whole)."""
    rows = _ROWS if t % _ROWS == 0 else t
    cols = _COLS if d % _COLS == 0 else d
    if rows % HALO:
        raise ValueError(f"{t} positions are not whole tiles of {HALO}")
    return rows, cols


def _params(interpret, semantics):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def _vma(x):
    return frozenset(getattr(jax.typeof(x), "vma", frozenset()))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _fill(s_ref, halo, tile):
    """``s_ref`` (HALO + rows, cols) float32 <- ``[halo; tile]``: a row
    ``r`` of the tile is ``s_ref[HALO + r]``, the rows beside it are read
    at an offset."""
    s_ref[:HALO] = halo
    s_ref[HALO:] = tile


def _fwd_kernel(b_ref, c_ref, x_ref, bp_ref, xp_ref, w_ref, y_ref, z_s):
    i = pl.program_id(0)
    rows = y_ref.shape[0]
    f32 = jnp.float32
    z = b_ref[...].astype(f32) * x_ref[...].astype(f32)
    before = bp_ref[...].astype(f32) * xp_ref[...].astype(f32)
    _fill(z_s, jnp.where(i > 0, before, 0.0), z)
    w = w_ref[...]
    conv = (w[0:1] * z_s[HALO - 2:HALO - 2 + rows]
            + w[1:2] * z_s[HALO - 1:HALO - 1 + rows] + w[2:3] * z)
    y_ref[...] = (c_ref[...].astype(f32) * conv).astype(y_ref.dtype)


def _bwd_kernel(b_ref, c_ref, x_ref, g_ref, bp_ref, xp_ref, gn_ref, cn_ref,
                w_ref, d_ref, dw_ref, z_s, dc_s, out_s):
    """Grid ``(row tiles, column tiles, 3)``: the last axis walks the three
    thirds of ``d[B | C | x]``; its first step computes all three into
    ``out_s`` (and ``dw``), each step writes its own third.  The operands'
    blocks do not change along that axis, so they are read once."""
    i, part = pl.program_id(0), pl.program_id(2)
    last = pl.num_programs(0) - 1
    rows = d_ref.shape[0]
    f32 = jnp.float32

    @pl.when(part == 0)
    def _():
        b, x = b_ref[...].astype(f32), x_ref[...].astype(f32)
        c, g = c_ref[...].astype(f32), g_ref[...].astype(f32)
        w = w_ref[...]
        z = b * x
        before = bp_ref[...].astype(f32) * xp_ref[...].astype(f32)
        _fill(z_s, jnp.where(i > 0, before, 0.0), z)
        z1 = z_s[HALO - 1:HALO - 1 + rows]
        z2 = z_s[HALO - 2:HALO - 2 + rows]
        conv = w[0:1] * z2 + w[1:2] * z1 + w[2:3] * z
        dc = g * c
        after = gn_ref[...].astype(f32) * cn_ref[...].astype(f32)
        # [dc; the next tile's first rows]: dz_t reads dc_{t+1}, dc_{t+2}
        dc_s[:rows] = dc
        dc_s[rows:] = jnp.where(i < last, after, 0.0)
        dz = (w[2:3] * dc + w[1:2] * dc_s[1:1 + rows]
              + w[0:1] * dc_s[2:2 + rows])
        out_s[0] = dz * x
        out_s[1] = g * conv
        out_s[2] = dz * b
        for tap, zk in enumerate((z2, z1, z)):
            dw_ref[0, tap:tap + 1] = jnp.sum(dc * zk, 0, keepdims=True)

    d_ref[...] = out_s[part].astype(d_ref.dtype)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------


def _views(t, rows, cols, n_cols):
    """Block views at grid step ``(i, j, ...)`` of a ``(T, k D)`` operand:
    row tile ``i`` and column tile ``j`` of its third ``k``, and the HALO
    rows before and after that tile (clamped at the ends, where the kernels
    read zeros in their place)."""
    per, halos = rows // HALO, t // HALO

    def third(k):
        return pl.BlockSpec((rows, cols),
                            lambda i, j, *_: (i, k * n_cols + j))

    def before(k):
        return pl.BlockSpec((HALO, cols), lambda i, j, *_: (
            jnp.maximum(i * per - 1, 0), k * n_cols + j))

    def after(k):
        return pl.BlockSpec((HALO, cols), lambda i, j, *_: (
            jnp.minimum((i + 1) * per, halos - 1), k * n_cols + j))

    return third, before, after


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_call(bcx, w, *, interpret):
    t, d3 = bcx.shape
    d = d3 // TAPS
    rows, cols = _tiles(t, d)
    n_cols = d // cols
    third, before, _ = _views(t, rows, cols, n_cols)
    w_spec = pl.BlockSpec((TAPS, cols), lambda i, j: (0, j))
    return pl.pallas_call(
        _fwd_kernel,
        name="short_conv_fwd",
        grid=(t // rows, n_cols),
        in_specs=[third(0), third(1), third(2), before(0), before(2),
                  w_spec],
        out_specs=pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, d), bcx.dtype, vma=_vma(bcx)),
        scratch_shapes=[pltpu.VMEM((HALO + rows, cols), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(interpret, ("parallel", "parallel")),
    )(bcx, bcx, bcx, bcx, bcx, w)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(bcx, w, g, *, interpret):
    t, d3 = bcx.shape
    d = d3 // TAPS
    rows, cols = _tiles(t, d)
    n_rows, n_cols = t // rows, d // cols
    third, before, after = _views(t, rows, cols, n_cols)
    d_bcx, dw = pl.pallas_call(
        _bwd_kernel,
        name="short_conv_bwd",
        grid=(n_rows, n_cols, TAPS),
        # g is (T, D): its "third 0" is its tile
        in_specs=[third(0), third(1), third(2), third(0), before(0),
                  before(2), after(0), after(1),
                  pl.BlockSpec((TAPS, cols), lambda i, j, p: (0, j))],
        out_specs=(
            pl.BlockSpec((rows, cols), lambda i, j, p: (i, p * n_cols + j)),
            pl.BlockSpec((1, TAPS, cols), lambda i, j, p: (i, 0, j))),
        out_shape=(
            jax.ShapeDtypeStruct((t, d3), bcx.dtype, vma=_vma(bcx)),
            jax.ShapeDtypeStruct((n_rows, TAPS, d), jnp.float32,
                                 vma=_vma(bcx))),
        scratch_shapes=[pltpu.VMEM((HALO + rows, cols), jnp.float32),
                        pltpu.VMEM((rows + HALO, cols), jnp.float32),
                        pltpu.VMEM((TAPS, rows, cols), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(interpret,
                                ("parallel", "parallel", "arbitrary")),
    )(bcx, bcx, bcx, g, bcx, bcx, g, bcx, w)
    return d_bcx, jnp.sum(dw, 0)


# ---------------------------------------------------------------------------
# the jnp path and the differentiable operator
# ---------------------------------------------------------------------------


def _shifted(z, k):
    """``z`` moved ``k`` positions later along axis 0, zeros in front."""
    return jnp.pad(z[:z.shape[0] - k], ((k, 0), (0, 0)))


def _jnp(bcx, w):
    b, c, x = (a.astype(jnp.float32) for a in jnp.split(bcx, TAPS, -1))
    z = b * x
    conv = w[0] * _shifted(z, 2) + w[1] * _shifted(z, 1) + w[2] * z
    return (c * conv).astype(bcx.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _short_conv(bcx, w, interpret):
    return _fwd_call(bcx, w, interpret=interpret)


def _short_conv_fwd(bcx, w, interpret):
    return _fwd_call(bcx, w, interpret=interpret), (bcx, w)


def _short_conv_bwd(interpret, res, g):
    bcx, w = res
    d_bcx, dw = _bwd_call(bcx, w, g.astype(bcx.dtype), interpret=interpret)
    return d_bcx, dw.astype(w.dtype)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv(bcx, w, *, interpret: bool = False, force_jnp: bool = False):
    """``C * conv3(B * x)`` of ``bcx = [B | C | x]`` (``(T, 3D)``, the
    in-projection's output) with the depthwise causal taps ``w`` (``(3,
    D)`` float32, ``w[2]`` on the current position): ``(T, D)`` in
    ``bcx``'s dtype.  Differentiable in ``bcx`` and ``w`` on every path
    (kernels ``short_conv_fwd`` and ``short_conv_bwd`` on a TPU or under
    ``interpret``)."""
    if bcx.ndim != 2 or bcx.shape[1] % TAPS or w.shape != (
            TAPS, bcx.shape[1] // TAPS):
        raise ValueError(f"bcx {bcx.shape} and w {w.shape} are not "
                         f"(T, 3D) and ({TAPS}, D)")
    if _use_kernel(interpret, force_jnp):
        return _short_conv(bcx, w, interpret)
    return _jnp(bcx, w)
