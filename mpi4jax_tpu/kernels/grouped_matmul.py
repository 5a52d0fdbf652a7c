"""Grouped matrix products over the experts one chip holds, in Pallas.

The rows of ``x`` come grouped by expert in whole tiles of ``TILE_ROWS``
rows: tile ``i`` belongs to held expert ``tile_expert[i]``, and only the
first ``n_tiles`` tiles are computed (the grid is that long, a number the
device reads, so a step's work follows its routing and not the buffer's
size).  A group's last tile is padded with zero rows; every held expert
has at least one tile, so every expert's weight gradient is written.

Three kernels, each named for the device trace (``name`` prefixes them):

- ``<name>`` — ``out[i] = x[i] @ w[tile_expert[i]]`` (``(M, K) x (E, K,
  N) -> (M, N)``);
- ``<name>_dx`` — the products' input gradient, ``g[i] @ w[e].T``;
- ``<name>_dw`` — the weights' gradient, ``sum over e's tiles of x[i].T @
  g[i]``, accumulated in the output block while the expert stays the same.

Operands go to the MXU in ``compute_dtype`` (bfloat16) with float32
accumulation; the weights may be kept in float32 and are cast a block at a
time.  Rows of tiles past ``n_tiles`` are left unwritten in ``out`` and in
the input gradient: callers read only rows they placed.  Off the TPU the
same products run as a ``jax.numpy`` path of identical arithmetic (every
tile computed), as ``flash_attention.py`` does.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = 128
_VMEM_LIMIT = 100 * 1024 * 1024


def _use_kernel(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "tpu"


def _params(interpret):
    return None if interpret else pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT)


def _vma(x):
    return frozenset(getattr(jax.typeof(x), "vma", frozenset()))


def _gmm_kernel(tile_expert, n_tiles, x_ref, w_ref, o_ref, *, transpose_w,
                compute_dtype):
    del tile_expert, n_tiles
    x = x_ref[...].astype(compute_dtype)
    w = w_ref[0].astype(compute_dtype)
    contract = (1,) if transpose_w else (0,)
    dims = (((1,), contract), ((), ()))
    o_ref[...] = jax.lax.dot_general(
        x, w, dims, preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _tgmm_kernel(tile_expert, n_tiles, x_ref, g_ref, o_ref, *,
                 compute_dtype):
    del n_tiles
    i = pl.program_id(0)
    part = jax.lax.dot_general(
        x_ref[...].astype(compute_dtype), g_ref[...].astype(compute_dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    first = jnp.logical_or(
        i == 0, tile_expert[i] != tile_expert[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _():
        o_ref[0] = part.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(first))
    def _():
        o_ref[0] = (o_ref[0].astype(jnp.float32) + part).astype(o_ref.dtype)


def _tile_grid(n_tiles):
    return (n_tiles.reshape(()),)


# each kernel call is a jit of its own, so the kernel's name in the program
# is its ``name`` and not the transformation it was traced under
@functools.partial(jax.jit, static_argnames=(
    "transpose_w", "out_dtype", "name", "compute_dtype", "interpret"))
def _gmm_call(x, w, tile_expert, n_tiles, *, transpose_w, out_dtype, name,
              compute_dtype, interpret):
    m, k_in = x.shape
    n_out = w.shape[1] if transpose_w else w.shape[2]
    w_block = (1,) + w.shape[1:]
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=_tile_grid(n_tiles),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, k_in), lambda i, te, n: (i, 0)),
            pl.BlockSpec(w_block, lambda i, te, n: (te[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, n_out), lambda i, te, n: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w,
                          compute_dtype=compute_dtype),
        name=name,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((m, n_out), out_dtype, vma=_vma(x)),
        interpret=interpret,
        compiler_params=_params(interpret),
    )(tile_expert, n_tiles, x, w)


@functools.partial(jax.jit, static_argnames=(
    "groups", "out_dtype", "name", "compute_dtype", "interpret"))
def _tgmm_call(x, g, tile_expert, n_tiles, groups, *, out_dtype, name,
               compute_dtype, interpret):
    k_in, n_out = x.shape[1], g.shape[1]
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=_tile_grid(n_tiles),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, k_in), lambda i, te, n: (i, 0)),
            pl.BlockSpec((TILE_ROWS, n_out), lambda i, te, n: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, k_in, n_out),
                               lambda i, te, n: (te[i], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, compute_dtype=compute_dtype),
        name=name,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((groups, k_in, n_out), out_dtype,
                                       vma=_vma(x)),
        interpret=interpret,
        compiler_params=_params(interpret),
    )(tile_expert, n_tiles, x, g)


# ---------------------------------------------------------------------------
# the jnp path: every tile, the same arithmetic
# ---------------------------------------------------------------------------


def _tiles(a):
    return a.reshape(-1, TILE_ROWS, a.shape[-1])


def _gmm_jnp(x, w, tile_expert, *, transpose_w, out_dtype, compute_dtype):
    wt = w[tile_expert].astype(compute_dtype)
    spec = "tmk,tnk->tmn" if transpose_w else "tmk,tkn->tmn"
    out = jnp.einsum(spec, _tiles(x).astype(compute_dtype), wt,
                     preferred_element_type=jnp.float32)
    return out.reshape(x.shape[0], -1).astype(out_dtype)


def _tgmm_jnp(x, g, tile_expert, n_tiles, groups, *, out_dtype,
              compute_dtype):
    live = jnp.arange(tile_expert.shape[0]) < n_tiles.reshape(())
    parts = jnp.einsum("tmk,tmn->tkn", _tiles(x).astype(compute_dtype),
                       _tiles(g).astype(compute_dtype),
                       preferred_element_type=jnp.float32)
    parts = jnp.where(live[:, None, None], parts, 0.0)
    return jax.ops.segment_sum(parts, tile_expert,
                               num_segments=groups).astype(out_dtype)


# ---------------------------------------------------------------------------
# the differentiable product
# ---------------------------------------------------------------------------


def _forward(x, w, tile_expert, n_tiles, name, compute_dtype, interpret):
    if _use_kernel(interpret):
        return _gmm_call(x, w, tile_expert, n_tiles, transpose_w=False,
                         out_dtype=jnp.float32, name=name,
                         compute_dtype=compute_dtype, interpret=interpret)
    return _gmm_jnp(x, w, tile_expert, transpose_w=False,
                    out_dtype=jnp.float32, compute_dtype=compute_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gmm(x, w, tile_expert, n_tiles, name, compute_dtype, interpret):
    return _forward(x, w, tile_expert, n_tiles, name, compute_dtype,
                    interpret)


def _gmm_fwd(x, w, tile_expert, n_tiles, name, compute_dtype, interpret):
    out = _forward(x, w, tile_expert, n_tiles, name, compute_dtype,
                   interpret)
    return out, (x, w, tile_expert, n_tiles)


def _gmm_bwd(name, compute_dtype, interpret, res, g):
    x, w, tile_expert, n_tiles = res
    if _use_kernel(interpret):
        dx = _gmm_call(g, w, tile_expert, n_tiles, transpose_w=True,
                       out_dtype=x.dtype, name=name + "_dx",
                       compute_dtype=compute_dtype, interpret=interpret)
        dw = _tgmm_call(x, g, tile_expert, n_tiles, w.shape[0],
                        out_dtype=w.dtype, name=name + "_dw",
                        compute_dtype=compute_dtype, interpret=interpret)
    else:
        dx = _gmm_jnp(g, w, tile_expert, transpose_w=True, out_dtype=x.dtype,
                      compute_dtype=compute_dtype)
        dw = _tgmm_jnp(x, g, tile_expert, n_tiles, w.shape[0],
                       out_dtype=w.dtype, compute_dtype=compute_dtype)
    return dx, dw, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x, w, tile_expert, n_tiles, *, name="moe_gmm",
                   compute_dtype=jnp.bfloat16, interpret=False):
    """``out[rows of tile i] = x[rows of tile i] @ w[tile_expert[i]]`` for
    the first ``n_tiles`` tiles of ``TILE_ROWS`` rows, in float32.

    ``x``: ``(M, K)`` with ``M`` a multiple of ``TILE_ROWS``; ``w``: ``(E,
    K, N)``; ``tile_expert``: ``(M // TILE_ROWS,)`` int32, non-decreasing
    over the live tiles; ``n_tiles``: ``(1,)`` int32.  Differentiable in
    ``x`` and ``w`` (kernels ``<name>_dx`` and ``<name>_dw``)."""
    if x.shape[0] % TILE_ROWS:
        raise ValueError(f"rows {x.shape[0]} are not whole tiles of "
                         f"{TILE_ROWS}")
    return _gmm(x, w, tile_expert, n_tiles, name, compute_dtype, interpret)
