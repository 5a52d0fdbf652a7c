"""Flash-attention block partials — the ring-attention hot op, in Pallas.

One ring-attention step computes attention of the local queries against one
rotating K/V block (mpi4jax_tpu/attention.py).  The Pallas kernel
fuses score computation, masking, and the streaming-softmax partials for one
(batch, head) pair entirely in VMEM — the (Tq, Tk) score matrix never
touches HBM (XLA materializes it between the einsum and the softmax in the
fallback path).

Outputs are *partials* in the standard flash/log-sum-exp form, merged across
ring steps by the caller:

    m      = rowmax(scores)                      (B, H, Tq)
    l      = rowsum(exp(scores - m))             (B, H, Tq)
    o_part = exp(scores - m) @ V                 (B, Tq, H, D)

``flash_block_partials`` runs the kernel when the process's backend is a
TPU and an identical-math jnp path on other platforms (or under
``force_jnp=True``); interpret mode covers CPU testing
(tests/test_kernels.py; the jnp/kernel equality, fully- and
partially-masked rows, and the blockwise-merge invariant).  On a TPU the
kernel is what runs: nothing catches a failed Mosaic compile and carries
on with the jnp path.

Every kernel streams (512 x 512) tiles with the other side's tile fixed
per grid step, so the live score tile is fixed-size for ANY length — the
VMEM ceiling is the K/V residency, ~2·Tk·D·itemsize under the 100 MB scoped
limit (roughly half that with a user mask, whose (bq, Tk_pad) block is also
VMEM-resident), not the Tk² of a materialized score matrix.

**Which tiles are masked** (``_tile_runs``): a tile pays for positions and
a select only where some score in it can be hidden — every tile under a
user mask, the diagonal tile of a causal call, and a ragged last tile of
the side a kernel walks.  ``causal=True`` skips fully-masked tiles
outright (~2x less MXU work than masking a computed square), so of a
causal call's n(n+1)/2 tiles a (batch, head) pair the n diagonal ones are
masked and the n(n-1)/2 below them run the softmax's own arithmetic alone
(``causal_tile_counts``).  The backward kernels take the scale out of the
tile as well — the fixed side's block is scaled once a grid step — and the
dk/dv kernel computes its score tile transposed (``k qᵀ``), so no tile is
transposed on its way to the MXU.

End-to-end, the causal ring (mpi4jax_tpu/attention.py) skips
fully-masked ring steps per rank (lax.cond) and drops masking on fully-
visible blocks, so total causal FLOPs are n(n+1)/2 blocks instead of n^2.

Kernel time on one TPU v5 lite chip (jax 0.9.0), each kernel alone, a
causal call over 32 query heads in bf16: at 16384 tokens on 8 K/V heads of
width 64, the forward 19.95 ms, ``_bwd_dq`` 20.43 ms, ``_bwd_dkv``
28.02 ms; at 8192 tokens and widths 192 (q/k) / 128 (v), 6.77 / 8.91 /
10.90 ms.  Inside a training step's trace the calls read the same to
within 0.1 ms.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_Q_TILE = 512  # query rows per grid step (keeps the score tile VMEM-sized)
# keys per streaming tile in the non-causal kernel: keeps the live score
# tile at 1 MB f32 for ANY Tk
_K_TILE = 512


def _use_kernel(interpret: bool, force_jnp: bool) -> bool:
    """The Pallas kernel on a TPU backend (or in interpret mode on
    request); the jnp path is the implementation for every other
    platform, not a fallback from a failed kernel."""
    return not force_jnp and (interpret or jax.default_backend() == "tpu")


def _tiles(tq, tk, causal):
    """The query and key tile sizes ``(bq, bk)`` of every kernel."""
    bq = _Q_TILE if tq > _Q_TILE else tq  # partial final tiles are fine
    bk = bq if causal else (_K_TILE if tk > _K_TILE else tk)
    return bq, bk


def _tile_runs(i, n, *, causal, ragged, masked, over_queries=False):
    """The tiles grid step ``i`` walks, as ``(start, count, needs_mask)``
    runs in walking order: ``n`` key tiles against query tile ``i`` (the
    forward and dq kernels), or with ``over_queries`` ``n`` query tiles
    against key tile ``i`` (the dk/dv kernel).  ``ragged``: the walked
    side's last tile is partial.  A tile needs the mask where a score in it
    can be hidden: every tile under a user mask; causal, the diagonal tile
    (tiles past it are fully hidden and skipped); and a ragged last tile.
    The fixed side's ragged tile needs none: its padded rows reach only
    output rows that are cut off.  ``i`` is a grid index inside a kernel
    and a Python int when counting (``causal_tile_counts``)."""
    if masked:
        return [(0, n, True)]
    last = n - 1 if ragged else n
    if not causal:
        return [(0, last, False)] + ([(last, 1, True)] if ragged else [])
    if not over_queries:  # a ragged last key tile is only ever a diagonal
        return [(0, i, False), (i, 1, True)]
    runs = [(i, 1, True), (i + 1, last - 1 - i, False)]
    if ragged:  # the ragged last query tile, where it is not the diagonal
        runs.append((last, jnp.minimum(last - i, 1), True))
    return runs


def causal_tile_counts(t):
    """``{kernel: (unmasked, masked)}``: the tiles each kernel of a causal
    call over ``t`` positions computes for one (batch, head) pair, without
    and with the position mask, counted from the runs the kernels walk."""
    bq, _ = _tiles(t, t, True)
    n = -(-t // bq)
    counts = {}
    for kernel, over_queries in (("fwd", False), ("bwd_dq", False),
                                 ("bwd_dkv", True)):
        tally = [0, 0]
        for i in range(n):
            for _, count, needs_mask in _tile_runs(
                    i, n, causal=True, ragged=t % bq != 0, masked=False,
                    over_queries=over_queries):
                tally[needs_mask] += max(0, int(count))
        counts[kernel] = tuple(tally)
    return counts


def _walk(runs, tile, carry):
    """Fold ``tile(t, carry, needs_mask=...)`` over the runs' tiles; a run
    of one tile is straight-line code, not a loop."""
    for start, count, needs_mask in runs:
        body = functools.partial(tile, needs_mask=needs_mask)
        if isinstance(count, int) and count == 1:
            carry = body(start, carry)
        else:
            carry = jax.lax.fori_loop(start, start + count, body, carry)
    return carry


def _at(t, size, n):
    """Tile ``t`` of ``n`` tiles of ``size`` along a block's rows or lanes:
    the whole block where there is one tile, else an offset Mosaic can
    prove a multiple of the tile (a lane offset must be one of 128, and a
    tile is 512 wherever there are several)."""
    if n == 1:
        return slice(None)
    return pl.dslice(pl.multiple_of(t * size, size), size)


def _visible(q0, k0, shape, *, q_axis, causal, q_end=None, k_end=None,
             mask=None):
    """Which scores of a tile whose first query is ``q0`` and first key
    ``k0`` (queries along ``q_axis``) are visible: those the user ``mask``
    tile shows, where given; causal, no key after its query; and no query
    at or past ``q_end`` nor key at or past ``k_end``, where given (a
    ragged tile's padding)."""
    def pos(start, axis):
        return start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    valid = [] if mask is None else [mask]
    if causal:
        valid.append(pos(q0, q_axis) >= pos(k0, 1 - q_axis))
    if q_end is not None:
        valid.append(pos(q0, q_axis) < q_end)
    if k_end is not None:
        valid.append(pos(k0, 1 - q_axis) < k_end)
    return functools.reduce(jnp.logical_and, valid)


def _nt(a, b):
    """``a @ b.T`` in float32, with no transpose of ``b``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _merge_tile(carry, s, vv):
    """Fold one (bq, bk) score tile into the online-softmax carry
    ``(m, l, acc)`` — the streaming kernel's rescale step.  Masked entries
    must already carry ``-inf`` in ``s``: ``exp`` makes them 0 against the
    finite ``mt_safe``."""
    m0, l0, acc0 = carry
    mt = jnp.maximum(m0, s.max(axis=-1))
    # fully-masked-so-far rows: exp against a 0 stand-in, p stays 0
    mt_safe = jnp.where(jnp.isinf(mt), 0.0, mt)
    p = jnp.exp(s - mt_safe[:, None])
    c = jnp.where(jnp.isinf(m0), 0.0, jnp.exp(m0 - mt_safe))
    l1 = l0 * c + p.sum(axis=-1)
    acc1 = acc0 * c[:, None] + jnp.dot(
        p.astype(vv.dtype), vv, preferred_element_type=jnp.float32
    )
    return mt, l1, acc1


def _carry_init(bq, d):
    return (
        jnp.full((bq,), -jnp.inf, jnp.float32),
        jnp.zeros((bq,), jnp.float32),
        jnp.zeros((bq, d), jnp.float32),
    )


def _pad_to(x, axis, target):
    if x.shape[axis] == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad)


def _kv_head(group):
    """The K/V row of the flattened ``(B*Hkv, T, D)`` layout that query row
    ``i`` of ``(B*H, T, D)`` reads: ``i // group`` (query heads ``h`` of a
    batch read K/V head ``h // group``; with one K/V head a query head,
    ``i`` itself)."""
    if group == 1:
        return lambda i: i
    return lambda i: i // group


def _kernel(*refs, bq, bk, tk, n_kt, causal, has_mask):
    # One (batch*head, q-tile) grid step: q (1, Bq, D), k/v whole
    # (1, Tk_pad, D), [mask (Bq, Tk_pad) — absent when unmasked],
    # o (1, Bq, D), m/l (1, 1, Bq).
    # The walk over (Bq, bk) KEY TILES carries the online softmax, so the
    # live score tile is a fixed (Bq, bk) regardless of Tk; causal, it
    # stops at the diagonal tile (key tiles past it are fully masked).
    # Mosaic tiling requires the last two block dims be (8, 128)-divisible
    # or span the whole array — hence the flattened (B*H, T, D) layout
    # (a (1, Tq, 1, D) block over (B, Tq, H, D) is not lowerable).
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        mask_ref = None
    qi = pl.program_id(1)
    q = q_ref[0]
    dv = v_ref.shape[-1]
    k_end = tk if tk != n_kt * bk else None  # padded keys never attend

    def tile(kt, carry, needs_mask):
        keys = _at(kt, bk, n_kt)
        s = jnp.dot(q, k_ref[0, keys, :].T,
                    preferred_element_type=jnp.float32)
        if needs_mask:
            s = jnp.where(_visible(
                qi * bq, kt * bk, (bq, bk), q_axis=0, causal=causal,
                k_end=k_end,
                mask=None if mask_ref is None else mask_ref[:, keys]),
                s, -jnp.inf)
        return _merge_tile(carry, s, v_ref[0, keys, :])

    m, l, acc = _walk(
        _tile_runs(qi, n_kt, causal=causal, ragged=k_end is not None,
                   masked=has_mask),
        tile, _carry_init(bq, dv))
    o_ref[0] = acc.astype(o_ref.dtype)
    m_ref[0, 0] = m
    l_ref[0, 0] = l


def _partials_impl(q, k, v, mask, scale, causal, interpret, force_jnp,
                   name="flash_attention"):
    """Forward partials — see ``flash_block_partials`` for the contract."""
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    group = h // k.shape[2]

    if not _use_kernel(interpret, force_jnp):
        if group > 1:
            k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        if causal:
            mask = jnp.tril(jnp.ones((tq, tk), bool))
        # scores/partials in f32, matching the kernel's accumulators, so
        # the two paths agree for sub-f32 inputs too
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * jnp.float32(scale)
        if mask is not None:
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m = s.max(axis=-1)
        m_safe = jnp.where(jnp.isinf(m), 0.0, m) if mask is not None else m
        p = jnp.exp(s - m_safe[..., None])
        if mask is not None:
            p = jnp.where(mask[None, None], p, 0.0)
        l = p.sum(axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        return o.astype(q.dtype), m, l

    qs = q * jnp.asarray(scale, q.dtype)

    # flatten to the (B*H, T, D) flash layout (see _kernel); the kernel
    # walks (bq, bk) key tiles, so the live score tile is fixed-size and the
    # VMEM ceiling is set by the K/V residency (~2·Tk·D·itemsize), not Tk²
    def to_bht(x, t):
        return jnp.moveaxis(x, 2, 1).reshape(b * x.shape[2], t, x.shape[-1])

    kv_head = _kv_head(group)

    bq, bk = _tiles(tq, tk, causal)
    n_kt = (tk + bk - 1) // bk
    tk_pad = n_kt * bk
    grid = (b * h, (tq + bq - 1) // bq)
    # under shard_map with VMA checking (ring attention on a mesh) the
    # outputs must be typed varying over the same axes as the inputs
    vma = frozenset(getattr(jax.typeof(q), "vma", frozenset()))
    out_shapes = (
        jax.ShapeDtypeStruct((b * h, tq, dv), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32, vma=vma),
        jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32, vma=vma),
    )
    q_spec = pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, tk_pad, d), lambda i, j: (kv_head(i), 0, 0),
                          memory_space=pltpu.VMEM)
    v_spec = pl.BlockSpec((1, tk_pad, dv), lambda i, j: (kv_head(i), 0, 0),
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, bq, dv), lambda i, j: (i, j, 0),
                          memory_space=pltpu.VMEM)
    ml_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j),
                           memory_space=pltpu.VMEM)
    in_specs = [q_spec, k_spec, v_spec]
    # pad K/V to a whole number of key tiles: pl.dslice would CLAMP the
    # last tile's start otherwise, silently misaligning the positional
    # masks; padded keys sit at kpos >= tk, which the masked tile discards
    kf = _pad_to(to_bht(k, tk), 1, tk_pad)
    vf = _pad_to(to_bht(v, tk), 1, tk_pad)
    operands = [to_bht(qs, tq), kf, vf]
    if mask is not None:
        in_specs.append(
            pl.BlockSpec((bq, tk_pad), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM)
        )
        operands.append(_pad_to(mask, 1, tk_pad))
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, tk=tk, n_kt=n_kt, causal=causal,
        has_mask=mask is not None,
    )
    o_bht, m_f, l_f = pl.pallas_call(
        kernel,
        name=name + "_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=(o_spec, ml_spec, ml_spec),
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=(
            None if interpret else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024
            )
        ),
    )(*operands)
    o = jnp.moveaxis(o_bht.reshape(b, h, tq, dv), 1, 2)
    m = m_f.reshape(b, h, tq)
    l = l_f.reshape(b, h, tq)
    return o, m, l


# ---------------------------------------------------------------------------
# backward (custom VJP)
# ---------------------------------------------------------------------------
#
# The partials map f(q, k, v) = (o_part, m, l) gets a blockwise custom VJP so
# `jax.grad` composes with the Pallas forward ON TPU (the kernel has no
# transpose rule of its own; before round 5 grads only worked on the CPU/jnp
# fallback).  The backward recomputes p = exp(s - m) tile-by-tile from the
# (q, k, v, m) residuals — the (Tq, Tk) score matrix never materializes in
# HBM, mirroring the forward — and applies, with s = scale * q k^T,
#
#     dp = g_o @ v^T + g_l          ds = p * dp
#     dq = scale * ds @ k           dk = scale * ds^T @ q
#     dv = p^T @ g_o
#
# The scale is taken out of the tiles: each kernel scales its fixed side's
# block once a grid step (q for dq, k for dk/dv), so s leaves the MXU
# scaled, and its (bq, d) or (bk, d) result once at the end.  The dk/dv
# kernel walks the transposed tiles (p^T = exp(k q^T * scale - m), m and
# g_l as lane rows), so both its products take a tile as it is.
#
# Measured on the v5e chip at (B=4, T=4096, H=8, D=128, f32), interleaved
# 15-iteration fori_loop amortization: full grad (fwd + both backward
# kernels) costs ~2.4x the forward alone non-causal (16.5 vs 7.0 ms/iter
# in one session) — consistent with the backward's ~2.5x matmul FLOPs (5
# tile dots vs the forward's 2) — and ~1.6x causal (12.8 vs 8.1 ms),
# where both backward kernels inherit the key-tile skipping via their
# loop bounds.  Session-band caveats as in the module docstring.
#
# Stabilizer semantics: `m` is treated as `stop_gradient` — its incoming
# cotangent is DROPPED.  This is exact for every numerically sane consumer:
# the downstream combination (merge_partials chains + the final `acc / l`
# normalization) is invariant to the stabilizer (shifting m while rescaling
# o_part and l by exp(m - m') leaves the result unchanged), so the composed
# gradient equals JAX's argmax-routed gradient of the jnp path in exact
# arithmetic.  Differentiating a function of `m` *alone* (e.g. `sum(m)`) is
# outside the contract and returns zero.


def _bwd_dq_kernel(*refs, scale, causal, bq, bk, tk, n_kt, has_mask):
    # grid step (i, qj): q/g_o tiles (1, bq, d), m/g_l (1, 1, bq),
    # k/v whole (1, tk_pad, d), [mask (bq, tk_pad)], out dq (1, bq, d).
    if has_mask:
        q_ref, k_ref, v_ref, m_ref, gl_ref, go_ref, mask_ref, dq_ref = refs
    else:
        q_ref, k_ref, v_ref, m_ref, gl_ref, go_ref, dq_ref = refs
        mask_ref = None
    qj = pl.program_id(1)
    qs = q_ref[0].astype(jnp.float32) * scale
    go = go_ref[0].astype(jnp.float32)
    m = m_ref[0, 0]
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)[:, None]
    gl = gl_ref[0, 0][:, None]
    d = qs.shape[-1]  # the q/k width; v and g_o may be narrower
    k_end = tk if tk != n_kt * bk else None

    def tile(kt, acc, needs_mask):
        keys = _at(kt, bk, n_kt)
        kk = k_ref[0, keys, :].astype(jnp.float32)
        vv = v_ref[0, keys, :].astype(jnp.float32)
        p = jnp.exp(_nt(qs, kk) - m_safe)
        if needs_mask:
            p = jnp.where(_visible(
                qj * bq, kt * bk, (bq, bk), q_axis=0, causal=causal,
                k_end=k_end,
                mask=None if mask_ref is None else mask_ref[:, keys]),
                p, 0.0)
        ds = p * (_nt(go, vv) + gl)
        return acc + jnp.dot(ds, kk, preferred_element_type=jnp.float32)

    acc = _walk(
        _tile_runs(qj, n_kt, causal=causal, ragged=k_end is not None,
                   masked=has_mask),
        tile, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, tq, n_qt, has_mask,
                    group=1):
    # grid step (i, kj): k/v tiles (1, bk, d), q/g_o whole (1, tq_pad, d),
    # m/g_l whole lane rows (1, 1, tq_pad), [mask transposed (bk, tq_pad)],
    # out dk/dv (1, bk, d).  Every tile is computed transposed, (bk, bq):
    # keys on the sublanes, queries on the lanes, as m and g_l lie.
    # With grouped K/V (``group`` query heads a K/V head) a third grid axis
    # walks the group's query heads; dk/dv (float32) sum over it.
    if has_mask:
        (q_ref, k_ref, v_ref, m_ref, gl_ref, go_ref, mask_ref,
         dk_ref, dv_ref) = refs
    else:
        q_ref, k_ref, v_ref, m_ref, gl_ref, go_ref, dk_ref, dv_ref = refs
        mask_ref = None
    kj = pl.program_id(1)
    ks = k_ref[0].astype(jnp.float32) * scale
    vv = v_ref[0].astype(jnp.float32)
    d, dv = ks.shape[-1], vv.shape[-1]
    q_end = tq if tq != n_qt * bq else None

    def tile(qt, carry, needs_mask):
        dk_acc, dv_acc = carry
        queries = _at(qt, bq, n_qt)
        q = q_ref[0, queries, :].astype(jnp.float32)
        go = go_ref[0, queries, :].astype(jnp.float32)
        m = m_ref[0, :, queries]
        p_t = jnp.exp(_nt(ks, q) - jnp.where(jnp.isinf(m), 0.0, m))
        if needs_mask:
            p_t = jnp.where(_visible(
                qt * bq, kj * bk, (bk, bq), q_axis=1, causal=causal,
                q_end=q_end,
                mask=None if mask_ref is None else mask_ref[:, queries]),
                p_t, 0.0)
        ds_t = p_t * (_nt(vv, go) + gl_ref[0, :, queries])
        return (
            dk_acc + jnp.dot(ds_t, q, preferred_element_type=jnp.float32),
            dv_acc + jnp.dot(p_t, go, preferred_element_type=jnp.float32),
        )

    zeros = jnp.zeros((bk, d), jnp.float32)
    dv_zeros = zeros if dv == d else jnp.zeros((bk, dv), jnp.float32)
    dk, dv = _walk(
        _tile_runs(kj, n_qt, causal=causal, ragged=q_end is not None,
                   masked=has_mask, over_queries=True),
        tile, (zeros, dv_zeros))
    dk = dk * scale
    if group == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return
    member = pl.program_id(2)

    @pl.when(member == 0)
    def _():
        dk_ref[0] = dk
        dv_ref[0] = dv

    @pl.when(member > 0)
    def _():
        dk_ref[0] += dk
        dv_ref[0] += dv


def _partials_bwd_impl(q, k, v, mask, m, g_o, g_l, scale, causal, interpret,
                       name="flash_attention"):
    b, tq, h, d = q.shape
    tk, dv, hk = k.shape[1], v.shape[-1], k.shape[2]
    group = h // hk
    bq, bk = _tiles(tq, tk, causal)
    n_qt = (tq + bq - 1) // bq
    n_kt = (tk + bk - 1) // bk
    tq_pad, tk_pad = n_qt * bq, n_kt * bk

    def to_bht(x, t, tp):
        return _pad_to(jnp.moveaxis(x, 2, 1).reshape(
            b * x.shape[2], t, x.shape[-1]), 1, tp)

    kv_head = _kv_head(group)

    qf = to_bht(q, tq, tq_pad)
    gof = to_bht(g_o, tq, tq_pad)
    kf = to_bht(k, tk, tk_pad)
    vf = to_bht(v, tk, tk_pad)
    # padded m rows are 0 (finite): their p is finite garbage, but padded
    # g_o/g_l rows are 0 so every contribution they touch is 0, and the
    # masked tiles' position guards zero them in dk/dv anyway
    mf = _pad_to(m.reshape(b * h, 1, tq), 2, tq_pad)
    glf = _pad_to(g_l.reshape(b * h, 1, tq), 2, tq_pad)
    maskf = None
    if mask is not None:
        maskf = _pad_to(_pad_to(mask, 0, tq_pad), 1, tk_pad)

    vma = frozenset(getattr(jax.typeof(q), "vma", frozenset()))
    def tile_spec(rows, width):
        return pl.BlockSpec((1, rows, width), lambda i, j: (i, j, 0),
                            memory_space=pltpu.VMEM)

    def whole_spec(rows, width, row=lambda i: i):
        return pl.BlockSpec((1, rows, width), lambda i, j: (row(i), 0, 0),
                            memory_space=pltpu.VMEM)

    mtile_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j),
                              memory_space=pltpu.VMEM)
    params = (
        None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        )
    )

    # dq: one grid step per (batch*head, query tile), walk over key tiles
    dq_in_specs = [tile_spec(bq, d), whole_spec(tk_pad, d, kv_head),
                   whole_spec(tk_pad, dv, kv_head), mtile_spec, mtile_spec,
                   tile_spec(bq, dv)]
    dq_operands = [qf, kf, vf, mf, glf, gof]
    if maskf is not None:
        dq_in_specs.append(
            pl.BlockSpec((bq, tk_pad), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM)
        )
        dq_operands.append(maskf)
    dq_f = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
            tk=tk, n_kt=n_kt, has_mask=maskf is not None,
        ),
        name=name + "_bwd_dq",
        grid=(b * h, n_qt),
        in_specs=dq_in_specs,
        out_specs=tile_spec(bq, d),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_pad, d), q.dtype, vma=vma),
        interpret=interpret,
        compiler_params=params,
    )(*dq_operands)

    # dk/dv: one grid step per (batch*head, key tile), walk over query
    # tiles, with m/g_l whole as lane rows and the mask transposed, so a
    # tile of each is the (bk, bq) tile's own layout
    mrow_spec = whole_spec(1, tq_pad)
    dkv_in_specs = [whole_spec(tq_pad, d), tile_spec(bk, d),
                    tile_spec(bk, dv), mrow_spec, mrow_spec,
                    whole_spec(tq_pad, dv)]
    dkv_operands = [qf, kf, vf, mf, glf, gof]
    if maskf is not None:
        dkv_in_specs.append(
            pl.BlockSpec((bk, tq_pad), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM)
        )
        dkv_operands.append(maskf.T)
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
        tq=tq, n_qt=n_qt, has_mask=maskf is not None,
    )
    if group == 1:
        dk_f, dv_f = pl.pallas_call(
            dkv_kernel,
            name=name + "_bwd_dkv",
            grid=(b * h, n_kt),
            in_specs=dkv_in_specs,
            out_specs=(tile_spec(bk, d), tile_spec(bk, dv)),
            out_shape=(
                jax.ShapeDtypeStruct((b * h, tk_pad, d), k.dtype, vma=vma),
                jax.ShapeDtypeStruct((b * h, tk_pad, dv), v.dtype, vma=vma),
            ),
            interpret=interpret,
            compiler_params=params,
        )(*dkv_operands)
    else:
        dk_f, dv_f = _grouped_dkv(
            dkv_kernel, dkv_in_specs, dkv_operands, group=group, rows=b * hk,
            n_kt=n_kt, bk=bk, shapes=((tk_pad, d), (tk_pad, dv)), vma=vma,
            name=name, interpret=interpret, params=params)
        dk_f, dv_f = dk_f.astype(k.dtype), dv_f.astype(v.dtype)

    def from_bht(x, t):
        return jnp.moveaxis(
            x[:, :t].reshape(b, x.shape[0] // b, t, x.shape[-1]), 1, 2)

    return from_bht(dq_f, tq), from_bht(dk_f, tk), from_bht(dv_f, tk)


def _grouped_dkv(kernel, in_specs, operands, *, group, rows, n_kt, bk,
                 shapes, vma, name, interpret, params):
    """The dk/dv kernel over a grid of ``(B*Hkv, key tiles, group)``: K/V
    tiles by K/V head, the query-side operands (whole, by query head
    ``i * group + member``, or a mask shared by every head) by the group's
    member, dk/dv in float32 summed over the members in the output block,
    which stays put along the last axis."""
    def member(spec, by_head):
        index = spec.index_map
        if by_head:
            return pl.BlockSpec(
                spec.block_shape,
                lambda i, j, r: index(i * group + r, j),
                memory_space=pltpu.VMEM)
        return pl.BlockSpec(spec.block_shape, lambda i, j, r: index(i, j),
                            memory_space=pltpu.VMEM)

    # q, k, v, m, g_l, g_o[, mask]: k and v by K/V head, the mask shared
    by_head = (True, False, False, True, True, True, False)
    specs = [member(s, h) for s, h in zip(in_specs, by_head)]
    out_specs = tuple(
        pl.BlockSpec((1, bk, width), lambda i, j, r: (i, j, 0),
                     memory_space=pltpu.VMEM) for _, width in shapes)
    return pl.pallas_call(
        functools.partial(kernel, group=group),
        name=name + "_bwd_dkv",
        grid=(rows, n_kt, group),
        in_specs=specs,
        out_specs=out_specs,
        out_shape=tuple(
            jax.ShapeDtypeStruct((rows,) + s, jnp.float32, vma=vma)
            for s in shapes),
        interpret=interpret,
        compiler_params=params,
    )(*operands)


def partials_name(name: str) -> str:
    """The ``checkpoint_name`` of the outputs of the kernel named
    ``name``: ``jax.checkpoint_policies.save_only_these_names`` of it keeps
    them through a ``jax.checkpoint``."""
    return name + "_partials"


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _partials(scale, causal, interpret, name, q, k, v, mask):
    return _partials_impl(q, k, v, mask, scale, causal, interpret, False,
                          name)


def _partials_fwd(scale, causal, interpret, name, q, k, v, mask):
    # named so that a rematerialisation policy can keep the kernel's
    # outputs, the residual ``m`` with them (see ``flash_block_partials``)
    o, m, l = checkpoint_name(
        _partials_impl(q, k, v, mask, scale, causal, interpret, False, name),
        partials_name(name))
    return (o, m, l), (q, k, v, mask, m)


def _partials_bwd(scale, causal, interpret, name, res, cts):
    q, k, v, mask, m = res
    g_o, _g_m, g_l = cts  # g_m dropped: stop-gradient stabilizer (see above)
    dq, dk, dv = _partials_bwd_impl(
        q, k, v, mask, m, g_o, g_l, scale, causal, interpret, name
    )
    dmask = None if mask is None else np.zeros(mask.shape, jax.dtypes.float0)
    return dq, dk, dv, dmask


_partials.defvjp(_partials_fwd, _partials_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "interpret", "force_jnp", "name"),
)
def flash_block_partials(
    q,
    k,
    v,
    mask,
    *,
    scale: float,
    causal: bool = False,
    interpret: bool = False,
    force_jnp: bool = False,
    name: str = "flash_attention",
):
    """Streaming-softmax partials of ``softmax(q k^T * scale) v`` for one
    K/V block.

    ``q``: (B, Tq, H, D); ``k``: (B, Tk, Hkv, D); ``v``: (B, Tk, Hkv, Dv),
    where the value width ``Dv`` may differ from the query/key width ``D``
    (latent attention: 192 against 128) and ``Hkv`` divides ``H``: grouped
    K/V, query head ``h`` attending with K/V head ``h // (H // Hkv)``
    (the kernels read that head's K/V through their block index, with no
    copy of K/V per query head; ``Hkv == H`` builds the kernels of
    ungrouped attention unchanged); ``mask``: (Tq, Tk)
    bool, True = attend (shared across batch and heads — the ring-step
    causal mask depends only on block offsets), or ``None`` for no masking
    (skips the mask load and selects entirely).

    ``causal=True`` (requires ``mask=None`` and ``Tq == Tk``) declares the
    triangular diagonal-block pattern *structurally*, which lets the TPU
    path skip the tiles past the diagonal and mask only the diagonal ones
    (``_tile_runs``): ~2x less MXU work than masking a fully-computed
    score block.  Semantically identical to ``mask=jnp.tril(...)``.

    ``name`` prefixes the kernels' names, which the device trace shows as
    their HLO instructions: ``<name>_fwd``, ``<name>_bwd_dq``,
    ``<name>_bwd_dkv``.  On the kernel path the forward's three outputs
    carry the ``checkpoint_name`` :func:`partials_name` (``<name>_partials``):
    under ``jax.checkpoint`` with
    ``policy=jax.checkpoint_policies.save_only_these_names(...)`` of it the
    backward reads them, and the forward kernel runs once; under any other
    policy, or none, the name changes nothing.  jax keeps what it derived
    for a call at given shapes for the next trace of the same call, under
    whatever policy: a process that traces one call under two policies
    clears jax's caches (``jax.clear_caches()``) between them.

    Returns ``(o_part, m, l)`` with shapes (B, Tq, H, Dv), (B, H, Tq),
    (B, H, Tq); ``m``/``l`` are float32, ``o_part`` keeps ``q``'s dtype
    (both paths).  Rows with no attendable key get ``m = -inf``, ``l = 0``,
    ``o_part = 0``.

    **Differentiable on every backend.**  The kernel path carries a
    blockwise custom VJP (Pallas backward kernels — the score matrix never
    reaches HBM in either direction); the jnp fallback is left unwrapped,
    so it keeps JAX's full native autodiff including *forward mode*.
    Forward-mode through the kernel path is unsupported (``jax.jvp``
    raises ``TypeError`` on a ``custom_vjp`` function — same reach as the
    reference's CPU/GPU builds, where p2p forward-mode also raises).  The
    custom VJP treats the stabilizer output ``m`` as ``stop_gradient``:
    any stabilizer-invariant consumer (``merge_partials`` chains, the
    ``acc / l`` normalization — i.e. any correct use) gets exact gradients;
    differentiating ``m`` in isolation returns zero by design.
    """
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"{q.shape[2]} query heads cannot share {k.shape[2]} key and "
            f"{v.shape[2]} value heads evenly")
    if causal:
        if mask is not None:
            raise ValueError("causal=True replaces mask; pass mask=None")
        if q.shape[1] != k.shape[1]:
            raise ValueError(
                f"causal=True is the diagonal-block pattern and needs "
                f"Tq == Tk, got {q.shape[1]} vs {k.shape[1]}"
            )
    if _use_kernel(interpret, force_jnp):
        return _partials(scale, causal, interpret, name, q, k, v, mask)
    return _partials_impl(q, k, v, mask, scale, causal, interpret, force_jnp)


def merge_partials(acc, m, l, o_new, m_new, l_new):
    """Log-sum-exp merge of two partial-attention states (the flash
    combine rule); all rows stay in the (B,H,Tq)/(B,Tq,H,D) layout."""
    m_out = jnp.maximum(m, m_new)
    m_safe = jnp.where(jnp.isinf(m_out), 0.0, m_out)
    c_old = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
    c_new = jnp.where(jnp.isinf(m_new), 0.0, jnp.exp(m_new - m_safe))
    l_out = l * c_old + l_new * c_new
    to_qhd = lambda c: jnp.moveaxis(c, 1, 2)[..., None]  # noqa: E731
    acc_out = acc * to_qhd(c_old) + o_new * to_qhd(c_new)
    return acc_out, m_out, l_out
