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

Both kernels stream (bq, bk) KEY TILES with online-softmax carries, so
the live score tile is fixed-size for ANY Tk — the VMEM ceiling is the
K/V residency, ~2·Tk·D·itemsize under the 100 MB scoped limit (roughly
half that with a user mask, whose (bq, Tk_pad) block is also
VMEM-resident), not the Tk² of a materialized score matrix.

``causal=True`` → ``_kernel_causal`` SKIPS fully-masked key tiles instead
of masking computed scores (~2x less MXU work, bounded by the shared
epilogue); outputs agree with the masked path to f32 matmul precision
(f32 dots use the MXU's bf16-multiply default in both kernels).

End-to-end, the causal ring (mpi4jax_tpu/attention.py) skips
fully-masked ring steps per rank (lax.cond) and drops masking on fully-
visible blocks, so total causal FLOPs are n(n+1)/2 blocks instead of n^2.

Kernel time and throughput on the chip: not measured (the chip lane,
tests/test_tpu_compiled.py, checks values only).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
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


def _merge_tile(carry, s, vv):
    """Fold one (bq, bk) score tile into the online-softmax carry
    ``(m, l, acc)`` — the shared rescale step of both streaming kernels.
    Masked entries must already carry ``-inf`` in ``s``."""
    m0, l0, acc0 = carry
    mt = jnp.maximum(m0, s.max(axis=-1))
    # fully-masked-so-far rows: exp against a 0 stand-in, p stays 0
    mt_safe = jnp.where(jnp.isinf(mt), 0.0, mt)
    p = jnp.exp(s - mt_safe[:, None])
    p = jnp.where(jnp.isinf(s), 0.0, p)  # masked entries carry -inf
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


def _kernel(*refs, bq, bk, tk, n_kt, has_mask):
    # Non-causal streaming kernel (one (batch*head, q-tile) grid step):
    # q (1, Bq, D), k/v whole (1, Tk_pad, D), [mask (Bq, Tk_pad) — absent
    # when unmasked], o (1, Bq, D), m/l (1, 1, Bq).
    # A fori_loop walks (Bq, bk) KEY TILES with online-softmax carries, so
    # the live score tile is a fixed (Bq, bk) regardless of Tk — long
    # non-causal blocks no longer materialize a (Bq, Tk) score tile (the
    # pre-round-5 kernel did, capping Tk at ~4k before VMEM overflow).
    # Mosaic tiling requires the last two block dims be (8, 128)-divisible
    # or span the whole array — hence the flattened (B*H, T, D) layout
    # (a (1, Tq, 1, D) block over (B, Tq, H, D) is not lowerable).
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        mask_ref = None
    q = q_ref[0]
    d = q.shape[-1]

    ragged = tk != n_kt * bk

    def body(kt, carry):
        kk = k_ref[0, pl.dslice(kt * bk, bk), :]
        vv = v_ref[0, pl.dslice(kt * bk, bk), :]
        s = jnp.dot(q, kk.T, preferred_element_type=jnp.float32)
        valid = None
        if ragged:  # padded tail keys never attend
            kpos = kt * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1
            )
            valid = kpos < tk
        if mask_ref is not None:
            mt_tile = mask_ref[:, pl.dslice(kt * bk, bk)]
            valid = mt_tile if valid is None else valid & mt_tile
        if valid is not None:
            s = jnp.where(valid, s, -jnp.inf)
        return _merge_tile(carry, s, vv)

    m, l, acc = jax.lax.fori_loop(0, n_kt, body, _carry_init(bq, d))
    o_ref[0] = acc.astype(o_ref.dtype)
    m_ref[0, 0] = m
    l_ref[0, 0] = l


def _kernel_causal(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *, bq, bk, tk):
    """Causal diagonal-block kernel with KEY-TILE SKIPPING: query tile
    ``qi`` only touches key tiles ``0..qi`` — a ``fori_loop`` over the
    fully-visible tiles (no masking at all) plus one triangular-masked
    boundary tile — so the MXU does ~half the work of the
    compute-everything-then-mask kernel on a causal block.  Streaming
    (online-softmax) accumulators carry across key tiles; outputs are the
    same partials contract as ``_kernel``."""
    qi = pl.program_id(1)
    q = q_ref[0]
    d = q.shape[-1]

    def load_tile(ref, kt):
        return ref[0, pl.dslice(kt * bk, bk), :]

    def body(kt, carry):
        s = jnp.dot(q, load_tile(k_ref, kt).T,
                    preferred_element_type=jnp.float32)
        return _merge_tile(carry, s, load_tile(v_ref, kt))

    m, l, acc = jax.lax.fori_loop(0, qi, body, _carry_init(bq, d))

    # boundary tile: triangular causal mask on global positions, plus the
    # ragged-tail guard (the final tile's rows beyond tk read clamped data)
    s = jnp.dot(q, load_tile(k_ref, qi).T, preferred_element_type=jnp.float32)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = qi * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where((qpos >= kpos) & (kpos < tk), s, -jnp.inf)
    m, l, acc = _merge_tile((m, l, acc), s, load_tile(v_ref, qi))

    o_ref[0] = acc.astype(o_ref.dtype)
    m_ref[0, 0] = m
    l_ref[0, 0] = l


def _partials_impl(q, k, v, mask, scale, causal, interpret, force_jnp):
    """Forward partials — see ``flash_block_partials`` for the contract."""
    b, tq, h, d = q.shape
    tk = k.shape[1]

    if not _use_kernel(interpret, force_jnp):
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

    # flatten to the (B*H, T, D) flash layout (see _kernel); both kernels
    # walk (bq, bk) key tiles, so the live score tile is fixed-size and the
    # VMEM ceiling is set by the K/V residency (~2·Tk·D·itemsize), not Tk²
    def to_bht(x, t):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)

    bq = _Q_TILE if tq > _Q_TILE else tq  # partial final tiles are fine
    bk = bq if causal else (_K_TILE if tk > _K_TILE else tk)
    n_kt = (tk + bk - 1) // bk
    tk_pad = n_kt * bk
    grid = (b * h, (tq + bq - 1) // bq)
    # under shard_map with VMA checking (ring attention on a mesh) the
    # outputs must be typed varying over the same axes as the inputs
    vma = frozenset(getattr(jax.typeof(q), "vma", frozenset()))
    out_shapes = (
        jax.ShapeDtypeStruct((b * h, tq, d), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32, vma=vma),
        jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32, vma=vma),
    )
    q_spec = pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, tk_pad, d), lambda i, j: (i, 0, 0),
                           memory_space=pltpu.VMEM)
    ml_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j),
                           memory_space=pltpu.VMEM)
    in_specs = [q_spec, kv_spec, kv_spec]
    # pad K/V to a whole number of key tiles: pl.dslice would CLAMP the
    # last tile's start otherwise, silently misaligning the positional
    # masks; padded keys sit at kpos >= tk, which both kernels' ragged
    # guards discard
    kf = _pad_to(to_bht(k, tk), 1, tk_pad)
    vf = _pad_to(to_bht(v, tk), 1, tk_pad)
    operands = [to_bht(qs, tq), kf, vf]
    if causal:
        kernel = functools.partial(_kernel_causal, bq=bq, bk=bq, tk=tk)
    else:
        kernel = functools.partial(
            _kernel, bq=bq, bk=bk, tk=tk, n_kt=n_kt,
            has_mask=mask is not None,
        )
        if mask is not None:
            in_specs.append(
                pl.BlockSpec((bq, tk_pad), lambda i, j: (j, 0),
                             memory_space=pltpu.VMEM)
            )
            operands.append(_pad_to(mask, 1, tk_pad))
    o_bht, m_f, l_f = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=(q_spec, ml_spec, ml_spec),
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=(
            None if interpret else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024
            )
        ),
    )(*operands)
    o = jnp.moveaxis(o_bht.reshape(b, h, tq, d), 1, 2)
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
# HBM, mirroring the forward — and applies
#
#     dp = g_o @ v^T + g_l          ds = p * dp * scale
#     dq = ds @ k                   dk = ds^T @ q_scaled
#     dv = p^T @ g_o
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
    q = q_ref[0].astype(jnp.float32)
    go = go_ref[0].astype(jnp.float32)
    m = m_ref[0, 0]
    gl = gl_ref[0, 0]
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)
    d = q.shape[-1]
    qpos = qj * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(kt, acc):
        kk = k_ref[0, pl.dslice(kt * bk, bk), :].astype(jnp.float32)
        vv = v_ref[0, pl.dslice(kt * bk, bk), :].astype(jnp.float32)
        s = jnp.dot(q, kk.T, preferred_element_type=jnp.float32) * scale
        kpos = kt * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < tk
        if causal:
            valid &= qpos >= kpos
        if mask_ref is not None:
            valid &= mask_ref[:, pl.dslice(kt * bk, bk)]
        p = jnp.where(valid, jnp.exp(s - m_safe[:, None]), 0.0)
        dp = jnp.dot(go, vv.T, preferred_element_type=jnp.float32)
        dp = dp + gl[:, None]
        ds = p * dp * scale
        return acc + jnp.dot(ds, kk, preferred_element_type=jnp.float32)

    hi = qj + 1 if causal else n_kt  # causal: key tiles past qj fully masked
    acc = jax.lax.fori_loop(0, hi, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, tq, tk, n_qt, has_mask):
    # grid step (i, kj): k/v tiles (1, bk, d), q/g_o whole (1, tq_pad, d),
    # m/g_l whole (1, tq_pad, 1), [mask (tq_pad, bk)], out dk/dv (1, bk, d).
    # m/g_l arrive TRANSPOSED (query positions on the SUBLANE dim): the
    # fori_loop below slices them at qj*bq, and Mosaic requires lane-dim
    # dynamic offsets to be provable multiples of 128 — only true when bq
    # is itself a multiple of 128, and bq = min(Tq, 512) — while sublane
    # offsets only need multiples of 8 (every bq here is).
    if has_mask:
        (q_ref, k_ref, v_ref, m_ref, gl_ref, go_ref, mask_ref,
         dk_ref, dv_ref) = refs
    else:
        q_ref, k_ref, v_ref, m_ref, gl_ref, go_ref, dk_ref, dv_ref = refs
        mask_ref = None
    kj = pl.program_id(1)
    kk = k_ref[0].astype(jnp.float32)
    vv = v_ref[0].astype(jnp.float32)
    d = kk.shape[-1]
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def body(qj, carry):
        dk_acc, dv_acc = carry
        qt = q_ref[0, pl.dslice(qj * bq, bq), :].astype(jnp.float32)
        got = go_ref[0, pl.dslice(qj * bq, bq), :].astype(jnp.float32)
        mt = m_ref[0, pl.dslice(qj * bq, bq), 0]
        glt = gl_ref[0, pl.dslice(qj * bq, bq), 0]
        m_safe = jnp.where(jnp.isinf(mt), 0.0, mt)
        s = jnp.dot(qt, kk.T, preferred_element_type=jnp.float32) * scale
        qpos = qj * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        valid = (kpos < tk) & (qpos < tq)
        if causal:
            valid &= qpos >= kpos
        if mask_ref is not None:
            valid &= mask_ref[pl.dslice(qj * bq, bq), :]
        p = jnp.where(valid, jnp.exp(s - m_safe[:, None]), 0.0)
        dp = jnp.dot(got, vv.T, preferred_element_type=jnp.float32)
        dp = dp + glt[:, None]
        ds = p * dp * scale
        dk_acc = dk_acc + jnp.dot(
            ds.T, qt, preferred_element_type=jnp.float32
        )
        dv_acc = dv_acc + jnp.dot(
            p.T, got, preferred_element_type=jnp.float32
        )
        return dk_acc, dv_acc

    lo = kj if causal else 0  # causal: query tiles before kj see no key here
    zeros = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, n_qt, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _partials_bwd_impl(q, k, v, mask, m, g_o, g_l, scale, causal, interpret):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    bq = _Q_TILE if tq > _Q_TILE else tq
    bk = bq if causal else (_Q_TILE if tk > _Q_TILE else tk)
    n_qt = (tq + bq - 1) // bq
    n_kt = (tk + bk - 1) // bk
    tq_pad, tk_pad = n_qt * bq, n_kt * bk

    def to_bht(x, t, tp):
        return _pad_to(jnp.moveaxis(x, 2, 1).reshape(b * h, t, d), 1, tp)

    qf = to_bht(q, tq, tq_pad)
    gof = to_bht(g_o, tq, tq_pad)
    kf = to_bht(k, tk, tk_pad)
    vf = to_bht(v, tk, tk_pad)
    # padded m rows are 0 (finite): their p is finite garbage, but padded
    # g_o/g_l rows are 0 so every contribution they touch is 0, and the
    # qpos/kpos guards zero them in dk/dv anyway
    mf = _pad_to(m.reshape(b * h, 1, tq), 2, tq_pad)
    glf = _pad_to(g_l.reshape(b * h, 1, tq), 2, tq_pad)
    maskf = None
    if mask is not None:
        maskf = _pad_to(_pad_to(mask, 0, tq_pad), 1, tk_pad)

    vma = frozenset(getattr(jax.typeof(q), "vma", frozenset()))
    tile_spec = pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM)
    ktile_spec = pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0),
                              memory_space=pltpu.VMEM)
    qwhole_spec = pl.BlockSpec((1, tq_pad, d), lambda i, j: (i, 0, 0),
                               memory_space=pltpu.VMEM)
    kwhole_spec = pl.BlockSpec((1, tk_pad, d), lambda i, j: (i, 0, 0),
                               memory_space=pltpu.VMEM)
    mtile_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j),
                              memory_space=pltpu.VMEM)
    params = (
        None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        )
    )

    # dq: one grid step per (batch*head, query tile), loop over key tiles
    dq_in_specs = [tile_spec, kwhole_spec, kwhole_spec, mtile_spec,
                   mtile_spec, tile_spec]
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
        name="flash_attention_bwd_dq",
        grid=(b * h, n_qt),
        in_specs=dq_in_specs,
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, tq_pad, d), q.dtype, vma=vma),
        interpret=interpret,
        compiler_params=params,
    )(*dq_operands)

    # dk/dv: one grid step per (batch*head, key tile), loop over query
    # tiles.  m/g_l go in TRANSPOSED — (bh, tq_pad, 1), query positions on
    # the sublane dim — because the kernel's fori_loop slices them at
    # qj*bq and lane-dim dynamic offsets must be provable multiples of
    # 128, which only holds when bq is one (sublane offsets need 8s).
    mT_spec = pl.BlockSpec((1, tq_pad, 1), lambda i, j: (i, 0, 0),
                           memory_space=pltpu.VMEM)
    mf_t = jnp.swapaxes(mf, 1, 2)
    glf_t = jnp.swapaxes(glf, 1, 2)
    dkv_in_specs = [qwhole_spec, ktile_spec, ktile_spec, mT_spec,
                    mT_spec, qwhole_spec]
    dkv_operands = [qf, kf, vf, mf_t, glf_t, gof]
    if maskf is not None:
        dkv_in_specs.append(
            pl.BlockSpec((tq_pad, bk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM)
        )
        dkv_operands.append(maskf)
    dk_f, dv_f = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
            tq=tq, tk=tk, n_qt=n_qt, has_mask=maskf is not None,
        ),
        name="flash_attention_bwd_dkv",
        grid=(b * h, n_kt),
        in_specs=dkv_in_specs,
        out_specs=(ktile_spec, ktile_spec),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, tk_pad, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, tk_pad, d), v.dtype, vma=vma),
        ),
        interpret=interpret,
        compiler_params=params,
    )(*dkv_operands)

    def from_bht(x, t):
        return jnp.moveaxis(x[:, :t].reshape(b, h, t, d), 1, 2)

    return from_bht(dq_f, tq), from_bht(dk_f, tk), from_bht(dv_f, tk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _partials(scale, causal, interpret, q, k, v, mask):
    return _partials_impl(q, k, v, mask, scale, causal, interpret, False)


def _partials_fwd(scale, causal, interpret, q, k, v, mask):
    o, m, l = _partials_impl(q, k, v, mask, scale, causal, interpret, False)
    return (o, m, l), (q, k, v, mask, m)


def _partials_bwd(scale, causal, interpret, res, cts):
    q, k, v, mask, m = res
    g_o, _g_m, g_l = cts  # g_m dropped: stop-gradient stabilizer (see above)
    dq, dk, dv = _partials_bwd_impl(
        q, k, v, mask, m, g_o, g_l, scale, causal, interpret
    )
    dmask = None if mask is None else np.zeros(mask.shape, jax.dtypes.float0)
    return dq, dk, dv, dmask


_partials.defvjp(_partials_fwd, _partials_bwd)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "interpret", "force_jnp")
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
):
    """Streaming-softmax partials of ``softmax(q k^T * scale) v`` for one
    K/V block.

    ``q``: (B, Tq, H, D); ``k``/``v``: (B, Tk, H, D); ``mask``: (Tq, Tk)
    bool, True = attend (shared across batch and heads — the ring-step
    causal mask depends only on block offsets), or ``None`` for no masking
    (skips the mask load and selects entirely).

    ``causal=True`` (requires ``mask=None`` and ``Tq == Tk``) declares the
    triangular diagonal-block pattern *structurally*, which lets the TPU
    path use the key-tile-skipping kernel (``_kernel_causal``): ~2x less
    MXU work than masking a fully-computed score block.  Semantically
    identical to ``mask=jnp.tril(...)``.

    Returns ``(o_part, m, l)`` with shapes (B, Tq, H, D), (B, H, Tq),
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
    if causal:
        if mask is not None:
            raise ValueError("causal=True replaces mask; pass mask=None")
        if q.shape[1] != k.shape[1]:
            raise ValueError(
                f"causal=True is the diagonal-block pattern and needs "
                f"Tq == Tk, got {q.shape[1]} vs {k.shape[1]}"
            )
    if _use_kernel(interpret, force_jnp):
        return _partials(scale, causal, interpret, q, k, v, mask)
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
