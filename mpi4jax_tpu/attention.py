"""Sequence/context-parallel attention on the communication primitives.

The reference contains no sequence parallelism (SURVEY.md §5) — but its
primitive set is exactly what the standard long-context schemes are built
from.  This module implements both standard schemes TPU-natively on
mpi4jax_tpu's primitives — first-class package API, demoed by
examples/long_context_attention.py and documented in docs/long_context.md:

- **ring attention** (blockwise attention over a ``sendrecv`` ring;
  Liu et al. 2023): each rank holds a sequence shard of K/V and rotates it
  around the ring with ``shift(1)`` — one CollectivePermute per step over
  ICI — accumulating attention with a streaming (flash-style) softmax.
  Memory per chip stays O(T/n) — in the BACKWARD too: a custom VJP saves
  only rank-local residuals and re-rotates K/V during the backward, with
  dK/dV accumulators traveling the ring (see ``ring_attention``) —
  enabling sequences n× longer than one chip could hold; compute overlaps
  the permutes (XLA pipelines the unrolled steps).
  Causal runs compute only the visible blocks (fully-masked ring
  steps are skipped per rank via ``lax.cond``; fully-visible blocks skip
  masking) — n(n+1)/2 blocks of MXU work instead of n² — and the diagonal
  block uses the key-tile-skipping causal kernel (see
  kernels/flash_attention.py; neither has a reading on the chip: PERF.md).
- **Ulysses-style attention** (``alltoall`` head exchange; Jacobs et al.
  2023): two all-to-alls re-shard from sequence-parallel to head-parallel
  and back, with full-sequence local attention in between.

Both are exact (not approximations) and match single-device attention to
f32 precision — see tests/test_long_context.py.
"""

import math

import jax
import jax.numpy as jnp
from functools import partial

import mpi4jax_tpu as mpx
from .experimental import notoken
from .kernels.flash_attention import flash_block_partials, merge_partials

__all__ = [
    "flash_attention",
    "reference_attention",
    "ring_attention",
    "ulysses_attention",
]


def reference_attention(q, k, v, *, causal=False):
    """Plain full attention (B, T, H, D) — the single-device ground truth.
    ``v`` may have its own width (B, T, H, Dv); the scale is ``1/sqrt`` of
    the query/key width."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ring_attention(q, k, v, *, comm=None, causal=False,
                   memory_efficient_grad=True):
    """Exact blockwise attention over a K/V ring.

    ``q``/``k``/``v``: rank-local sequence shards ``(B, T_local, H, D)``;
    the global sequence is the rank-order concatenation.  Returns the local
    shard of the attention output.  Call inside a parallel region.

    The per-block attention partials come from
    ``mpi4jax_tpu.kernels.flash_attention``: the fused Pallas kernel on TPU
    (the (Tq, Tk) score matrix never leaves VMEM), the identical-math jnp
    path elsewhere; ``merge_partials`` is the flash combine rule across
    ring steps.

    ``memory_efficient_grad=True`` (default) gives the ring its own custom
    VJP: the forward saves only rank-LOCAL tensors plus the final softmax
    stats — O(T/n) per chip — and the backward RE-ROTATES K/V around the
    ring, accumulating dK/dV gradients that travel with their blocks (one
    extra full ring of communication; blockwise kernels throughout, so no
    score matrix materializes).  Plain reverse-mode AD through the forward
    would instead pin every rotated K/V block (plus each step's merge
    accumulator) as residuals — O(T_global) per chip, silently forfeiting
    ring attention's defining memory property exactly when sequences are
    long.  Set ``False`` to use plain AD (keeps ``jax.jvp`` forward-mode
    support, which a ``custom_vjp`` function cannot offer).
    """
    comm = comm if comm is not None else mpx.get_default_comm()
    if memory_efficient_grad:
        return _ring_attention_me(causal, comm, q, k, v)
    out, _m, _l = _ring_forward(q, k, v, comm, causal)
    return out


def _ring_forward(q, k, v, comm, causal):
    """The ring forward; returns the normalized output AND the final
    streaming-softmax stats (m, l) so the memory-efficient backward can
    reconstruct per-block probabilities without storing blocks."""
    size = comm.Get_size()
    rank = comm.Get_rank()
    b, t_loc, h, d = q.shape
    scale = 1.0 / math.sqrt(d)

    # streaming-softmax accumulators (flash-attention style)
    m = jnp.full((b, h, t_loc), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t_loc), jnp.float32)
    acc = jnp.zeros_like(q)
    # promote fresh (replicated-typed) constants so they can join the
    # varying carry (docs/sharp_bits.md)
    # pass comm explicitly: custom_vjp traces this function lazily (at
    # grad/partial-eval time), after the enclosing region context popped,
    # so the default-comm resolution would pick the wrong axes
    m, l, acc = mpx.varying((m, l, acc), comm=comm)

    k_blk, v_blk = k, v
    # static unroll: `size` steps, each one CollectivePermute + one block of
    # MXU work — XLA pipelines compute with the permutes
    for step in range(size):
        # k_blk currently holds the shard originally owned by src = rank -
        # step (mod size).  Causal block taxonomy (block granularity, exact):
        #   step == 0  (src == rank):  the diagonal block — triangular mask;
        #   step <= rank (src < rank): every key precedes every query —
        #       fully visible, compute UNMASKED (no mask load/selects);
        #   step >  rank (src > rank): every key follows every query —
        #       fully masked, skip the block's compute entirely.
        # `rank` is a traced per-device value (SPMD traces one program), so
        # the skip is a lax.cond: ranks take the identity branch at run
        # time instead of computing a block that masking would zero out.
        # This halves total causal ring FLOPs (sum over ranks: n(n+1)/2
        # useful blocks vs n^2 computed blocks before).
        if causal and step == 0:
            # diagonal block: global offsets cancel — declare the triangle
            # structurally so the TPU kernel can SKIP the fully-masked key
            # tiles (~1.7x on this block) instead of masking computed scores
            o_new, m_new, l_new = flash_block_partials(
                q, k_blk, v_blk, None, scale=scale, causal=True
            )
            acc, m, l = merge_partials(acc, m, l, o_new, m_new, l_new)
        elif causal:

            def _attend(carry, kb=k_blk, vb=v_blk):
                acc, m, l = carry
                o_new, m_new, l_new = flash_block_partials(
                    q, kb, vb, None, scale=scale
                )
                return merge_partials(acc, m, l, o_new, m_new, l_new)

            acc, m, l = jax.lax.cond(
                step <= rank, _attend, lambda carry: carry, (acc, m, l)
            )
        else:
            o_new, m_new, l_new = flash_block_partials(
                q, k_blk, v_blk, None, scale=scale
            )
            acc, m, l = merge_partials(acc, m, l, o_new, m_new, l_new)

        if step + 1 < size:
            # rotate K/V one hop around the ring (tokenless: the data
            # dependency on k_blk/v_blk already orders the permute)
            k_blk = notoken.sendrecv(k_blk, k_blk, dest=mpx.shift(1), comm=comm)
            v_blk = notoken.sendrecv(v_blk, v_blk, dest=mpx.shift(1), comm=comm)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    # merge accumulates in f32; return in the input dtype
    out = (acc / jnp.moveaxis(l_safe, 1, 2)[..., None]).astype(q.dtype)
    return out, m, l


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ring_attention_me(causal, comm, q, k, v):
    out, _m, _l = _ring_forward(q, k, v, comm, causal)
    return out


def _ring_me_fwd(causal, comm, q, k, v):
    out, m, l = _ring_forward(q, k, v, comm, causal)
    # residuals are rank-LOCAL only: O(T/n) per chip
    return out, (q, k, v, out, m, l)


def _ring_me_bwd(causal, comm, res, g):
    """Ring-attention backward with re-communication instead of residuals.

    Reconstruction: with the FINAL stabilizer ``m`` and normalizer ``l``,
    the output decomposes over blocks as

        out = (sum_b o_b * e^{m_b - m}) / l,     l = sum_b l_b * e^{m_b - m}

    where ``(o_b, m_b, l_b)`` are block partials.  The cotangents of each
    block's partials are therefore ``g_o_b = (g / l) * e^{m_b - m}`` and
    ``g_l_b = -(sum_d g*out / l) * e^{m_b - m}`` (the softmax "delta"
    term), with the stabilizer weights' own derivative dropped — exact,
    because the decomposition is invariant to every stabilizer (the same
    argument as ``flash_block_partials``'s custom VJP).  Each ring step
    recomputes one block's ``m_b`` (a forward kernel call), feeds these
    cotangents through the blockwise backward kernels (``jax.vjp`` of
    ``flash_block_partials``), and accumulates (dK_b, dV_b) into buffers
    that ROTATE WITH the block — after the full cycle of ``size`` hops
    every dK/dV lands back on its owner with all ranks' contributions.
    """
    q, k, v, out, m, l = res
    size = comm.Get_size()
    rank = comm.Get_rank()
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)

    g = g.astype(jnp.float32)
    out32 = out.astype(jnp.float32)
    # cotangents of the (acc, l) pair that produced out = acc / l
    g_acc = g / jnp.moveaxis(l_safe, 1, 2)[..., None]          # (B,T,H,D)
    delta = jnp.moveaxis((g * out32).sum(-1), 2, 1)            # (B,H,T)
    g_l = -delta / l_safe

    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dq, dk, dv = mpx.varying((dq, dk, dv), comm=comm)
    k_blk, v_blk = k, v

    for step in range(size):
        blk_causal = bool(causal and step == 0)

        def _block(kb, vb, dk_c, dv_c, blk_causal=blk_causal):
            (o_b, m_b, l_b), vjp = jax.vjp(
                lambda q_, kb_, vb_: flash_block_partials(
                    q_, kb_, vb_, None, scale=scale, causal=blk_causal
                ),
                q, kb, vb,
            )
            w = jnp.exp(m_b - m_safe)  # stabilizer reweight
            g_ob = (g_acc * jnp.moveaxis(w, 1, 2)[..., None]).astype(o_b.dtype)
            g_lb = g_l * w
            # the TRUE m_b cotangent (L depends on m_b through w): with it
            # the triple is the full chain rule, so the jnp fallback's
            # native AD is exact; the kernel path's custom VJP drops it,
            # which is equally exact by stabilizer invariance
            g_mb = w * (
                jnp.moveaxis((g_acc * o_b.astype(jnp.float32)).sum(-1), 2, 1)
                + g_l * l_b
            )
            dq_b, dk_b, dv_b = vjp((g_ob, g_mb, g_lb))
            return (dq_b.astype(jnp.float32),
                    dk_c + dk_b.astype(jnp.float32),
                    dv_c + dv_b.astype(jnp.float32))

        if causal and step > 0:
            dq_b, dk, dv = jax.lax.cond(
                step <= rank,
                _block,
                lambda kb, vb, dk_c, dv_c: (jnp.zeros_like(dq), dk_c, dv_c),
                k_blk, v_blk, dk, dv,
            )
        else:
            dq_b, dk, dv = _block(k_blk, v_blk, dk, dv)
        dq = dq + dq_b

        # rotate: dK/dV accumulators travel with their block and need the
        # FULL cycle of `size` hops to land back on the owner; K/V are
        # never read after the last step, so their final hop is elided
        # (same guard as the forward)
        if step + 1 < size:
            k_blk = notoken.sendrecv(k_blk, k_blk, dest=mpx.shift(1),
                                     comm=comm)
            v_blk = notoken.sendrecv(v_blk, v_blk, dest=mpx.shift(1),
                                     comm=comm)
        dk = notoken.sendrecv(dk, dk, dest=mpx.shift(1), comm=comm)
        dv = notoken.sendrecv(dv, dv, dest=mpx.shift(1), comm=comm)

    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention_me.defvjp(_ring_me_fwd, _ring_me_bwd)


def flash_attention(q, k, v, causal=False):
    """Single-device attention via the fused flash kernel: block partials +
    normalization, so the (T, T) score matrix never reaches HBM (the
    ``reference_attention`` einsum materializes it).  ``v`` may be narrower
    than ``q``/``k`` (latent attention's 128 against 192); the scale is
    ``1/sqrt`` of the query/key width.  Causal uses the
    key-tile-skipping kernel on TPU; non-causal streams (512, 512) key
    tiles with online-softmax carries, so the live score tile is fixed-
    size regardless of sequence length — the VMEM ceiling is the K/V
    residency (~2·T·D·itemsize, about 90k f32 tokens at D=128), not T².

    Differentiable on every backend: ``flash_block_partials`` carries a
    blockwise custom VJP (Pallas backward kernels on TPU), so gradients
    match ``reference_attention``'s without ever materializing the score
    matrix — forward or backward.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    o, _, l = flash_block_partials(q, k, v, None, scale=scale, causal=causal)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (o / jnp.moveaxis(l_safe, 1, 2)[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, *, comm=None, causal=False):
    """Exact attention via all-to-all head exchange (Ulysses).

    Input shards ``(B, T_local, H, D)`` with ``H % size == 0``: re-shard to
    ``(B, T_global, H/size, D)`` with one ``alltoall``, run full-sequence
    local flash attention on the head group (fused kernel — the global
    score matrix never hits HBM), and re-shard back.
    """
    comm = comm if comm is not None else mpx.get_default_comm()
    size = comm.Get_size()
    b, t_loc, h, d = q.shape
    if h % size != 0:
        raise ValueError(f"ulysses needs heads ({h}) divisible by ranks ({size})")
    h_loc = h // size

    def seq_to_heads(x):
        # (B, T_l, H, D) -> alltoall rows = head groups -> (B, T_g, H/size, D)
        x = x.reshape(b, t_loc, size, h_loc, d).transpose(2, 0, 1, 3, 4)
        x = notoken.alltoall(x, comm=comm)  # row i: rank i's T_l for my heads
        return x.transpose(1, 0, 2, 3, 4).reshape(b, size * t_loc, h_loc, d)

    def heads_to_seq(x):
        # (B, T_g, H/size, D) -> (B, T_l, H, D)
        x = x.reshape(b, size, t_loc, h_loc, d).transpose(1, 0, 2, 3, 4)
        x = notoken.alltoall(x, comm=comm)
        return x.transpose(1, 2, 0, 3, 4).reshape(b, t_loc, h, d)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = flash_attention(qh, kh, vh, causal)
    return heads_to_seq(out)
