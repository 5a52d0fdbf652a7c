"""Pallas kernel tests: flash-attention block partials.

The kernel (``mpi4jax_tpu/kernels/flash_attention.py``) is the ring-attention
hot op — ``mpi4jax_tpu.attention.ring_attention`` calls it once
per ring step.  Interpret mode runs the actual kernel body on CPU; the
acceptance criterion is equality with the identical-math jnp path
(``force_jnp=True``), including rows with no attendable key, which must come
back as ``m = -inf``, ``l = 0``, ``o = 0`` rather than NaN.
"""

import math
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi4jax_tpu.kernels import flash_attention
from mpi4jax_tpu.kernels.flash_attention import (
    causal_tile_counts,
    flash_block_partials,
    merge_partials,
)


def _qkv(seed, b, tq, tk, h, d, dtype=jnp.float32):
    """q, k, v; ``h`` is the heads or ``(query heads, K/V heads)``, ``d``
    the width or ``(q/k width, v width)``."""
    h, hkv = h if isinstance(h, tuple) else (h, h)
    d, dv = d if isinstance(d, tuple) else (d, d)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, tq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, tk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, tk, hkv, dv), dtype)
    return q, k, v


def _scale(d):
    return 1.0 / math.sqrt(d[0] if isinstance(d, tuple) else d)


@pytest.mark.parametrize(
    "b,tq,tk,h,d",
    [
        (1, 16, 16, 1, 32),
        (2, 16, 24, 4, 32),  # rectangular block (ring step of unequal shards)
        (2, 8, 8, 3, 64),
        (1, 257, 1100, 1, 32),  # ragged q AND k tiles (streaming loop)
    ],
)
def test_kernel_matches_jnp_path(b, tq, tk, h, d):
    q, k, v = _qkv(0, b, tq, tk, h, d)
    mask = jax.random.bernoulli(jax.random.PRNGKey(7), 0.8, (tq, tk))
    scale = 1.0 / math.sqrt(d)
    o_k, m_k, l_k = flash_block_partials(q, k, v, mask, scale=scale,
                                         interpret=True)
    o_j, m_j, l_j = flash_block_partials(q, k, v, mask, scale=scale,
                                         force_jnp=True)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_j),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_j),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_j),
                               rtol=1e-5, atol=1e-5)


def test_kernel_fully_masked_rows():
    """A ring step attending a strictly-future K/V block has fully-masked
    query rows: the partials must be (m=-inf, l=0, o=0) — not NaN — so the
    merge rule can ignore them."""
    b, t, h, d = 2, 16, 2, 32
    q, k, v = _qkv(1, b, t, t, h, d)
    # causal mask of a future block: every row fully masked
    mask = jnp.zeros((t, t), bool)
    for kwargs in ({"interpret": True}, {"force_jnp": True}):
        o, m, l = flash_block_partials(q, k, v, mask, scale=0.1, **kwargs)
        o, m, l = np.asarray(o), np.asarray(m), np.asarray(l)
        assert np.all(np.isinf(m)) and np.all(m < 0), kwargs
        assert np.all(l == 0.0), kwargs
        assert np.all(o == 0.0), kwargs
        assert not np.any(np.isnan(o)), kwargs


def test_kernel_partially_masked_rows():
    """The causal diagonal block: rows have 1..t attendable keys."""
    b, t, h, d = 1, 16, 2, 32
    q, k, v = _qkv(2, b, t, t, h, d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    o_k, m_k, l_k = flash_block_partials(q, k, v, mask, scale=0.2,
                                         interpret=True)
    o_j, m_j, l_j = flash_block_partials(q, k, v, mask, scale=0.2,
                                         force_jnp=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_j),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_j),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["interpret", "force_jnp"])
def test_blockwise_merge_equals_full_softmax(impl):
    """Splitting K/V into blocks, computing partials per block, and folding
    with merge_partials must equal plain full attention — the invariant
    ring_attention rests on."""
    b, t, h, d = 2, 32, 2, 32
    q, k, v = _qkv(3, b, t, t, h, d)
    scale = 1.0 / math.sqrt(d)
    kwargs = {impl: True} if impl == "force_jnp" else {"interpret": True}

    # ground truth: full softmax attention
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    expected = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v
    )

    m = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    acc = jnp.zeros_like(q)
    n_blocks = 4
    blk = t // n_blocks
    full_mask = jnp.ones((t, blk), bool)
    for i in range(n_blocks):
        kb = k[:, i * blk : (i + 1) * blk]
        vb = v[:, i * blk : (i + 1) * blk]
        o_new, m_new, l_new = flash_block_partials(
            q, kb, vb, full_mask, scale=scale, **kwargs
        )
        acc, m, l = merge_partials(acc, m, l, o_new, m_new, l_new)
    out = acc / jnp.moveaxis(l, 1, 2)[..., None]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("impl", ["interpret", "force_jnp"])
def test_mask_none_equals_all_true_mask(impl):
    b, t, h, d = 2, 16, 2, 32
    q, k, v = _qkv(5, b, t, t, h, d)
    kwargs = {impl: True} if impl == "force_jnp" else {"interpret": True}
    o_n, m_n, l_n = flash_block_partials(q, k, v, None, scale=0.2, **kwargs)
    o_t, m_t, l_t = flash_block_partials(
        q, k, v, jnp.ones((t, t), bool), scale=0.2, **kwargs
    )
    np.testing.assert_allclose(np.asarray(o_n), np.asarray(o_t),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_n), np.asarray(m_t), rtol=1e-7)
    np.testing.assert_allclose(np.asarray(l_n), np.asarray(l_t), rtol=1e-6)


@pytest.mark.parametrize("impl", ["interpret", "force_jnp"])
def test_bf16_dtype_contract(impl):
    """o_part keeps the input dtype; m/l are f32 on both paths."""
    b, t, h, d = 1, 16, 2, 32
    q, k, v = _qkv(6, b, t, t, h, d, dtype=jnp.bfloat16)
    kwargs = {impl: True} if impl == "force_jnp" else {"interpret": True}
    o, m, l = flash_block_partials(q, k, v, None, scale=0.2, **kwargs)
    assert o.dtype == jnp.bfloat16
    assert m.dtype == jnp.float32 and l.dtype == jnp.float32


def test_ring_attention_preserves_bf16_dtype():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "examples"))
    from long_context_attention import ring_attention

    import mpi4jax_tpu as mpx

    comm = mpx.get_default_comm()
    size = comm.Get_size()
    shape = (size, 1, 8, 2, 32)
    q = jnp.ones(shape, jnp.bfloat16)

    @mpx.spmd
    def f(q):
        return ring_attention(q, q, q, comm=comm, causal=True)

    out = f(q)
    assert out.dtype == jnp.bfloat16


def test_merge_with_fully_masked_block_is_identity():
    b, t, h, d = 1, 8, 1, 32
    q, k, v = _qkv(4, b, t, t, h, d)
    o1, m1, l1 = flash_block_partials(q, k, v, jnp.ones((t, t), bool),
                                      scale=0.3, force_jnp=True)
    o0, m0, l0 = flash_block_partials(q, k, v, jnp.zeros((t, t), bool),
                                      scale=0.3, force_jnp=True)
    acc, m, l = merge_partials(o1, m1, l1, o0, m0, l0)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(o1), rtol=1e-7)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m1))
    np.testing.assert_allclose(np.asarray(l), np.asarray(l1), rtol=1e-7)


@pytest.mark.parametrize(
    "b,t,h,d",
    [
        (2, 16, 4, 32),     # single q tile
        (1, 1024, 1, 32),   # two full tiles (exercises the fori_loop)
        (1, 1100, 1, 32),   # ragged final tile (K/V padding + kpos guard)
        # grouped K/V (4 query heads a K/V head) at width 64 over three
        # tiles, whole and ragged; latent widths (qk 192, v 128)
        pytest.param(1, 1536, (8, 2), 64, id="grouped-64-t1536"),
        pytest.param(1, 1100, (8, 2), 64, id="grouped-64-t1100"),
        pytest.param(1, 1536, 2, (192, 128), id="qk192-v128-t1536"),
    ],
)
def test_causal_kernel_matches_tril_mask(b, t, h, d):
    """causal=True (the key-tile-skipping kernel) must equal the general
    kernel/jnp path given the equivalent triangular mask, including across
    tile boundaries and ragged tails."""
    q, k, v = _qkv(3, b, t, t, h, d)
    scale = _scale(d)
    o_c, m_c, l_c = flash_block_partials(q, k, v, None, scale=scale,
                                         causal=True, interpret=True)
    mask = jnp.tril(jnp.ones((t, t), bool))
    o_j, m_j, l_j = flash_block_partials(q, k, v, mask, scale=scale,
                                         force_jnp=True)
    np.testing.assert_allclose(np.asarray(m_c), np.asarray(m_j),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(l_c), np.asarray(l_j),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_j),
                               rtol=1e-4, atol=1e-4)


def _normalized(o, l):
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return o / jnp.moveaxis(l_safe, 1, 2)[..., None]


@pytest.mark.parametrize(
    "b,tq,tk,h,d,causal,masked",
    [
        (1, 16, 16, 2, 32, False, False),
        (2, 16, 24, 2, 32, False, False),   # rectangular ring block
        (2, 16, 24, 2, 32, False, True),    # user mask (float0 cotangent)
        (1, 64, 64, 2, 32, True, False),    # causal kernel
        (1, 550, 550, 1, 32, True, False),  # ragged tiles (padding guards)
        (1, 257, 1100, 1, 32, False, False),  # streaming non-causal tiles
        (1, 257, 1100, 1, 32, False, True),   # ... with a mask
        # grouped K/V at width 64 over three tiles, whole and ragged
        pytest.param(1, 1536, 1536, (8, 2), 64, True, False,
                     id="grouped-64-t1536"),
        pytest.param(1, 1100, 1100, (8, 2), 64, True, False,
                     id="grouped-64-t1100"),
        pytest.param(1, 1536, 1536, 2, (192, 128), True, False,
                     id="qk192-v128-t1536"),
        # no user mask: the ragged last key tile (dq, forward) and the
        # ragged last query tile (dk/dv) are the only masked tiles
        pytest.param(1, 1100, 1300, 2, 32, False, False,
                     id="ragged-tiles-only-masked"),
    ],
)
def test_grad_kernel_matches_jnp_path(b, tq, tk, h, d, causal, masked):
    """The blockwise backward (Pallas kernels, run under interpret) must
    agree with the dense jnp backward — same custom-VJP formula, different
    execution/tiling — through a normalized-attention loss."""
    q, k, v = _qkv(11, b, tq, tk, h, d)
    mask = (
        jax.random.bernoulli(jax.random.PRNGKey(7), 0.8, (tq, tk))
        if masked else None
    )
    scale = _scale(d)

    def loss(q, k, v, **kwargs):
        o, _, l = flash_block_partials(
            q, k, v, mask, scale=scale, causal=causal, **kwargs
        )
        return (_normalized(o, l) ** 2).sum()

    g_k = jax.grad(lambda *a: loss(*a, interpret=True), (0, 1, 2))(q, k, v)
    g_j = jax.grad(lambda *a: loss(*a, force_jnp=True), (0, 1, 2))(q, k, v)
    for a, e, nm in zip(g_k, g_j, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=1e-3, atol=1e-4,
            err_msg=f"d{nm}",
        )


@pytest.mark.parametrize("impl", ["interpret", "force_jnp"])
def test_grad_through_blockwise_merge(impl):
    """Gradients through a merge_partials chain: this is the path where a
    NONZERO stabilizer cotangent (g_m) reaches the custom VJP and is
    dropped — exact because the merge rule is stabilizer-invariant.  The
    composed gradient must match full-softmax attention's."""
    b, t, h, d = 1, 32, 2, 32
    q, k, v = _qkv(12, b, t, t, h, d)
    scale = 1.0 / math.sqrt(d)
    kwargs = {impl: True} if impl == "force_jnp" else {"interpret": True}
    n_blocks, blk = 4, t // 4

    def loss_blockwise(q, k, v):
        m = jnp.full((b, h, t), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, h, t), jnp.float32)
        acc = jnp.zeros_like(q)
        for i in range(n_blocks):
            kb = k[:, i * blk: (i + 1) * blk]
            vb = v[:, i * blk: (i + 1) * blk]
            o_new, m_new, l_new = flash_block_partials(
                q, kb, vb, None, scale=scale, **kwargs
            )
            acc, m, l = merge_partials(acc, m, l, o_new, m_new, l_new)
        return ((acc / jnp.moveaxis(l, 1, 2)[..., None]) ** 2).sum()

    def loss_full(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return (out ** 2).sum()

    g_b = jax.grad(loss_blockwise, (0, 1, 2))(q, k, v)
    g_f = jax.grad(loss_full, (0, 1, 2))(q, k, v)
    for a, e, nm in zip(g_b, g_f, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=5e-3, atol=5e-4,
            err_msg=f"d{nm}",
        )


@pytest.mark.parametrize("impl", ["interpret", "force_jnp"])
def test_grad_fully_masked_rows_no_nan(impl):
    """Rows with no attendable key (m = -inf, l = 0) must produce ZERO
    gradients, not NaN, so the ring's skipped-block merges stay clean."""
    b, t, h, d = 1, 16, 2, 32
    q, k, v = _qkv(13, b, t, t, h, d)
    mask = jnp.zeros((t, t), bool)
    kwargs = {impl: True} if impl == "force_jnp" else {"interpret": True}

    def loss(q, k, v):
        o, _, l = flash_block_partials(q, k, v, mask, scale=0.2, **kwargs)
        return (_normalized(o, l) ** 2).sum()

    g = jax.grad(loss, (0, 1, 2))(q, k, v)
    for a, nm in zip(g, "qkv"):
        a = np.asarray(a)
        assert not np.any(np.isnan(a)), f"d{nm} has NaN"
        np.testing.assert_array_equal(a, np.zeros_like(a), err_msg=f"d{nm}")


def test_grad_bf16_dtype_contract():
    """Cotangents keep the primal dtypes on both backward paths."""
    b, t, h, d = 1, 16, 2, 32
    q, k, v = _qkv(14, b, t, t, h, d, dtype=jnp.bfloat16)
    for kwargs in ({"interpret": True}, {"force_jnp": True}):
        def loss(q, k, v):
            o, _, l = flash_block_partials(
                q, k, v, None, scale=0.2, causal=True, **kwargs
            )
            return (_normalized(o, l).astype(jnp.float32) ** 2).sum()

        g = jax.grad(loss, (0, 1, 2))(q, k, v)
        assert all(a.dtype == jnp.bfloat16 for a in g), kwargs


def test_forward_mode_stays_supported_on_jnp_path():
    """The custom VJP wraps only the kernel path: the jnp fallback must
    keep JAX's native forward-mode (jax.jvp) — regression for wrapping
    the whole dispatch in custom_vjp, which would raise TypeError here."""
    q, k, v = _qkv(15, 1, 8, 8, 1, 32)

    def f(q):
        o, _, l = flash_block_partials(q, k, v, None, scale=0.2,
                                       force_jnp=True)
        return (_normalized(o, l) ** 2).sum()

    _, tang = jax.jvp(f, (q,), (jnp.ones_like(q),))
    assert np.isfinite(float(tang))


def test_causal_kernel_validation():
    q, k, v = _qkv(4, 1, 16, 24, 1, 32)
    with pytest.raises(ValueError, match="Tq == Tk"):
        flash_block_partials(q, k, v, None, scale=1.0, causal=True,
                             interpret=True)
    q2, k2, v2 = _qkv(4, 1, 16, 16, 1, 32)
    with pytest.raises(ValueError, match="replaces mask"):
        flash_block_partials(q2, k2, v2, jnp.ones((16, 16), bool),
                             scale=1.0, causal=True, interpret=True)


@pytest.mark.parametrize("t,unmasked,masked", [
    (512, 0, 1),
    (1536, 3, 3),
    (8192, 120, 16),    # a layer of the latent-attention training cell
    (16384, 496, 32),   # a layer of the hybrid training cell
])
def test_causal_tile_counts(t, unmasked, masked):
    """A causal call of n whole tiles puts the n diagonal tiles of each
    kernel on the masked path and the n(n-1)/2 below them on the unmasked
    one, for each (batch, head) pair."""
    assert causal_tile_counts(t) == {
        k: (unmasked, masked) for k in ("fwd", "bwd_dq", "bwd_dkv")}


@pytest.mark.parametrize("over_queries", [False, True],
                         ids=["walking-keys", "walking-queries"])
@pytest.mark.parametrize("t", [300, 1100, 1536, 2048])
def test_tile_runs_cover_the_causal_triangle_once(t, over_queries):
    """The runs the kernels walk visit each tile of the causal triangle
    once and mask exactly the diagonal tiles and, walking queries, a
    ragged last query tile (walking keys, a ragged last key tile is only
    ever on the diagonal)."""
    bq = 512 if t > 512 else t
    n = -(-t // bq)
    ragged = t % bq != 0
    seen = Counter()
    for i in range(n):
        for start, count, needs_mask in flash_attention._tile_runs(
                i, n, causal=True, ragged=ragged, masked=False,
                over_queries=over_queries):
            for j in range(int(start), int(start) + int(count)):
                qt, kt = (j, i) if over_queries else (i, j)
                seen[qt, kt] += 1
                assert needs_mask == (
                    qt == kt or (over_queries and ragged and qt == n - 1))
    assert seen == {(qt, kt): 1 for qt in range(n) for kt in range(qt + 1)}
