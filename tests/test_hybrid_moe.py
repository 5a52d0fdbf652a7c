"""The hybrid decoder of short convolutions, grouped-query attention and
sigmoid-routed experts (``mpi4jax_tpu.models.hybrid_moe``), its gated short
convolution (``kernels/short_conv.py``) and grouped K/V in the flash
kernels (``kernels/flash_attention.py``), on the CPU at a tiny size,
against the plain float32 reference of the benchmark
(``chipbench/reference/hybrid_moe.py``, which imports nothing of the
program).

Tolerances and their reasons.  The kernels and their jnp paths do the same
float32 arithmetic in another order: outputs written in bfloat16 agree to
one bfloat16 step of the largest value (``BF16_STEP``), float32 ones to
``F32_TOL``.  The model rounds every matmul operand to bfloat16 (a relative
step of 2**-8) and accumulates in float32; the reference is float32
throughout.  Aggregates average the rounding out: the loss agrees to
``LOSS_TOL`` (measured 1.5e-5 to 3e-5 here), each group's gradient norm to
``NORM_TOL`` (measured under 2e-3), each parameter's gradient as a whole to
``GRAD_TOL`` in relative Frobenius norm, and the norm of each parameter's
first AdamW change (about the learning rate a weight, whatever the
rounding) to ``UPDATE_TOL`` (measured under 3e-3).  Each planted fault
moves the loss or some parameter's gradient by more.
"""

import dataclasses
import hashlib
import json
import math
import os
import re
import sys
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench.reference import hybrid_moe as ref  # noqa: E402
from mpi4jax_tpu.kernels import flash_attention  # noqa: E402
from mpi4jax_tpu.kernels import grouped_matmul as gm  # noqa: E402
from mpi4jax_tpu.kernels import short_conv as sc  # noqa: E402
from mpi4jax_tpu.kernels.flash_attention import (  # noqa: E402
    flash_block_partials,
)
from mpi4jax_tpu.models import hybrid_moe  # noqa: E402
from mpi4jax_tpu.parallel import moe  # noqa: E402

BF16_STEP = 2.0 ** -8
F32_TOL = 1e-5
LOSS_TOL = 2e-4
NORM_TOL = 5e-3
GRAD_TOL = 3e-2
UPDATE_TOL = 1e-2
TOKENS = 32
SEED = 2 ** 31 + 17
CONFIG = os.path.join(REPO, "chipbench", "configs", "lfm2_24b_a2b_train.json")


def tiny_config(held_first=0, held=4):
    """The cell's configuration at hidden 128 (4 query heads of 32, 2 K/V
    heads), 8 experts (``held`` of them here) of width 32, top-2,
    vocabulary 97; the layers held (a dense convolution layer, then
    attention and three convolutions) and every other key as the cell has
    them."""
    with open(CONFIG) as f:
        c = json.load(f)
    c.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=96, moe_intermediate_size=32,
             num_experts=held, num_experts_per_tok=2, vocab_size=97)
    c["deployment"] = dict(c["deployment"], experts_per_layer=8,
                           held_first=held_first)
    return c


def _ids(seed=0, n=TOKENS + 1, vocab=97):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, n),
                       jnp.int32)


def _initial(c, d, seed=SEED):
    return hybrid_moe.init_state(d, *ref.init_params(c, ref.seed_key(seed)))


def _state(c, d):
    """The seeded state with a selection bias large enough to change which
    experts are chosen (it starts at 0 in training)."""
    state = _initial(c, d)
    state["bias"] = 0.3 * jax.random.normal(jax.random.key(5),
                                            state["bias"].shape)
    return state


def _reference(c, params, bias, ids):
    def loss(p):
        ce, _ = ref.losses(c, p, bias, ids)
        return ce

    with jax.default_matmul_precision("highest"):
        ce, grads = jax.jit(jax.value_and_grad(loss))(params)
    sq = dict.fromkeys(ref.GROUPS, 0.0)
    for path, g in jax.tree.flatten_with_path(grads)[0]:
        sq[ref.group_of([str(getattr(k, "key", k)) for k in path])] += \
            float(jnp.sum(g * g))
    return {"ce": float(ce), "grads": grads,
            "grad_norms": {k: math.sqrt(v) for k, v in sq.items()}}


@pytest.fixture(scope="module")
def case():
    c = tiny_config()
    d = hybrid_moe.Dims.from_config(c)
    state, ids = _state(c, d), _ids()
    return c, d, state, ids, _reference(c, state["params"], state["bias"],
                                        ids)


def _program_grads(d, state, ids, **kw):
    def loss(p):
        ce, _ = hybrid_moe.loss_and_aux(d, p, state["bias"], ids, **kw)
        return ce

    ce, grads = jax.jit(jax.value_and_grad(loss))(state["params"])
    return float(ce), grads


def _leaf_gaps(grads, want):
    return {jax.tree_util.keystr(path): float(
                jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for (path, g), w in zip(jax.tree.flatten_with_path(grads)[0],
                                    jax.tree.leaves(want["grads"]))
            if float(jnp.linalg.norm(w)) > 0}


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------


def _conv_case(t, d, seed=0, taps=None):
    ks = jax.random.split(jax.random.key(seed), 3)
    bcx = jax.random.normal(ks[0], (t, 3 * d)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (3, d)) if taps is None else taps
    g = jax.random.normal(ks[2], (t, d)).astype(jnp.bfloat16)
    return bcx, w, g


def _by_hand(bcx, w):
    """``C * conv3(B * x)`` position by position, in float64."""
    b, c, x = np.split(np.asarray(bcx, np.float64), 3, -1)
    z = b * x
    w = np.asarray(w, np.float64)
    out = np.zeros_like(z)
    for t in range(z.shape[0]):
        for j in range(3):
            if t - 2 + j >= 0:
                out[t] += w[j] * z[t - 2 + j]
    return c * out


def _close(a, b, step=BF16_STEP):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= step * np.max(np.abs(b)), \
        np.max(np.abs(a - b)) / np.max(np.abs(b))


@pytest.mark.parametrize("t,d", [(32, 128), (1024, 1024)],
                         ids=["one_tile", "tiles_2x2"])
def test_short_conv_kernels_match_the_jnp_path(t, d):
    """Forward and backward kernels (interpreted) against the jnp path and
    a count by hand, on one tile and on 2 x 2 tiles of 512 (where a tile
    reads the rows before and after it)."""
    bcx, w, g = _conv_case(t, d)
    y_k, vjp_k = jax.vjp(lambda a, b: sc.short_conv(a, b, interpret=True),
                         bcx, w)
    y_j, vjp_j = jax.vjp(lambda a, b: sc.short_conv(a, b, force_jnp=True),
                         bcx, w)
    assert y_k.dtype == jnp.bfloat16 and y_k.shape == (t, d)
    _close(y_k, y_j)
    _close(y_j, _by_hand(bcx, w))
    (d_bcx_k, dw_k), (d_bcx_j, dw_j) = vjp_k(g), vjp_j(g)
    assert d_bcx_k.shape == bcx.shape and dw_k.dtype == jnp.float32
    _close(d_bcx_k, d_bcx_j)
    _close(dw_k, dw_j, F32_TOL)


def test_short_conv_at_the_first_positions_and_tile_edges():
    """Position 0 sees only its own tap, position 1 two taps; with only
    tap 2 the operator is ``B * x * C`` everywhere, and with only tap 0 it
    is ``C_t * B_{t-2} * x_{t-2}``, across tile edges too."""
    t, d = 1024, 512
    for tap in range(3):
        w = jnp.zeros((3, d)).at[tap].set(1.0)
        bcx, _, g = _conv_case(t, d, seed=tap, taps=w)
        y = sc.short_conv(bcx, w, interpret=True)
        b, c, x = (np.asarray(a, np.float64) for a in jnp.split(bcx, 3, -1))
        lag = 2 - tap
        want = np.zeros_like(b)
        want[lag:] = c[lag:] * b[:t - lag] * x[:t - lag]
        _close(y, want)
        assert not np.asarray(y[:lag], np.float64).any()
    # the first two positions with every tap
    bcx, w, _ = _conv_case(8, 128, seed=7)
    hand = _by_hand(bcx, w)
    _close(sc.short_conv(bcx, w, interpret=True)[:2], hand[:2])


def test_short_conv_gradient_reaches_back_two_positions():
    """The backward kernel's ``d[B | C | x]`` and ``dw`` against jax's own
    gradient of the count by hand, with the cotangent on one position at
    a tile's start: it reaches that position and the two before it, in
    the previous tile."""
    t, d = 1024, 512
    bcx, w, _ = _conv_case(t, d, seed=3)
    g = jnp.zeros((t, d), jnp.bfloat16).at[512].set(1.0)
    _, vjp = jax.vjp(lambda a, b: sc.short_conv(a, b, interpret=True),
                     bcx, w)
    d_bcx, dw = vjp(g)
    rows = np.nonzero(np.abs(np.asarray(d_bcx, np.float64)).sum(-1))[0]
    assert rows.tolist() == [510, 511, 512]
    _, want_vjp = jax.vjp(lambda a, b: sc.short_conv(a, b, force_jnp=True),
                          bcx, w)
    want_bcx, want_dw = want_vjp(g)
    _close(d_bcx, want_bcx)
    _close(dw, want_dw, F32_TOL)


def test_short_conv_rejects_shapes_that_are_not_its_own():
    with pytest.raises(ValueError):
        sc.short_conv(jnp.zeros((8, 10)), jnp.zeros((3, 3)))
    with pytest.raises(ValueError):
        sc.short_conv(jnp.zeros((8, 12)), jnp.zeros((2, 4)))


# ---------------------------------------------------------------------------
# grouped K/V in the flash kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("group", [1, 4])
def test_grouped_kv_flash_kernels_match_the_jnp_path(causal, group):
    """Forward and both backward kernels (interpreted) against the jnp path
    (K/V repeated over each group) and a plain attention, for one and four
    query heads a K/V head; dk and dv sum over the group."""
    b, t, h, d = 1, 256, 8, 32
    ks = jax.random.split(jax.random.key(group), 4)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h // group, d))
    v = jax.random.normal(ks[2], (b, t, h // group, d))
    go = jax.random.normal(ks[3], (b, t, h, d))

    def flash(**kw):
        def attend(q, k, v):
            o, _, l = flash_block_partials(q, k, v, None, scale=d ** -0.5,
                                           causal=causal,
                                           name="gqa_attention", **kw)
            return o / jnp.moveaxis(l, 1, 2)[..., None]
        return attend

    def plain(q, k, v):
        kk, vv = (jnp.repeat(a, group, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)

    got, got_vjp = jax.vjp(flash(interpret=True), q, k, v)
    jnp_out, jnp_vjp = jax.vjp(flash(force_jnp=True), q, k, v)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(plain, q, k, v)
        want_grads = want_vjp(go)
    np.testing.assert_allclose(got, jnp_out, rtol=F32_TOL, atol=F32_TOL)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    for a, j, w in zip(got_vjp(go), jnp_vjp(go), want_grads):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, j, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(a, w, rtol=5e-2, atol=5e-2)


def _pallas_calls(jaxpr, out):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn))
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                    _pallas_calls(getattr(sub, "jaxpr", sub), out)
    return out


# digests of the three pallas_calls (grid, block specs, index maps and
# kernel) of an ungrouped latent-attention call and its gradient: a change
# to the kernels an ungrouped call builds shows here, and is pinned anew
UNGROUPED_DIGESTS = {"causal": "36e4a4fdf5200d1b",
                     "masked": "f81a48c7dff11526"}


@pytest.mark.parametrize("kind", ["causal", "masked"])
def test_ungrouped_calls_build_the_kernels_they_built_before(kind):
    causal = kind == "causal"
    mask = None if causal else jnp.tril(jnp.ones((256, 256), bool))
    q = jnp.ones((1, 256, 2, 48), jnp.bfloat16)
    v = jnp.ones((1, 256, 2, 32), jnp.bfloat16)

    def loss(q, k, v):
        o, _, l = flash_block_partials(q, k, v, mask, scale=0.1,
                                       causal=causal, interpret=True,
                                       name="mla_attention")
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(l)

    calls = _pallas_calls(jax.make_jaxpr(jax.value_and_grad(
        loss, (0, 1, 2)))(q, q, v).jaxpr, [])
    assert len(calls) == 3
    assert hashlib.sha256("\n".join(calls).encode()).hexdigest()[:16] == \
        UNGROUPED_DIGESTS[kind]


def test_grouped_kv_needs_whole_groups():
    q = jnp.zeros((1, 16, 6, 8))
    with pytest.raises(ValueError):
        flash_block_partials(q, jnp.zeros((1, 16, 4, 8)),
                             jnp.zeros((1, 16, 4, 8)), None, scale=1.0)
    with pytest.raises(ValueError):
        flash_block_partials(q, jnp.zeros((1, 16, 3, 8)),
                             jnp.zeros((1, 16, 2, 8)), None, scale=1.0)


# ---------------------------------------------------------------------------
# the model against the plain reference
# ---------------------------------------------------------------------------


def test_the_configuration_reads_as_the_published_stack():
    with open(CONFIG) as f:
        d = hybrid_moe.Dims.from_config(json.load(f))
    assert d.kinds == ("conv", "attn", "conv", "conv", "conv")
    assert d.dense == (True, False, False, False, False)
    assert (d.head_dim, d.heads, d.kv_heads, d.experts, d.held) == \
        (64, 32, 8, 64, 8)
    assert d.router_eps == 1e-6 and d.eps == 1e-5
    assert hybrid_moe.param_count(d) == 469_284_992
    for key, value in (("conv_bias", True), ("conv_L_cache", 4),
                       ("use_expert_bias", False)):
        with open(CONFIG) as f:
            c = json.load(f)
        c[key] = value
        with pytest.raises(ValueError):
            hybrid_moe.Dims.from_config(c)


@pytest.mark.parametrize("path", ["jnp", "kernels_interpreted"])
def test_loss_and_grad_norms_match_the_reference(case, path):
    c, d, state, ids, want = case
    kw = {"interpret": True} if path == "kernels_interpreted" else {}
    _, out = jax.jit(hybrid_moe.make_train_step(d, **kw))(state, ids)
    norms = dict(zip(hybrid_moe.GRAD_GROUPS, np.asarray(out["grad_norms"])))
    assert abs(float(out["loss"][0]) - want["ce"]) / want["ce"] < LOSS_TOL
    gaps = {g: abs(norms[g] - want["grad_norms"][g]) / want["grad_norms"][g]
            for g in norms}
    assert max(gaps.values()) < NORM_TOL, gaps
    assert int(out["dropped"]) == 0
    # every token chose top_k experts in each of the four MoE layers
    assert np.asarray(out["loads"]).sum(-1).tolist() == [TOKENS * 2] * 4


def test_gradients_match_the_reference_parameter_by_parameter(case):
    c, d, state, ids, want = case
    ce, grads = _program_grads(d, state, ids)
    assert abs(ce - want["ce"]) / want["ce"] < LOSS_TOL
    gaps = _leaf_gaps(grads, want)
    assert len(gaps) > 30 and max(gaps.values()) < GRAD_TOL, gaps


def test_the_first_update_matches_the_reference(case):
    """The program's first AdamW step and bias move against the
    reference's from its own gradients and loads."""
    c, d, state, ids, _ = case
    new, _ = jax.jit(hybrid_moe.make_train_step(d))(state, ids)
    got = ref.first_step(c, state["params"], state["bias"], ids)
    for (path, a), b in zip(jax.tree.flatten_with_path(new["params"])[0],
                            jax.tree.leaves(state["params"])):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) == pytest.approx(
            got["update_norms"][name], rel=UPDATE_TOL, abs=1e-9), name
    np.testing.assert_allclose(np.asarray(new["bias"] - state["bias"]),
                               got["bias_move"], atol=1e-7)
    assert int(new["count"]) == 1


def test_the_float8_control_fails_the_gradient_norms(case):
    c, d, state, ids, want = case
    got = ref.first_step(dict(c, reference_operands="float8_e4m3fn"),
                         state["params"], state["bias"], ids)
    assert max(abs(got["grad_norms"][g] - want["grad_norms"][g])
               / want["grad_norms"][g] for g in ref.GROUPS) > NORM_TOL
    same = ref.first_step(c, state["params"], state["bias"], ids)
    assert same["ce"] == pytest.approx(want["ce"], rel=1e-6)


def _interleaved_rope(monkeypatch, d):
    def rope(x, theta):
        t, half = x.shape[0], x.shape[-1] // 2
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2
                         / x.shape[-1])
        ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * freq)[:, None]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                          x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                         -1).reshape(x.shape)

    monkeypatch.setattr(hybrid_moe, "_rope", rope)


def _conv_looking_ahead(monkeypatch, d):
    def short_conv(bcx, w, **kw):
        return sc.short_conv(bcx[::-1], w, force_jnp=True)[::-1]

    monkeypatch.setattr(hybrid_moe, "short_conv", short_conv)


def _kv_heads_misread(monkeypatch, d):
    sound = hybrid_moe.flash_block_partials

    def flash(q, k, v, mask, **kw):
        return sound(q, k[:, :, ::-1], v[:, :, ::-1], mask, **kw)

    monkeypatch.setattr(hybrid_moe, "flash_block_partials", flash)


def _head_untied(monkeypatch, d):
    def cross_entropy(self, h, params, targets):
        head = jax.lax.stop_gradient(params["embed"])
        from mpi4jax_tpu.models import training
        return training.cross_entropy(
            h, targets, lambda hc: self.mm(training.rms_norm(
                hc, params["final_norm"], self.d.eps), head.T))

    monkeypatch.setattr(hybrid_moe._Model, "cross_entropy", cross_entropy)


def _qk_norm_left_out(monkeypatch, d):
    sound = hybrid_moe.rms_norm

    def rms_norm(x, w, eps):
        return x if x.shape[-1] == d.head_dim else sound(x, w, eps)

    monkeypatch.setattr(hybrid_moe, "rms_norm", rms_norm)


@pytest.mark.parametrize("fault", [
    _interleaved_rope, _conv_looking_ahead, _kv_heads_misread,
    _head_untied, _qk_norm_left_out], ids=lambda f: f.__name__.strip("_"))
def test_planted_faults_fail_against_the_reference(case, monkeypatch,
                                                   fault):
    c, d, state, ids, want = case
    fault(monkeypatch, d)
    ce, grads = _program_grads(d, state, ids)
    assert abs(ce - want["ce"]) / want["ce"] > LOSS_TOL or \
        max(_leaf_gaps(grads, want).values()) > GRAD_TOL


def test_init_state_takes_only_the_configured_shapes():
    c = tiny_config()
    d = hybrid_moe.Dims.from_config(c)
    params, bias = ref.init_params(c, ref.seed_key(SEED))
    state = hybrid_moe.init_state(d, params, bias)
    assert int(state["count"]) == 0 and state["bias"].shape == (4, 8)
    params["layers"][1]["attn"]["k_proj"] = \
        params["layers"][1]["attn"]["q_proj"]
    with pytest.raises(ValueError):
        hybrid_moe.init_state(d, params, bias)


def test_the_step_trains_and_moves_the_bias():
    """Six steps on one sequence at the full rate (no warmup): the loss
    falls, the count advances, the bias moves by at most its rate a
    step."""
    c = tiny_config()
    d = dataclasses.replace(hybrid_moe.Dims.from_config(c), warmup_steps=1)
    step = jax.jit(hybrid_moe.make_train_step(d))
    state = _initial(c, d)
    losses = []
    for _ in range(6):
        state, out = step(state, _ids(0))
        losses.append(float(out["loss"][0]))
    assert losses[-1] < 0.99 * losses[0]
    assert int(state["count"]) == 6
    moved = np.asarray(state["bias"])
    assert np.abs(moved).max() > 0 and np.all(np.abs(moved) <= 6 * 0.001
                                              + 1e-9)


# ---------------------------------------------------------------------------
# the chip's share of an expert layer
# ---------------------------------------------------------------------------


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight ranks of one expert each, as the deployment divides a layer of
    64 over 8 chips: their held parts add up to the uncut layer, the
    program's own (one call holding all eight) and the plain reference's
    (no shared expert here, so nothing is counted once)."""
    c = tiny_config(held=8)
    d = hybrid_moe.Dims.from_config(c)
    p = _initial(c, d)["params"]["layers"][1]["routed"]
    x = jax.random.normal(jax.random.key(1), (TOKENS, d.hidden))
    bias = 0.01 * jax.random.normal(jax.random.key(2), (d.experts,))
    kw = dict(top_k=d.top_k, routed_scaling=d.routed_scaling,
              norm_eps=d.router_eps)

    def share(first, count):
        held = moe.HeldExperts(p["router"],
                               p["w_gate_up"][first:first + count],
                               p["w_down"][first:first + count])
        return moe.held_experts_layer(x, held, bias, held=(first, count),
                                      **kw)

    parts = [share(r, 1) for r in range(8)]
    whole, loads = share(0, 8)
    assert all(np.array_equal(lo, loads) for _, lo in parts)
    assert sum(int(np.asarray(y).any()) for y, _ in parts) >= 6
    total = sum(y for y, _ in parts)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.routed_experts(c, p, bias, x)
    scale = float(jnp.max(jnp.abs(uncut)))
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-2 * scale
    # seven shares are not the whole
    assert float(jnp.max(jnp.abs(total - parts[0][0] - uncut))) > \
        1e-1 * scale


def test_the_routing_epsilon_is_the_configurations():
    """LFM2 normalises the chosen scores with ``+ 1e-6``; JoyAI keeps
    DeepSeek-V3's ``1e-20``; each model's ``Dims`` carries its own."""
    x = jax.random.normal(jax.random.key(3), (16, 8))
    router = jax.random.normal(jax.random.key(4), (8, 6))
    with jax.default_matmul_precision("highest"):
        top = np.asarray(jax.lax.top_k(jax.nn.sigmoid(x @ router), 2)[0],
                         np.float64)
    for eps in (1e-20, 1e-6, 0.5):
        _, w, _ = moe.route_sigmoid_topk(x, router, jnp.zeros((6,)), top_k=2,
                                         routed_scaling=1.0, norm_eps=eps)
        want = top / (top.sum(-1, keepdims=True) + eps)
        np.testing.assert_allclose(w, want, rtol=1e-6)
    assert hybrid_moe.Dims.from_config(tiny_config()).router_eps == 1e-6


# ---------------------------------------------------------------------------
# the plan against the program
# ---------------------------------------------------------------------------


def _kernel_calls(jaxpr, times=1, calls=None):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    calls = Counter() if calls is None else calls
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] += times
            continue
        n = times * eqn.params["length"] if eqn.primitive.name == "scan" \
            else times
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                    _kernel_calls(getattr(sub, "jaxpr", sub), n, calls)
    return calls


def test_the_step_calls_each_kernel_as_train_plan_says(case):
    c, d, state, ids, _ = case
    jax.clear_caches()
    try:
        step = hybrid_moe.make_train_step(d, interpret=True)
        calls = _kernel_calls(jax.make_jaxpr(step)(state, ids).jaxpr)
    finally:
        jax.clear_caches()
    assert dict(calls) == hybrid_moe.train_plan(d) == {
        "gqa_attention_fwd": 1, "gqa_attention_bwd_dq": 1,
        "gqa_attention_bwd_dkv": 1, "short_conv_fwd": 8,
        "short_conv_bwd": 4, "moe_gmm": 16, "moe_gmm_dx": 8,
        "moe_gmm_dw": 8}


# ---------------------------------------------------------------------------
# the kernels at the cell's widths, compiled for a described v5e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def kernels_on(monkeypatch):
    """Take the kernel path on this CPU host, as the chip would."""
    monkeypatch.setattr(flash_attention, "_use_kernel",
                        lambda interpret, force_jnp: not force_jnp)
    monkeypatch.setattr(sc, "_use_kernel",
                        lambda interpret, force_jnp: not force_jnp)
    monkeypatch.setattr(gm, "_use_kernel", lambda interpret: True)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache)


def _custom_calls(text):
    calls = Counter(re.sub(r"(\.\d+)+$", "", n) for n in re.findall(
        r"%([\w.\-]+) = [^\n]*custom-call\(", text))
    calls.pop("custom-call", None)
    return calls


def test_conv_and_grouped_attention_compile_for_v5e(topo, kernels_on):
    """The short convolution at 16384 tokens of width 2048 and grouped
    attention at 16384 tokens, 32 query and 8 K/V heads of 64, forward and
    backward, each kernel named in the compiled program as the trace will
    show it."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    t = 16384
    bcx = jax.ShapeDtypeStruct((t, 3 * 2048), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one)
    q = jax.ShapeDtypeStruct((1, t, 32, 64), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, t, 8, 64), jnp.bfloat16, sharding=one)

    def loss(bcx, w, q, k, v):
        o, _, l = flash_block_partials(q, k, v, None, scale=0.125,
                                       causal=True, name="gqa_attention")
        return (jnp.sum(sc.short_conv(bcx, w).astype(jnp.float32))
                + jnp.sum(o.astype(jnp.float32)) + jnp.sum(l))

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).lower(
        bcx, w, q, kv, kv).compile().as_text()
    assert _custom_calls(text) == {
        "short_conv_fwd": 1, "short_conv_bwd": 1, "gqa_attention_fwd": 1,
        "gqa_attention_bwd_dq": 1, "gqa_attention_bwd_dkv": 1}


def test_the_tiny_step_compiles_for_v5e_to_train_plan(topo, kernels_on):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    c = tiny_config()
    d = hybrid_moe.Dims.from_config(c)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: _initial(c, d)))
    ids = jax.ShapeDtypeStruct((TOKENS + 1,), jnp.int32, sharding=one)
    text = jax.jit(hybrid_moe.make_train_step(d)).lower(state, ids)\
        .compile().as_text()
    assert _custom_calls(text) == hybrid_moe.train_plan(d)


def test_grouped_products_at_width_1536_compile_for_v5e(topo, kernels_on):
    """The three grouped products at the cell's widths (8 held experts,
    2048 -> 2 x 1536, the dispatch buffer of 16384 tokens at top-4): a
    float32 weight block of 2048 x 3072 is 25 MB a buffer and fits the
    kernels' VMEM."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    rows = moe.held_capacity_rows(16384, 4, 8)
    x = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((8, 2048, 3072), jnp.float32, sharding=one)
    te = jax.ShapeDtypeStruct((rows // gm.TILE_ROWS,), jnp.int32,
                              sharding=one)
    n = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)

    def loss(x, w, te, n):
        return jnp.sum(gm.grouped_matmul(x, w, te, n) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(x, w, te, n).compile()\
        .as_text()
    assert _custom_calls(text) == {"moe_gmm": 1, "moe_gmm_dx": 1,
                                   "moe_gmm_dw": 1}


def test_the_rate_warms_up_linearly():
    """Step ``k`` of a warmup over ``n`` steps runs at ``k / n`` of the
    rate, then at the rate; the first step's change of a weight is about
    ``lr / n``."""
    from mpi4jax_tpu.models import training

    c = tiny_config()
    d = hybrid_moe.Dims.from_config(c)
    assert d.warmup_steps == 2000
    rates = [float(training.warmup_lr(d, jnp.asarray(k)))
             for k in (1, 1000, 2000, 5000)]
    np.testing.assert_allclose(rates, [d.lr / 2000, d.lr / 2, d.lr, d.lr],
                               rtol=1e-6)
    state = _initial(c, d)
    new, _ = jax.jit(hybrid_moe.make_train_step(d))(state, _ids(0))
    moved = np.abs(np.asarray(new["params"]["layers"][0]["conv"]["out_proj"]
                              - state["params"]["layers"][0]["conv"][
                                  "out_proj"]))
    assert float(np.median(moved)) == pytest.approx(d.lr / 2000, rel=0.05)
