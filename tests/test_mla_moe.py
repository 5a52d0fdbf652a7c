"""The MLA + sigmoid-routed MoE + MTP decoder (``mpi4jax_tpu.models.
mla_moe``), its held-experts layer (``parallel/moe.py``), the grouped
products (``kernels/grouped_matmul.py``) and attention with a value width
apart from the query/key width (``kernels/flash_attention.py``), on the
CPU at a tiny size, against the plain float32 reference of the benchmark
(``chipbench/reference/mla_moe.py``, which imports nothing of the program).

Tolerances and their reasons.  The program rounds every matmul operand to
bfloat16 (8 significant bits, a relative step of 2**-8) and accumulates in
float32; the reference is float32 throughout.  Aggregates average the
rounding out: the two losses agree to ``LOSS_TOL`` (measured 1e-5 to 3e-5
here), each group's gradient norm to ``NORM_TOL`` (measured under 1e-3),
and each parameter's gradient, as a whole, to ``GRAD_TOL`` in relative
Frobenius norm; the main logits to ``LOGIT_TOL`` of their largest value.
AdamW's first step moves each weight by about the learning rate whatever
its gradient's size, so the norm of each parameter's change agrees to
``UPDATE_TOL`` (measured under 3e-3).  Each planted fault below moves at
least one of these by more.
"""

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

from chipbench.reference import mla_moe as ref  # noqa: E402
from mpi4jax_tpu.attention import reference_attention  # noqa: E402
from mpi4jax_tpu.kernels import flash_attention  # noqa: E402
from mpi4jax_tpu.kernels import grouped_matmul as gm  # noqa: E402
from mpi4jax_tpu.kernels.flash_attention import (  # noqa: E402
    flash_block_partials,
)
from mpi4jax_tpu.models import mla_moe  # noqa: E402
from mpi4jax_tpu.parallel import moe  # noqa: E402

LOSS_TOL = 2e-4
NORM_TOL = 5e-3
GRAD_TOL = 3e-2
LOGIT_TOL = 2e-2
UPDATE_TOL = 1e-2
TOKENS = 32
SEED = 2 ** 31 + 17


def tiny_config(held_first=0, held=4):
    """Hidden 64, 2 heads, 8 experts (``held`` of them here), vocabulary
    97; every other key as the benchmark's configuration has it."""
    return {
        "hidden_size": 64, "q_lora_rank": 48, "kv_lora_rank": 32,
        "num_attention_heads": 2, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
        "moe_intermediate_size": 32, "n_routed_experts": held,
        "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
        "num_hidden_layers": 3, "vocab_size": 97, "rope_theta": 32000000,
        "rms_norm_eps": 1e-6, "first_k_dense_replace": 1,
        "n_shared_experts": 1, "num_nextn_predict_layers": 1, "n_group": 1,
        "topk_group": 1, "scoring_func": "sigmoid", "norm_topk_prob": True,
        "rope_interleave": True, "hidden_act": "silu",
        "tie_word_embeddings": False, "rope_scaling": None,
        "deployment": {"experts_per_layer": 8, "held_first": held_first},
        "assumed": {"mtp_loss_weight": 0.3, "bias_update_rate": 0.001,
                    "init_std": 0.02,
                    "embed_init_std": 1.0,
                    "adamw": {"lr": 3e-4, "beta1": 0.9,
                              "beta2": 0.95, "eps": 1e-8,
                              "weight_decay": 0.1}},
        "precision": {"matmul_operands": "bfloat16"},
    }


def _ids(seed=0, n=TOKENS + 2, vocab=97):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, n),
                       jnp.int32)


def _initial(c, d, seed=SEED):
    """The step-0 state around the reference's seeded initial parameters."""
    return mla_moe.init_state(d, *ref.init_params(c, ref.seed_key(seed)))


def _state(c, d):
    """The seeded state with a selection bias large enough to change which
    experts are chosen (it starts at 0 in training)."""
    state = _initial(c, d)
    state["bias"] = jax.tree.map(
        lambda b: 0.3 * jax.random.normal(jax.random.key(5), b.shape),
        state["bias"])
    return state


def _reference(c, params, bias, ids):
    """The plain reference's two losses, every gradient and the gradient
    norms by group, from one compiled function."""
    def loss(p):
        (main, mtp), _ = ref.losses(c, p, bias, ids)
        return main + c["assumed"]["mtp_loss_weight"] * mtp, (main, mtp)

    with jax.default_matmul_precision("highest"):
        grads, (main, mtp) = jax.jit(jax.grad(loss, has_aux=True))(params)
    sq = dict.fromkeys(ref.GROUPS, 0.0)
    for path, g in jax.tree.flatten_with_path(grads)[0]:
        group = ref.group_of([str(k.key) for k in path])
        sq[group] += float(jnp.sum(g * g))
    return {"ce_main": float(main), "ce_mtp": float(mtp), "grads": grads,
            "grad_norms": {k: math.sqrt(v) for k, v in sq.items()}}


@pytest.fixture(scope="module")
def case():
    c = tiny_config()
    d = mla_moe.Dims.from_config(c)
    state, ids = _state(c, d), _ids()
    return c, d, state, ids, _reference(c, state["params"], state["bias"],
                                        ids)


def _program(d, state, ids, **kw):
    _, out = jax.jit(mla_moe.make_train_step(d, **kw))(state, ids)
    norms = dict(zip(mla_moe.GRAD_GROUPS, np.asarray(out["grad_norms"])))
    return out, norms


def _gaps(out, norms, want):
    loss = max(abs(float(out["loss"][0]) - want["ce_main"]) / want["ce_main"],
               abs(float(out["loss"][1]) - want["ce_mtp"]) / want["ce_mtp"])
    norm = max(abs(norms[g] - want["grad_norms"][g]) / want["grad_norms"][g]
               for g in norms)
    return loss, norm


def _program_grads(d, state, ids):
    """The program's losses and every parameter's gradient."""
    def loss(p):
        total, (losses, _) = mla_moe.loss_and_aux(d, p, state["bias"], ids)
        return total, losses

    grads, losses = jax.jit(jax.grad(loss, has_aux=True))(state["params"])
    return [float(x) for x in losses], grads


def _leaf_gaps(grads, want):
    """Each parameter's gradient against the reference's, as a relative
    Frobenius distance, by key path."""
    return {jax.tree_util.keystr(path): float(
                jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for (path, g), w in zip(jax.tree.flatten_with_path(grads)[0],
                                    jax.tree.leaves(want["grads"]))}


# ---------------------------------------------------------------------------
# the model against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["jnp", "kernels_interpreted"])
def test_losses_and_grad_norms_match_the_reference(case, path):
    c, d, state, ids, want = case
    kw = {"interpret": True} if path == "kernels_interpreted" else {}
    out, norms = _program(d, state, ids, **kw)
    loss, norm = _gaps(out, norms, want)
    assert loss < LOSS_TOL and norm < NORM_TOL, (loss, norm)
    assert int(out["dropped"]) == 0
    # every token chose top_k experts in each MoE layer and the MTP block
    assert np.asarray(out["loads"]).sum(-1).tolist() == [TOKENS * 2] * 3


def test_gradients_and_logits_match_the_reference(case):
    c, d, state, ids, want = case
    params, bias = state["params"], state["bias"]
    _, grads = _program_grads(d, state, ids)
    gaps = _leaf_gaps(grads, want)
    assert max(gaps.values()) < GRAD_TOL, gaps
    model = mla_moe._Model(d, False)

    @jax.jit
    def logits(params):
        h, _ = model.trunk(params, bias, ids)
        return model.mm(mla_moe._rms_norm(h, params["final_norm"], 1e-6),
                        params["head"])

    @jax.jit
    def logits_ref(params):
        h, _ = ref.hidden(c, params, bias, ids)
        return ref.rms_norm(h, params["final_norm"], 1e-6) @ params["head"]

    with jax.default_matmul_precision("highest"):
        want_logits = logits_ref(params)
    top = float(jnp.max(jnp.abs(want_logits)))
    assert float(jnp.max(jnp.abs(logits(params) - want_logits))) < \
        LOGIT_TOL * top


def test_the_float8_control_fails_the_gradient_norms(case):
    """One precision below the configuration's: the reference with every
    matmul operand rounded to float8 e4m3 (the benchmark's control).  The
    losses of random weights sit near ln(vocab) whatever the rounding, so
    it is the gradient norms that catch it."""
    c, d, state, ids, want = case
    got = ref.first_step(dict(c, reference_operands="float8_e4m3fn"),
                         state["params"], state["bias"], ids)
    assert max(abs(got["grad_norms"][g] - want["grad_norms"][g])
               / want["grad_norms"][g] for g in ref.GROUPS) > NORM_TOL
    # the reference at its own precision reads what the plain autodiff does
    same = ref.first_step(c, state["params"], state["bias"], ids)
    assert same["ce_main"] == pytest.approx(want["ce_main"], rel=1e-6)
    for g in ref.GROUPS:
        assert same["grad_norms"][g] == pytest.approx(want["grad_norms"][g],
                                                      rel=1e-5)


def test_the_first_update_matches_the_reference(case):
    """The program's first AdamW step and bias move against the
    reference's from its own gradients and loads: each parameter's change
    has the reference's norm, and the bias moves as the reference's."""
    c, d, state, ids, want = case
    new, _ = jax.jit(mla_moe.make_train_step(d))(state, ids)
    got = ref.first_step(c, state["params"], state["bias"], ids)
    for (path, a), b in zip(jax.tree.flatten_with_path(new["params"])[0],
                            jax.tree.leaves(state["params"])):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) == pytest.approx(
            got["update_norms"][name], rel=UPDATE_TOL), name
    moved = np.concatenate([np.asarray(new["bias"]["moe"] - state["bias"][
        "moe"]), np.asarray(new["bias"]["mtp"] - state["bias"]["mtp"])[None]])
    np.testing.assert_allclose(moved, got["bias_move"], atol=1e-7)


def test_init_state_takes_only_the_configured_shapes():
    c = tiny_config()
    d = mla_moe.Dims.from_config(c)
    params, bias = ref.init_params(c, ref.seed_key(SEED))
    state = mla_moe.init_state(d, params, bias)
    assert int(state["count"]) == 0
    assert all(float(jnp.abs(m).max()) == 0.0
               for m in jax.tree.leaves((state["m"], state["v"])))
    params["moe"]["routed"]["w_down"] = params["moe"]["routed"]["w_down"][1:]
    with pytest.raises(ValueError):
        mla_moe.init_state(d, params, bias)


def test_the_reference_initial_state_follows_the_configuration():
    """Norm scales 1, the embedding at ``embed_init_std``, every other
    weight at ``init_std``, the bias 0; the same seed the same state, any
    seed (the benchmark's exceed 32 bits) its own."""
    c = tiny_config(held=8)
    params, bias = ref.init_params(c, ref.seed_key(SEED))
    for path, a in jax.tree.flatten_with_path(params)[0]:
        name = [str(k.key) for k in path]
        if name[-1].endswith("norm"):
            assert float(jnp.abs(a - 1).max()) == 0.0, name
        else:
            std = 1.0 if name[0] == "embed" else 0.02
            assert float(jnp.std(a)) == pytest.approx(std, rel=0.15), name
    assert all(float(jnp.abs(b).max()) == 0.0 for b in jax.tree.leaves(bias))
    again, _ = ref.init_params(c, ref.seed_key(SEED))
    other, _ = ref.init_params(c, ref.seed_key(SEED + 2 ** 32))
    assert np.array_equal(again["head"], params["head"])
    assert not np.array_equal(other["head"], params["head"])


def _absent_expert_computed(monkeypatch, d):
    sound = mla_moe.held_experts_layer

    def layer(x, params, bias, *, held, **kw):
        y, loads = sound(x, params, bias, held=held, **kw)
        first, count = held
        extra, _ = sound(x, params, bias, held=(first + count, count), **kw)
        return y + extra, loads

    monkeypatch.setattr(mla_moe, "held_experts_layer", layer)


def _shared_expert_dropped(monkeypatch, d):
    sound = mla_moe._Model.swiglu

    def swiglu(self, p, x):
        out = sound(self, p, x)
        return out * 0 if p["w_down"].shape[0] == d.expert_ff else out

    monkeypatch.setattr(mla_moe._Model, "swiglu", swiglu)


def _bias_ignored(monkeypatch, d):
    sound = mla_moe.held_experts_layer

    def layer(x, params, bias, **kw):
        return sound(x, params, jnp.zeros_like(bias), **kw)

    monkeypatch.setattr(mla_moe, "held_experts_layer", layer)


def _mtp_skipped(monkeypatch, d):
    def mtp(self, params, bias, h, tokens):
        m = params["mtp"]
        ce = self.cross_entropy(h, m["final_norm"], params["head"],
                                tokens[2:])
        return ce, jnp.zeros((d.experts,), jnp.int32)

    monkeypatch.setattr(mla_moe._Model, "mtp", mtp)


def _rope_off_k_pe(monkeypatch, d):
    sound = mla_moe._rope

    def rope(x, theta):
        return x if x.shape[-2] == 1 else sound(x, theta)

    monkeypatch.setattr(mla_moe, "_rope", rope)


@pytest.mark.parametrize("fault", [
    _shared_expert_dropped, _bias_ignored, _mtp_skipped, _rope_off_k_pe,
    _absent_expert_computed], ids=lambda f: f.__name__.strip("_"))
def test_planted_faults_fail_against_the_reference(case, monkeypatch, fault):
    """Each fault moves a loss or some parameter's gradient past its
    tolerance.  (At this size the attention of random weights is near
    uniform, so RoPE left off ``k_pe`` shows in the gradients of the
    attention weights, not in the losses.)"""
    c, d, state, ids, want = case
    fault(monkeypatch, d)
    (main, mtp), grads = _program_grads(d, state, ids)
    loss = max(abs(main - want["ce_main"]) / want["ce_main"],
               abs(mtp - want["ce_mtp"]) / want["ce_mtp"])
    assert loss > LOSS_TOL or max(_leaf_gaps(grads, want).values()) > \
        GRAD_TOL


# ---------------------------------------------------------------------------
# the chip's share of an expert layer
# ---------------------------------------------------------------------------


def test_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """Two ranks of four experts each: their held parts, with the shared
    expert counted once, add up to the uncut layer — the program's own
    (one call holding all eight) and the plain reference's."""
    c = tiny_config(held=8)
    d = mla_moe.Dims.from_config(c)
    p = _initial(c, d)["params"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)  # the first MoE layer
    x = jax.random.normal(jax.random.key(1), (TOKENS, d.hidden))
    bias = 0.01 * jax.random.normal(jax.random.key(2), (d.experts,))
    r = p["routed"]
    kw = dict(top_k=d.top_k, routed_scaling=d.routed_scaling,
              norm_eps=d.router_eps)

    def share(first, count):
        held = moe.HeldExperts(r["router"],
                               r["w_gate_up"][first:first + count],
                               r["w_down"][first:first + count])
        return moe.held_experts_layer(x, held, bias, held=(first, count),
                                      **kw)

    (y0, loads0), (y1, loads1) = share(0, 4), share(4, 4)
    whole, loads = share(0, 8)
    assert np.array_equal(loads0, loads) and np.array_equal(loads1, loads)
    assert loads[:4].sum() > 0 and loads[4:].sum() > 0  # both ranks work
    np.testing.assert_allclose(y0 + y1, whole, rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(c, p["shared"]["w_gate_up"],
                            p["shared"]["w_down"], x)
        routed, _ = ref.routed_experts(c, r, bias, x)
    scale = float(jnp.max(jnp.abs(routed)))
    # the shared expert counted once, the two shares added: the uncut layer
    layer = shared + routed
    assert float(jnp.max(jnp.abs(shared + y0 + y1 - layer))) < 1e-2 * scale
    # one share alone is not the whole
    assert float(jnp.max(jnp.abs(shared + y0 - layer))) > 1e-1 * scale


def test_routing_is_top_k_of_biased_sigmoid_with_normalised_weights():
    x = jax.random.normal(jax.random.key(3), (16, 8))
    router = jax.random.normal(jax.random.key(4), (8, 6))
    bias = jnp.array([0.0, 5.0, 0.0, 0.0, 0.0, -5.0])
    idx, w, loads = moe.route_sigmoid_topk(x, router, bias, top_k=2,
                                           routed_scaling=2.5, norm_eps=1e-20)
    assert (np.asarray(idx) == 1).any(1).all()      # the bias selects
    assert not (np.asarray(idx) == 5).any()
    s = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(s, idx, 1)
    np.testing.assert_allclose(w, 2.5 * picked / picked.sum(1, keepdims=True),
                               rtol=1e-6)
    assert int(loads.sum()) == 32 and int(loads[1]) == 16


def test_no_token_is_dropped_however_uneven_the_routing():
    """Every token chooses the same experts: the held ones take every
    token, and the buffer holds them all."""
    c = tiny_config(held=4)
    d = mla_moe.Dims.from_config(c)
    state = _initial(c, d)
    r = jax.tree.map(lambda a: a[0], state["params"]["moe"]["routed"])
    x = jnp.ones((TOKENS, d.hidden))
    bias = jnp.array([9.0, 9.0, 0, 0, 0, 0, 0, 0])
    held = moe.HeldExperts(r["router"], r["w_gate_up"], r["w_down"])
    y, loads = moe.held_experts_layer(x, held, bias, held=(0, 4), top_k=2,
                                      routed_scaling=2.5,
                                      norm_eps=d.router_eps)
    assert loads[:2].tolist() == [TOKENS, TOKENS]
    assert moe.held_capacity_rows(TOKENS, 2, 4) >= 2 * TOKENS
    want, _ = ref.routed_experts(c, r, bias, x)
    np.testing.assert_allclose(y, want, rtol=2e-2, atol=2e-2 * float(
        jnp.max(jnp.abs(want))))


def test_grouped_products_interpreted_match_the_jnp_path():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5 * gm.TILE_ROWS, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 64, 32)), jnp.float32)
    tile_expert = jnp.array([0, 0, 1, 2, 2], jnp.int32)
    n_tiles = jnp.array([4], jnp.int32)
    live = 4 * gm.TILE_ROWS

    def loss(x, w, **kw):
        out = gm.grouped_matmul(x, w, tile_expert, n_tiles, **kw)
        return jnp.sum(out[:live] ** 2)

    for kw in ({"interpret": True}, {}):  # the kernel, the jnp path
        out = gm.grouped_matmul(x, w, tile_expert, n_tiles, **kw)
        xb = x.astype(jnp.bfloat16).astype(jnp.float32)
        wb = w.astype(jnp.bfloat16).astype(jnp.float32)
        by_hand = jnp.concatenate(
            [xb[i * 128:(i + 1) * 128] @ wb[int(tile_expert[i])]
             for i in range(4)])
        np.testing.assert_allclose(out[:live], by_hand, rtol=1e-5, atol=1e-4)
        gx, gw = jax.grad(loss, (0, 1))(x, w, **kw)
        if "interpret" in kw:
            first = (gx[:live], gw)
        else:
            np.testing.assert_allclose(gx[:live], first[0], rtol=1e-5,
                                       atol=1e-3)
            np.testing.assert_allclose(gw, first[1], rtol=1e-5, atol=1e-3)


def test_train_plan_counts_the_kernels_a_step_calls():
    d = mla_moe.Dims.from_config(tiny_config())
    assert mla_moe.train_plan(d) == {
        "mla_attention_fwd": 4, "mla_attention_bwd_dq": 4,
        "mla_attention_bwd_dkv": 4, "moe_gmm": 12, "moe_gmm_dx": 6,
        "moe_gmm_dw": 6}


def _kernel_calls(jaxpr, times=1, calls=None):
    """``{kernel name: calls}`` of the ``pallas_call``s in ``jaxpr`` and
    every jaxpr nested in it, a scan's body counted once an iteration."""
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


def _step_program(d, state, ids, policy=mla_moe._LAYER_POLICY):
    """The interpreted kernels' step under a layer rematerialisation
    ``policy`` (None: every value recomputed): its kernel calls by the
    jaxpr, its new state and outputs, and every gradient.  jax keeps what
    it derived of a jitted call for the next trace of the same call
    whatever the enclosing policy, so the caches are cleared around it."""
    old = mla_moe._LAYER_POLICY
    jax.clear_caches()
    try:
        mla_moe._LAYER_POLICY = policy
        step = mla_moe.make_train_step(d, interpret=True)
        calls = _kernel_calls(jax.make_jaxpr(step)(state, ids).jaxpr)
        new, out = jax.jit(step)(state, ids)
        grads = jax.jit(jax.grad(lambda p: mla_moe.loss_and_aux(
            d, p, state["bias"], ids, interpret=True)[0]))(state["params"])
    finally:
        mla_moe._LAYER_POLICY = old
        jax.clear_caches()
    return calls, (new, out, grads)


def test_the_step_calls_each_kernel_as_train_plan_says(case):
    """The attention and expert kernels in the step's program, each as
    often as ``train_plan`` says: the plan cannot drift from the
    program."""
    c, d, state, ids, _ = case
    calls, _ = _step_program(d, state, ids)
    assert dict(calls) == mla_moe.train_plan(d)


def test_keeping_the_attention_partials_changes_no_bit(case):
    """The step that keeps the attention kernel's partials through each
    layer's rematerialisation against the one that recomputes every value:
    the forward kernel runs half as often, and the losses, the gradients,
    the outputs and the updated state are the same bits."""
    c, d, state, ids, _ = case
    kept_calls, kept = _step_program(d, state, ids)
    full_calls, full = _step_program(d, state, ids, policy=None)
    fwd = "mla_attention_fwd"
    assert full_calls[fwd] == 2 * kept_calls[fwd] == 2 * mla_moe.train_plan(
        d)[fwd]
    assert full_calls - Counter({fwd: full_calls[fwd]}) == \
        kept_calls - Counter({fwd: kept_calls[fwd]})
    assert jax.tree.structure(kept) == jax.tree.structure(full)
    for (path, a), b in zip(jax.tree.flatten_with_path(kept)[0],
                            jax.tree.leaves(full)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)


def test_the_step_trains_and_moves_the_bias():
    """Six steps on one sequence: the loss falls, the count advances, the
    selection bias moves by at most its rate a step."""
    c = tiny_config()
    d = mla_moe.Dims.from_config(c)
    step = jax.jit(mla_moe.make_train_step(d))
    state = _initial(c, d)
    losses = []
    for _ in range(6):
        state, out = step(state, _ids(0))
        losses.append(float(out["loss"][0]))
    assert losses[-1] < 0.99 * losses[0]
    assert int(state["count"]) == 6
    moved = np.asarray(state["bias"]["moe"])
    assert np.abs(moved).max() > 0 and np.all(np.abs(moved) <= 6 * 0.001
                                              + 1e-9)


def test_the_first_update_moves_a_weight_by_the_rate():
    """Adam's first update moves a weight by about the learning rate,
    whatever its gradient's size; a weight with no gradient (an id the
    sequence does not hold) moves by its weight decay alone."""
    c = tiny_config()
    d = mla_moe.Dims.from_config(c)
    state = _initial(c, d)
    ids = _ids(0)
    new, _ = jax.jit(mla_moe.make_train_step(d))(state, ids)
    before, after = state["params"]["embed"], new["params"]["embed"]
    # the trunk embeds ids 0..T-1, the MTP module ids 1..T
    seen = np.isin(np.arange(d.vocab), np.asarray(ids[:-1]))
    step = np.abs(np.asarray(after - before)
                  + d.lr * d.weight_decay * np.asarray(before))
    assert seen.any() and not seen.all()
    # a gradient within a few hundred times Adam's eps moves it a little less
    np.testing.assert_allclose(step[seen], d.lr, rtol=5e-2)
    assert float(np.median(step[seen])) == pytest.approx(d.lr, rel=1e-3)
    assert float(np.max(step[~seen])) < 1e-3 * d.lr


# ---------------------------------------------------------------------------
# attention with a value width apart from the query/key width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("impl", ["interpret", "force_jnp"])
def test_qk_width_apart_from_v_width_matches_reference(causal, impl):
    b, t, h, dqk, dv = 1, 256, 2, 48, 32
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (b, t, h, dqk))
    k = jax.random.normal(ks[1], (b, t, h, dqk))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = jax.random.normal(ks[3], (b, t, h, dv))
    scale = 1.0 / math.sqrt(dqk)

    def flash(q, k, v):
        o, _, l = flash_block_partials(q, k, v, None, scale=scale,
                                       causal=causal, name="mla_attention",
                                       **{impl: True})
        return o / jnp.moveaxis(l, 1, 2)[..., None]

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=causal)

    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(plain, q, k, v)
        want_grads = want_vjp(g)
    got, got_vjp = jax.vjp(flash, q, k, v)
    assert got.shape == (b, t, h, dv)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    for a, w in zip(got_vjp(g), want_grads):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, rtol=5e-2, atol=5e-2)


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
    monkeypatch.setattr(gm, "_use_kernel", lambda interpret: True)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    # jitted calls traced at these shapes on the jnp path, or under another
    # rematerialisation policy, are not to be reused
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache)


def _custom_calls(text):
    """The named custom calls of a compiled program (XLA's own, named plain
    ``custom-call``, left out)."""
    calls = Counter(re.sub(r"(\.\d+)+$", "", n) for n in re.findall(
        r"%([\w.\-]+) = [^\n]*custom-call\(", text))
    calls.pop("custom-call", None)
    return calls


def _keep_partials(name):
    return jax.checkpoint_policies.save_only_these_names(
        flash_attention.partials_name(name))


@pytest.mark.parametrize("name,dqk,dv,remat,forwards", [
    pytest.param("mla_attention", 192, 128, None, 1,
                 id="mla_attention-192-128"),
    pytest.param("flash_attention", 128, 128, None, 1,
                 id="flash_attention-128-128"),
    pytest.param("mla_attention", 192, 128, jax.checkpoint, 2,
                 id="mla_attention-192-128-checkpoint"),
    pytest.param("mla_attention", 192, 128,
                 lambda f: jax.checkpoint(
                     f, policy=_keep_partials("mla_attention")), 1,
                 id="mla_attention-192-128-checkpoint_keeping_partials"),
])
def test_attention_kernels_compile_for_v5e(topo, kernels_on, name, dqk, dv,
                                           remat, forwards):
    """Forward and both backward kernels at 8192 tokens and 32 heads, the
    cell's widths (192 against 128) and one width; each named in the
    compiled program as the trace will show it.  Under a plain
    ``jax.checkpoint`` the backward runs the forward kernel again, as ring
    attention's callers get it; under the policy that keeps the kernel's
    partials it does not."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    qk = jax.ShapeDtypeStruct((1, 8192, 32, dqk), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 8192, 32, dv), jnp.bfloat16, sharding=one)

    def attend(q, k, v):
        o, _, l = flash_block_partials(q, k, v, None, scale=0.07,
                                       causal=True, name=name)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(l)

    loss = attend if remat is None else remat(attend)
    # the loss's value too, as a training step reports it: the forward
    # pass's kernel then stays in the program beside any recomputation
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(qk, qk, v)\
        .compile().as_text()
    assert _custom_calls(text) == {f"{name}_fwd": forwards,
                                   f"{name}_bwd_dq": 1, f"{name}_bwd_dkv": 1}


def test_the_tiny_step_compiles_for_v5e_to_train_plan(topo, kernels_on):
    """The tiny step with one MoE layer (its scan runs once, so the
    compiled program names each kernel call once) compiled for the chip:
    every attention and expert kernel as often as ``train_plan`` says."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    c = dict(tiny_config(), num_hidden_layers=2)
    d = mla_moe.Dims.from_config(c)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: _initial(c, d)))
    ids = jax.ShapeDtypeStruct((TOKENS + 2,), jnp.int32, sharding=one)
    text = jax.jit(mla_moe.make_train_step(d)).lower(state, ids).compile()\
        .as_text()
    assert _custom_calls(text) == mla_moe.train_plan(d)


def test_grouped_products_compile_for_v5e(topo, kernels_on):
    """The three grouped products at the cell's widths (16 held experts,
    2048 -> 2 x 768, the dispatch buffer of 8192 tokens)."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    rows = moe.held_capacity_rows(8192, 8, 16)
    x = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((16, 2048, 1536), jnp.float32, sharding=one)
    te = jax.ShapeDtypeStruct((rows // gm.TILE_ROWS,), jnp.int32,
                              sharding=one)
    n = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)

    def loss(x, w, te, n):
        return jnp.sum(gm.grouped_matmul(x, w, te, n) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(x, w, te, n).compile()\
        .as_text()
    assert _custom_calls(text) == {"moe_gmm": 1, "moe_gmm_dx": 1,
                                   "moe_gmm_dw": 1}
