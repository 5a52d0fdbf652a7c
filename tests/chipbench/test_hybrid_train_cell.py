"""The hybrid training cell ``lfm2_24b_a2b_train16k.1chip`` on the CPU at a
tiny size: its files resolve, its driver runs through the harness's own
run and comes out ``correct``, the float8 control, a skipped update and
wrong updates come out not correct, the parent tree stops at once, its
five readers read known sums off a synthetic trace and nothing where the
trace's kernel calls differ from the plan, and the work arithmetic gives
the numbers reckoned by hand at the cell's size.  No time, rate or share is
asserted of a run here: those come from the chip.
"""

import dataclasses
import os
import sys

import pytest

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from chipbench import harness, train_work_hybrid, trace_reduce  # noqa: E402
from test_chipbench import PEAK, SEED, make_root  # noqa: E402

CELL = "lfm2_24b_a2b_train16k.1chip"
BENCH = os.path.join(REPO, "chipbench")
READERS = ("hybrid_train_step_mfu", "short_conv_roofline",
           "gqa_attention_roofline", "hybrid_grouped_mm_roofline",
           "hybrid_held_load_max_over_mean")


def _config():
    return harness.load_json(os.path.join(BENCH, "configs",
                                          "lfm2_24b_a2b_train.json"))


def _traffic():
    return harness.load_json(os.path.join(BENCH, "traffic",
                                          "train16k_packed_zipf.json"))


def _tiny():
    """The cell's configuration with every width cut to a test's size
    (hidden 128, 4 query and 2 K/V heads, 4 of 8 experts held, vocabulary
    97) and its mix at 32 tokens a step; every other key, the layers held
    and the limits among them, as the cell has them."""
    config = _config()
    config.update(hidden_size=128, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=96,
                  moe_intermediate_size=32, num_experts=4,
                  num_experts_per_tok=2, vocab_size=97)
    config["deployment"].update(experts_per_layer=8, held_first=0)
    traffic = _traffic()
    traffic.update(tokens_per_step=32, doc_median=8)
    return config, traffic


def _root(tmp_path, warmup_steps=None):
    config, traffic = _tiny()
    if warmup_steps is not None:
        config["assumed"]["adamw"]["warmup_steps"] = warmup_steps
    make_root(str(tmp_path), [("tiny.hybrid", CELL, "tiny_hybrid", config,
                               "tiny_hybrid_mix", traffic, 1)])
    return str(tmp_path)


@pytest.fixture
def root(tmp_path):
    return _root(tmp_path)


def run(root, hook=None, traced=False):
    return harness.run("tiny.hybrid", SEED, 0.3, traced, root=root,
                       devices=jax.devices()[:1], peaks=PEAK,
                       driver_hook=hook)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------


def test_the_cell_resolves_with_its_metrics():
    resolved = harness.resolve_cell(REPO, CELL)
    assert resolved["cell"]["chips"] == 1
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "steps_per_s_per_chip", "setup_s"}
    assert {m["name"] for m in resolved["per_layer"]} == set(READERS) | {
        "setup_programs_compiled", "setup_build_fetch_s",
        "setup_build_host_s", "sw_idle_share", "sw_compiles_in_window"}
    assert all(m["moves"] == "steps_per_s_per_chip"
               for m in resolved["per_layer"] if m["name"] in READERS)
    config = resolved["config"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 64, "vocab_size": 65536}
    assert resolved["traffic"]["tokens_per_step"] == 16384


def test_the_configuration_keeps_the_published_widths():
    """Every key of the published configuration as published, but the
    depth, the experts held and the vocabulary (``reduced``); the layers
    held are layer 0 and one whole period of the published pattern."""
    config = _config()
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "moe_intermediate_size": 1536,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: config[k] for k in published} == published
    types = config["layer_types"]
    assert len(types) == 40 and types.count("full_attention") == 10
    held = config["deployment"]["layers_held"]
    assert held == [0, 2, 3, 4, 5]
    assert [types[i] for i in held] == ["conv", "full_attention", "conv",
                                        "conv", "conv"]
    assert len(held) == config["num_hidden_layers"]
    assert config["num_experts"] * \
        config["deployment"]["expert_parallel_ranks"] == \
        config["deployment"]["experts_per_layer"] == 64
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["assumed"]["tie_word_embeddings"] is True
    assert config["sizes"]["parameters_here"] == \
        train_work_hybrid.params_here(config)


# ---------------------------------------------------------------------------
# runs through the harness
# ---------------------------------------------------------------------------


def test_a_sound_run_is_correct(root):
    result = run(root)
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == {"steps_per_s_per_chip", "setup_s"}
    counters = result["counters"]
    assert counters["calls"] == {"train_step": result["attempted"]}
    assert counters["dropped"] == 0
    assert counters["params_here"] == train_work_hybrid.params_here(
        harness.load_json(os.path.join(root, "chipbench", "configs",
                                       "tiny_hybrid.json")))
    assert result["compared"]["trains"][0] < 0


def test_a_traced_run_on_the_cpu_reads_nothing_off_the_trace(root):
    """The CPU's trace has no device plane: no reader reports, and the
    traced steps' counters are there (4 MoE layers of 4 held experts)."""
    result = run(root, traced=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 3
    assert not set(READERS) & set(result["metrics"])
    assert result["metrics"]["sw_compiles_in_window"]["value"] == 0.0
    loads = result["counters"]["held_loads"]
    assert len(loads) == 3 and all(len(step) == 4 and
                                   all(len(layer) == 4 for layer in step)
                                   for step in loads)


def _restart(**changes):
    """Start the driver again, its ``dims`` changed by ``changes``."""
    def hook(driver):
        driver.dims = dataclasses.replace(driver.dims, **changes)
        driver.start()
    return hook


def _failed(result):
    return {n for n, (v, lim) in result["compared"].items() if not v <= lim}


def test_the_float8_control_is_not_correct(root):
    """The reference with every matmul operand rounded to float8 e4m3, one
    precision below the bfloat16 the configuration states, in the
    program's place: its loss or gradient norms fail (at this size its
    rounding flips routing too, so its bias move and updates may fail
    beside them)."""
    def control(driver):
        driver.control = "float8_e4m3fn"

    result = run(root, control)
    assert result["correct"] is False
    failed = _failed(result)
    assert failed & {"loss_gap", "grad_norm_gap", "routing_grad_norm_gap"}


def _adamw(*, bias_corrected=True, decay_norms=False):
    """AdamW with a planted fault: the moments' bias correction left out,
    or weight decay on the norm scales too."""
    from mpi4jax_tpu.models import training

    def adamw(d, state, grads, lr):
        count = state["count"] + 1
        n = count.astype(jax.numpy.float32)
        c1 = 1.0 - d.beta1 ** n if bias_corrected else 1.0
        c2 = 1.0 - d.beta2 ** n if bias_corrected else 1.0

        def update(path, p, g, m, v):
            m = d.beta1 * m + (1.0 - d.beta1) * g
            v = d.beta2 * v + (1.0 - d.beta2) * g * g
            step = (m / c1) / (jax.numpy.sqrt(v / c2) + d.adam_eps)
            if decay_norms or not training.is_norm(path):
                step = step + d.weight_decay * p
            return p - lr * step, m, v

        out = jax.tree.map_with_path(update, state["params"], grads,
                                     state["m"], state["v"])
        return [jax.tree.map(lambda t, i=i: t[i], out,
                             is_leaf=lambda t: isinstance(t, tuple))
                for i in range(3)], count
    return adamw


@pytest.mark.parametrize("fault,caught", [
    ({"adamw": _adamw(bias_corrected=False)}, "update_norm_gap"),
    ({"adamw": _adamw(decay_norms=True), "warmup_steps": 1},
     "update_norm_gap"),
    ({"dims": {"lr": 6e-4}}, "update_norm_gap"),
    ({"dims": {"bias_rate": -0.001}}, "bias_move_gap"),
    ({"dims": {"router_eps": 1.0}}, "routing_grad_norm_gap"),
], ids=["bias_correction_left_out", "norm_scales_decayed", "rate_doubled",
        "bias_moved_the_wrong_way", "router_eps_wrong"])
def test_a_wrong_update_is_not_correct(tmp_path, monkeypatch, fault,
                                       caught):
    """The step runs, but what it computes or the update it makes of the
    parameters or of the selection bias is not the reference's.  Norm
    scales decayed are seen here at the full rate: at this size the
    warmup's first step moves a norm scale by about one float32 ulp, and
    the decay by a tenth of that, and reads under ``update_norm_gap``'s
    limit (0.015); at the cell's size it reads 0.031-0.033, over it
    (PERF.md section 2)."""
    from mpi4jax_tpu.models import hybrid_moe

    root = _root(tmp_path, fault.get("warmup_steps"))
    if "adamw" in fault:
        monkeypatch.setattr(hybrid_moe, "_adamw", fault["adamw"])
    result = run(root, _restart(**fault.get("dims", {})))
    assert result["correct"] is False
    assert caught in _failed(result)


def test_a_step_that_does_not_update_is_not_correct(root, monkeypatch):
    """The first step's loss and gradients are right, but no step moves
    the parameters.  (At this size the selection bias's moves alone may
    change the first sequence's loss by more than ``trains`` asks.)"""
    from mpi4jax_tpu.models import hybrid_moe

    def frozen(d, state, grads, lr):
        return (state["params"], state["m"], state["v"]), state["count"] + 1

    monkeypatch.setattr(hybrid_moe, "_adamw", frozen)
    result = run(root, _restart())
    assert result["correct"] is False
    assert "update_norm_gap" in _failed(result)


def test_the_parent_tree_fails_at_once(root, monkeypatch):
    """A tree without the model stops in ``setup()`` with an exit that is
    not 0, before any program is built."""
    import importlib

    real = importlib.import_module

    def without_model(name, *a, **kw):
        if name == "mpi4jax_tpu.models.hybrid_moe":
            raise ImportError(name)
        return real(name, *a, **kw)

    monkeypatch.setattr(importlib, "import_module", without_model)
    with pytest.raises(SystemExit) as stopped:
        run(root)
    assert stopped.value.code not in (0, None)


# ---------------------------------------------------------------------------
# the readers on a synthetic trace
# ---------------------------------------------------------------------------


def _ctx(plan_matches=True):
    """One step of 1 s between its spans on one device: the attention
    kernels 0.3 s in all, the convolution kernels 0.06 s, the grouped
    products 0.1 s, under XLA's numbered names; two MoE layers' held
    loads."""
    ms = 1_000_000
    plan = {"gqa_attention_fwd": 1, "gqa_attention_bwd_dq": 1,
            "gqa_attention_bwd_dkv": 1, "short_conv_fwd": 2,
            "short_conv_bwd": 1, "moe_gmm": 2, "moe_gmm_dx": 1,
            "moe_gmm_dw": 1}
    calls = [("%gqa_attention_fwd.3", 100),
             ("%gqa_attention_bwd_dq.1", 100),
             ("%gqa_attention_bwd_dkv.1", 100),
             ("%short_conv_fwd.2", 10), ("%short_conv_fwd.5", 10),
             ("%short_conv_bwd.1", 40),
             ("%moe_gmm.2", 20), ("%moe_gmm.9", 20),
             ("%moe_gmm_dx.4", 30), ("%moe_gmm_dw.4", 30),
             ("%custom-call.5", 5)]
    device, at = [], 10 * ms
    for name, took in calls:
        device.append((f"{name} = bf16[16384,2048] custom-call(bf16[] %a)",
                       at, took * ms))
        at += (took + 1) * ms
    device.append(("%fusion.1 = f32[8] fusion(f32[] %b)", at, 100 * ms))
    raw = {"devices": {0: device}, "host": [
        (trace_reduce.WINDOW_SPAN, 0, 1200 * ms),
        ("dispatch_step", 0, 5 * ms), ("wait_step", 5 * ms, 995 * ms)]}
    traced = dict({n: float(k) for n, k in plan.items()},
                  **{"custom-call": 1.0})
    if not plan_matches:
        traced["short_conv_bwd"] = 2.0
    counters = {"steps": 1, "tokens_per_step": 16384, "train_plan": plan,
                "traced_kernel_calls_a_step": traced,
                "held_loads": [[[3000, 1000, 2000], [1000, 1000, 1000]]]}
    return {"trace": trace_reduce.reduce_events(raw), "config": _config(),
            "traffic": _traffic(), "peaks": PEAK, "chips": 1,
            "reduce": trace_reduce, "counters": counters,
            "reader": lambda n: harness.load_module(
                os.path.join(BENCH, "layer_metrics", n + ".py"))}


def _read(ctx, name):
    return ctx["reader"](name).read(ctx)


def test_the_readers_on_a_synthetic_trace():
    ctx = _ctx()
    config = ctx["config"]
    flops = train_work_hybrid.model_flops_per_step(config, 16384)
    assert _read(ctx, "hybrid_train_step_mfu") == pytest.approx(
        100 * flops / 1.2 / PEAK["bf16_flops_per_s"])
    attn = train_work_hybrid.attention_flops_per_step(config, 16384)
    assert _read(ctx, "gqa_attention_roofline") == pytest.approx(
        100 * attn / PEAK["bf16_flops_per_s"] / 0.3)
    conv = train_work_hybrid.short_conv_bytes(config, 16384)
    moved = 2 * conv["short_conv_fwd"] + conv["short_conv_bwd"]
    assert _read(ctx, "short_conv_roofline") == pytest.approx(
        100 * moved / PEAK["hbm_bytes_per_s"] / 0.06)
    least = sum(train_work_hybrid.grouped_mm_floor(
        config, rows, PEAK["bf16_flops_per_s"], PEAK["hbm_bytes_per_s"])
        for rows in (6000, 3000))
    assert _read(ctx, "hybrid_grouped_mm_roofline") == pytest.approx(
        100 * least / 0.1)
    assert _read(ctx, "hybrid_held_load_max_over_mean") == pytest.approx(
        (3000 / 2000 + 1.0) / 2)


def test_the_readers_read_nothing_where_the_plan_differs():
    ctx = _ctx(plan_matches=False)
    for name in READERS:
        assert _read(ctx, name) is None, name
    ctx = _ctx()
    del ctx["counters"]["traced_kernel_calls_a_step"]
    for name in READERS:
        assert _read(ctx, name) is None, name
    # the MLA cell's readers do not read this cell's kernels
    ctx = _ctx()
    assert ctx["reader"]("train_kernels").plan_holds(ctx) is False


# ---------------------------------------------------------------------------
# the work arithmetic at the cell's size
# ---------------------------------------------------------------------------


def test_work_at_the_cell_size_by_hand():
    config = _config()
    h = 2048
    conv = 3 * h * h + 3 * h + h * h               # 16.78 M
    attn = 2 * h * 32 * 64 + 2 * h * 8 * 64 + 2 * 64  # 10.49 M
    expert = 3 * h * 1536                          # 9.44 M
    assert (train_work_hybrid.conv_params(config),
            train_work_hybrid.attention_params(config),
            train_work_hybrid.expert_params(config)) == (conv, attn, expert)
    dense = conv + 2 * h + 3 * h * 11776           # layer 0: 89.1 M
    moe_attn = attn + 2 * h + 8 * expert + h * 64  # layer 2: 86.1 M
    moe_conv = conv + 2 * h + 8 * expert + h * 64  # layers 3-5: 92.4 M
    total = dense + moe_attn + 3 * moe_conv + 8192 * h + h
    assert train_work_hybrid.params_here(config) == total == 469_284_992
    assert 16 * total / 1e9 == pytest.approx(7.51, abs=0.01)
    # causal attention counted once: 3 x 32 heads x 2 x 64 x 16384^2
    attention = 3 * 32 * 128 * 16384 ** 2
    assert train_work_hybrid.attention_flops_per_step(config, 16384) == \
        attention
    assert attention / 1e12 == pytest.approx(3.30, abs=0.01)
    # 6 N T with N = 186.1 M matmul weights a token meets (the routed
    # experts at top-4 x 8/64), plus the attention: 21.6 TFLOP
    per_token = 4 * (4 * h * h) + (2 * h * 2048 + 2 * h * 512) \
        + 3 * h * 11776 + 4 * (h * 64 + 4 * 8 / 64 * expert) + 8192 * h
    assert train_work_hybrid.matmul_params_a_token(config) == \
        pytest.approx(per_token)
    flops = train_work_hybrid.model_flops_per_step(config, 16384)
    assert flops == pytest.approx(6 * per_token * 16384 + attention)
    assert flops / 1e12 == pytest.approx(21.60, abs=0.01)
    # the dense layer's two fifths of the model FLOPs (its SwiGLU a third)
    assert 6 * (4 * h * h + 3 * h * 11776) * 16384 / flops == \
        pytest.approx(0.41, abs=0.01)
    assert 6 * 3 * h * 11776 * 16384 / flops == pytest.approx(0.33, abs=0.01)
    # a short-convolution kernel call's bytes at 16384 tokens
    acts = 2 * 16384 * h
    assert train_work_hybrid.short_conv_bytes(config, 16384) == {
        "short_conv_fwd": 4 * acts + 12 * h,
        "short_conv_bwd": 7 * acts + 24 * h}
    # the grouped products of 8192 rows: FLOP-bound
    rows = 8192
    flop_s = 18 * rows * h * 1536 / PEAK["bf16_flops_per_s"]
    byte_s = 2 * (8 * 3 * h * 1536 + 2 * rows * h) / PEAK["hbm_bytes_per_s"]
    assert flop_s > byte_s
    assert train_work_hybrid.grouped_mm_floor(
        config, rows, PEAK["bf16_flops_per_s"], PEAK["hbm_bytes_per_s"]) \
        == pytest.approx(flop_s)
