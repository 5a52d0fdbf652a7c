"""The training cell ``joyai_llm_flash_train8k.1chip`` on the CPU at a tiny
size: its files resolve, its driver runs through the harness's own run
and comes out ``correct``, the float8 control and planted faults come out
not correct, its four readers read known sums off a synthetic trace and
nothing where the trace's kernel calls differ from the plan, and the work
arithmetic gives the numbers reckoned by hand at the cell's size.  No time,
rate or share is asserted of a run here: those come from the chip.
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

from chipbench import harness, train_work, trace_reduce  # noqa: E402
from test_chipbench import PEAK, SEED, make_root  # noqa: E402

CELL = "joyai_llm_flash_train8k.1chip"
BENCH = os.path.join(REPO, "chipbench")
READERS = ("train_step_mfu", "mla_attention_roofline",
           "moe_grouped_mm_roofline", "moe_held_load_max_over_mean")


def _config():
    return harness.load_json(os.path.join(BENCH, "configs",
                                          "joyai_llm_flash_train.json"))


def _traffic():
    return harness.load_json(os.path.join(BENCH, "traffic",
                                          "train8k_packed_zipf.json"))


def _tiny():
    """The cell's configuration with every width cut to a test's size
    (hidden 64, 2 heads, 4 of 8 experts held, vocabulary 97) and its mix
    at 32 tokens a step; every other key, the limits among them, as the
    cell has them."""
    config = _config()
    config.update(hidden_size=64, q_lora_rank=48, kv_lora_rank=32,
                  num_attention_heads=2, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
                  moe_intermediate_size=32, n_routed_experts=4,
                  num_experts_per_tok=2, num_hidden_layers=3, vocab_size=97)
    config["deployment"].update(experts_per_layer=8, held_first=0)
    traffic = _traffic()
    traffic.update(tokens_per_step=32, doc_median=8)
    return config, traffic


@pytest.fixture
def root(tmp_path):
    config, traffic = _tiny()
    make_root(str(tmp_path), [("tiny.train", CELL, "tiny_train", config,
                               "tiny_train_mix", traffic, 1)])
    return str(tmp_path)


def run(root, hook=None, traced=False):
    return harness.run("tiny.train", SEED, 0.3, traced, root=root,
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
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert resolved["traffic"]["tokens_per_step"] == 8192


def test_the_configuration_keeps_the_published_widths():
    """Every width as the source publishes it; only depth, the experts
    held and the vocabulary are cut (``reduced``)."""
    config = _config()
    published = {
        "hidden_size": 2048, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "num_attention_heads": 32, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128,
        "intermediate_size": 7168, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "rope_theta": 32000000,
        "rope_interleave": True, "rope_scaling": None,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False}
    assert {k: config[k] for k in published} == published
    assert config["deployment"]["experts_per_layer"] == 256
    assert config["n_routed_experts"] * \
        config["deployment"]["expert_parallel_ranks"] == 256
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]


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
    assert counters["params_here"] == train_work.params_here(
        harness.load_json(os.path.join(root, "chipbench", "configs",
                                       "tiny_train.json")))
    assert result["compared"]["trains"][0] < 0


def test_a_traced_run_on_the_cpu_reads_nothing_off_the_trace(root):
    """The CPU's trace has no device plane: no reader reports, and the
    traced steps' counters are there."""
    result = run(root, traced=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 3
    assert not set(READERS) & set(result["metrics"])
    assert result["metrics"]["sw_compiles_in_window"]["value"] == 0.0
    assert len(result["counters"]["held_loads"]) == 3


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
    program's place: its losses or gradient norms fail."""
    def control(driver):
        driver.control = "float8_e4m3fn"

    result = run(root, control)
    assert result["correct"] is False
    failed = _failed(result)
    assert failed and failed <= {"main_loss_gap", "mtp_loss_gap",
                                 "grad_norm_gap", "routing_grad_norm_gap"}


def _adamw(*, bias_corrected=True, decay_norms=False):
    """The program's AdamW with a planted fault: the moments' bias
    correction left out, or weight decay on the norm scales too."""
    from mpi4jax_tpu.models import mla_moe

    def adamw(d, state, grads):
        count = state["count"] + 1
        n = count.astype(jax.numpy.float32)
        c1 = 1.0 - d.beta1 ** n if bias_corrected else 1.0
        c2 = 1.0 - d.beta2 ** n if bias_corrected else 1.0

        def update(path, p, g, m, v):
            m = d.beta1 * m + (1.0 - d.beta1) * g
            v = d.beta2 * v + (1.0 - d.beta2) * g * g
            step = (m / c1) / (jax.numpy.sqrt(v / c2) + d.adam_eps)
            if decay_norms or not mla_moe._is_norm(path):
                step = step + d.weight_decay * p
            return p - d.lr * step, m, v

        out = jax.tree.map_with_path(update, state["params"], grads,
                                     state["m"], state["v"])
        return [jax.tree.map(lambda t, i=i: t[i], out,
                             is_leaf=lambda t: isinstance(t, tuple))
                for i in range(3)], count
    return adamw


@pytest.mark.parametrize("fault,caught", [
    ({"adamw": _adamw(bias_corrected=False)}, "update_norm_gap"),
    ({"adamw": _adamw(decay_norms=True)}, "update_norm_gap"),
    ({"dims": {"lr": 6e-4}}, "update_norm_gap"),
    ({"dims": {"lr": 1.5e-4}}, "update_norm_gap"),
    ({"dims": {"bias_rate": -0.001}}, "bias_move_gap"),
    ({"dims": {"bias_rate": 0.002}}, "bias_move_gap"),
], ids=["bias_correction_left_out", "norm_scales_decayed", "rate_doubled",
        "rate_halved", "bias_moved_the_wrong_way", "bias_rate_doubled"])
def test_a_wrong_update_is_not_correct(root, monkeypatch, fault, caught):
    """The first step's losses and gradients are right, but the update it
    makes of the parameters or of the selection bias is not the
    reference's."""
    from mpi4jax_tpu.models import mla_moe

    if "adamw" in fault:
        monkeypatch.setattr(mla_moe, "_adamw", fault["adamw"])
    result = run(root, _restart(**fault.get("dims", {})))
    assert result["correct"] is False
    assert caught in _failed(result)


def test_the_mtp_skipped_is_not_correct(root, monkeypatch):
    from mpi4jax_tpu.models import mla_moe

    def mtp(self, params, bias, h, tokens):
        m = params["mtp"]
        return (self.cross_entropy(h, m["final_norm"], params["head"],
                                   tokens[2:]),
                jax.numpy.zeros((self.d.experts,), jax.numpy.int32))

    monkeypatch.setattr(mla_moe._Model, "mtp", mtp)
    result = run(root, _restart())
    assert result["correct"] is False
    value, limit = result["compared"]["mtp_loss_gap"]
    assert value > limit


def test_a_step_that_does_not_update_is_not_correct(root, monkeypatch):
    """The first step's losses and gradients are right, but no step moves
    the parameters: the loss does not fall."""
    from mpi4jax_tpu.models import mla_moe

    def frozen(d, state, grads):
        return (state["params"], state["m"], state["v"]), state["count"] + 1

    monkeypatch.setattr(mla_moe, "_adamw", frozen)
    result = run(root, _restart())
    assert result["correct"] is False
    assert _failed(result) == {"update_norm_gap", "trains"}


def test_the_parent_tree_fails_at_once(root, monkeypatch):
    """A tree without the model stops in ``setup()`` with an exit that is
    not 0, before any program is built."""
    import importlib

    real = importlib.import_module

    def without_model(name, *a, **kw):
        if name == "mpi4jax_tpu.models.mla_moe":
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
    kernels 0.3 s in all, the grouped products 0.1 s, under XLA's numbered
    names; two MoE layers' held loads."""
    config = _config()
    ms = 1_000_000
    plan = {"mla_attention_fwd": 2, "mla_attention_bwd_dq": 1,
            "mla_attention_bwd_dkv": 1, "moe_gmm": 2, "moe_gmm_dx": 1,
            "moe_gmm_dw": 1}
    calls = [("%mla_attention_fwd.3", 50), ("%mla_attention_fwd.7", 50),
             ("%mla_attention_bwd_dq.1", 100),
             ("%mla_attention_bwd_dkv.1", 100),
             ("%moe_gmm.2", 20), ("%moe_gmm.9", 20),
             ("%moe_gmm_dx.4", 30), ("%moe_gmm_dw.4", 30),
             ("%custom-call.5", 5)]
    device, at = [], 10 * ms
    for name, took in calls:
        device.append((f"{name} = bf16[8192,32,128] custom-call(bf16[] %a)",
                       at, took * ms))
        at += (took + 1) * ms
    device.append(("%fusion.1 = f32[8] fusion(f32[] %b)", at, 100 * ms))
    raw = {"devices": {0: device}, "host": [
        (trace_reduce.WINDOW_SPAN, 0, 1200 * ms),
        ("dispatch_step", 0, 5 * ms), ("wait_step", 5 * ms, 995 * ms)]}
    traced = {"mla_attention_fwd": 2.0, "mla_attention_bwd_dq": 1.0,
              "mla_attention_bwd_dkv": 1.0, "moe_gmm": 2.0,
              "moe_gmm_dx": 1.0, "moe_gmm_dw": 1.0, "custom-call": 1.0}
    if not plan_matches:
        traced["moe_gmm"] = 3.0
    counters = {"steps": 1, "tokens_per_step": 8192, "train_plan": plan,
                "traced_kernel_calls_a_step": traced,
                "held_loads": [[[300, 100, 200], [100, 100, 100]]]}
    ctx = {"trace": trace_reduce.reduce_events(raw), "config": config,
           "traffic": _traffic(), "peaks": PEAK, "chips": 1,
           "reduce": trace_reduce, "counters": counters,
           "reader": lambda n: harness.load_module(
               os.path.join(BENCH, "layer_metrics", n + ".py"))}
    return ctx


def _read(ctx, name):
    return ctx["reader"](name).read(ctx)


def test_the_readers_on_a_synthetic_trace():
    ctx = _ctx()
    config = ctx["config"]
    flops = train_work.model_flops_per_step(config, 8192)
    assert _read(ctx, "train_step_mfu") == pytest.approx(
        100 * flops / 1.2 / PEAK["bf16_flops_per_s"])
    attn = train_work.attention_flops_per_step(config, 8192)
    assert _read(ctx, "mla_attention_roofline") == pytest.approx(
        100 * attn / PEAK["bf16_flops_per_s"] / 0.3)
    least = sum(train_work.grouped_mm_floor(
        config, rows, PEAK["bf16_flops_per_s"], PEAK["hbm_bytes_per_s"])
        for rows in (600, 300))
    assert _read(ctx, "moe_grouped_mm_roofline") == pytest.approx(
        100 * least / 0.1)
    assert _read(ctx, "moe_held_load_max_over_mean") == pytest.approx(
        (300 / 200 + 1.0) / 2)


def test_the_readers_read_nothing_where_the_plan_differs():
    ctx = _ctx(plan_matches=False)
    for name in READERS:
        assert _read(ctx, name) is None, name
    ctx = _ctx()
    del ctx["counters"]["traced_kernel_calls_a_step"]
    for name in READERS:
        assert _read(ctx, name) is None, name


# ---------------------------------------------------------------------------
# the work arithmetic at the cell's size
# ---------------------------------------------------------------------------


def test_work_at_the_cell_size_by_hand():
    config = _config()
    # MLA 26.35 M: q_a 3.15, q_b 9.44, kv_a 1.18, kv_b 4.19, o 8.39 (with
    # its two norm scales)
    mla = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
           + 32 * 128 * 2048)
    assert train_work.mla_params(config) == mla + 1536 + 512
    expert = 3 * 2048 * 768
    dense = mla + 3 * 2048 * 7168
    moe_layer = mla + 17 * expert + 2048 * 256
    mtp = 2 * 2048 * 2048 + moe_layer
    vocab = 2 * 16160 * 2048
    norms = 2 * 2048 * 6 + (1536 + 512) * 6 + 2048 * 4
    total = dense + 4 * moe_layer + mtp + vocab + norms
    assert train_work.params_here(config) == total == 680_439_808
    assert 16 * total / 1e9 == pytest.approx(10.89, abs=0.01)
    # 6 N T with the routed experts at top-8 x 16/256, plus causal
    # attention counted once: 15.5 + 12.4 TFLOP
    attention = 3 * 32 * (192 + 128) * 8192 ** 2 * 6
    assert train_work.attention_flops_per_step(config, 8192) == attention
    assert attention / 1e12 == pytest.approx(12.37, abs=0.01)
    flops = train_work.model_flops_per_step(config, 8192)
    assert flops / 1e12 == pytest.approx(27.84, abs=0.01)
    assert (flops - attention) / 1e12 == pytest.approx(15.47, abs=0.01)
    # 100 % of the peak is 0.141 s a step
    assert flops / PEAK["bf16_flops_per_s"] == pytest.approx(0.1413,
                                                             abs=1e-4)
    # the grouped products of 4096 rows: FLOP-bound
    rows = 4096
    flop_s = 18 * rows * 2048 * 768 / PEAK["bf16_flops_per_s"]
    byte_s = 2 * (16 * 3 * 2048 * 768 + 2 * rows * 2048) / \
        PEAK["hbm_bytes_per_s"]
    assert flop_s > byte_s
    assert train_work.grouped_mm_floor(
        config, rows, PEAK["bf16_flops_per_s"], PEAK["hbm_bytes_per_s"]) \
        == pytest.approx(flop_s)
