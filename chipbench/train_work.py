"""Operation and byte arithmetic of the training cells: what a step of an
MLA + MoE + MTP decoder has to compute and move, from the configuration's
shapes alone (the companion of ``work.py``, which holds the solver's and
the collectives').

These functions are the yardstick's: nothing here imports the program.
The conventions:

- **Model FLOPs of a step** (``model_flops_per_step``): 6 x the matmul
  parameters a token meets x the tokens (forward 2, backward 4), plus
  causal attention counted once.  A token meets every weight outside the
  routed experts (MLA, the dense FFN, the shared experts, the routers,
  the MTP projection, the head twice: main and MTP; the embedding is a
  lookup) and, of the routed experts, the expected ``top_k x held /
  experts`` of each MoE layer.  Recomputation does not count.
- **Causal attention** (``attention_flops_per_step``): per layer and
  head ``(qk width + v width) x T^2`` for the forward (half of each T x T
  product), three times that for forward and backward.
- **Grouped products** (``grouped_mm_floor``): for ``rows`` routed rows
  through one MoE layer's held experts in a step, the FLOP floor is three
  times the forward's ``2 x rows x 3 x hidden x expert_ff``; the byte
  floor reads the held experts' weights once and the routed rows in and
  out once, in bfloat16.
"""

BF16_BYTES = 2


def _dims(c: dict) -> dict:
    return {
        "h": c["hidden_size"], "heads": c["num_attention_heads"],
        "qk": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        "v": c["v_head_dim"], "rope": c["qk_rope_head_dim"],
        "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
        "dense_ff": c["intermediate_size"],
        "ff": c["moe_intermediate_size"], "held": c["n_routed_experts"],
        "experts": c["deployment"]["experts_per_layer"],
        "top_k": c["num_experts_per_tok"],
        "moe_layers": c["num_hidden_layers"] - c["first_k_dense_replace"]
        + c["num_nextn_predict_layers"],
        "attn_layers": c["num_hidden_layers"]
        + c["num_nextn_predict_layers"],
        "vocab": c["vocab_size"],
    }


def mla_params(c: dict) -> int:
    """Weights of one MLA block (its norm scales included)."""
    d = _dims(c)
    return (d["h"] * d["q_rank"] + d["q_rank"]
            + d["q_rank"] * d["heads"] * d["qk"]
            + d["h"] * (d["kv_rank"] + d["rope"]) + d["kv_rank"]
            + d["kv_rank"] * d["heads"] * (d["qk"] - d["rope"] + d["v"])
            + d["heads"] * d["v"] * d["h"])


def expert_params(c: dict) -> int:
    """One SwiGLU expert: gate, up and down."""
    d = _dims(c)
    return 3 * d["h"] * d["ff"]


def params_here(c: dict) -> int:
    """Parameters this chip holds: the dense layer, the MoE layers with
    their held experts, the MTP module, the vocabulary slice (embedding
    and head), every norm scale."""
    d = _dims(c)
    h = d["h"]
    block_norms = 2 * h
    dense = mla_params(c) + 3 * h * d["dense_ff"] + block_norms
    moe = (mla_params(c) + block_norms + expert_params(c) * (1 + d["held"])
           + h * d["experts"])
    mtp = 2 * h * h + 3 * h + moe
    vocab = 2 * d["vocab"] * h + h
    return dense + (d["moe_layers"] - 1) * moe + mtp + vocab


def matmul_params_a_token(c: dict) -> float:
    """Matmul weights one token meets in a step (norm scales left out)."""
    d = _dims(c)
    h = d["h"]
    mla = mla_params(c) - d["q_rank"] - d["kv_rank"]
    routed = d["top_k"] * d["held"] / d["experts"] * expert_params(c)
    per_moe = mla + expert_params(c) + h * d["experts"] + routed
    dense = mla + 3 * h * d["dense_ff"]
    return dense + d["moe_layers"] * per_moe + 2 * h * h + 2 * d["vocab"] * h


def attention_flops_per_step(c: dict, tokens: int) -> float:
    d = _dims(c)
    forward = d["heads"] * (d["qk"] + d["v"]) * tokens ** 2
    return 3.0 * forward * d["attn_layers"]


def model_flops_per_step(c: dict, tokens: int) -> float:
    return (6.0 * matmul_params_a_token(c) * tokens
            + attention_flops_per_step(c, tokens))


def grouped_mm_floor(c: dict, rows: int, peak_flops: float,
                     peak_bytes_per_s: float) -> float:
    """Least seconds one MoE layer's grouped products (forward and both
    gradients) take for ``rows`` routed rows: the larger of the FLOP
    floor and the byte floor."""
    d = _dims(c)
    flops = 3 * 2 * rows * 3 * d["h"] * d["ff"]
    moved = BF16_BYTES * (d["held"] * 3 * d["h"] * d["ff"]
                          + 2 * rows * d["h"])
    return max(flops / peak_flops, moved / peak_bytes_per_s)
