"""Operation and byte arithmetic of the hybrid training cell: what a step
of a decoder of gated short convolutions, grouped-query attention and
sigmoid-routed experts (LFM2-MoE) has to compute and move, from the
configuration's shapes alone (the companion of ``train_work.py``, which
reads the MLA cell's keys).

These functions are the yardstick's: nothing here imports the program.
The conventions are ``train_work.py``'s:

- **Model FLOPs of a step** (``model_flops_per_step``): 6 x the matmul
  parameters a token meets x the tokens, plus causal attention counted
  once.  A token meets every weight outside the routed experts (the
  convolution layers' in- and out-projections, the attention projections,
  the dense SwiGLU, the routers, the head, which is the embedding: the
  lookup is free) and, of the routed experts, the expected ``top_k x held
  / experts`` of each MoE layer.  The convolution's three taps a channel
  (elementwise, 3 x 2 FLOPs a channel and token) and recomputation do not
  count.
- **Causal attention** (``attention_flops_per_step``): per attention layer
  and query head ``2 x head width x T^2`` for the forward (half of each
  T x T product), three times that for forward and backward; grouped K/V
  changes the bytes, not the FLOPs.
- **Short convolution** (``short_conv_bytes``): the least bytes a kernel
  call moves: the forward reads ``[B | C | x]`` (3 D a token) and writes
  ``y`` (D), in bfloat16, and reads the taps; the backward reads ``[B | C |
  x]`` and ``dy``, writes ``d[B | C | x]``, reads the taps and writes
  ``dw`` (float32).  The rows each tile reads beside it are not counted.
- **Grouped products** (``grouped_mm_floor``): as ``train_work.py``'s, at
  this configuration's expert width.
"""

BF16_BYTES = 2
F32_BYTES = 4
TAPS = 3


def _dims(c: dict) -> dict:
    kinds = {"conv": "conv", "full_attention": "attn"}
    layers = [(kinds[c["layer_types"][i]], i < c["num_dense_layers"])
              for i in c["deployment"]["layers_held"]]
    h, heads = c["hidden_size"], c["num_attention_heads"]
    return {
        "h": h, "heads": heads, "kv": c["num_key_value_heads"],
        "hd": h // heads, "dense_ff": c["intermediate_size"],
        "ff": c["moe_intermediate_size"], "held": c["num_experts"],
        "experts": c["deployment"]["experts_per_layer"],
        "top_k": c["num_experts_per_tok"], "vocab": c["vocab_size"],
        "layers": layers,
        "attn_layers": sum(kind == "attn" for kind, _ in layers),
    }


def conv_params(c: dict) -> int:
    """One short-convolution operator: in-projection, taps, out-projection."""
    h = _dims(c)["h"]
    return h * TAPS * h + TAPS * h + h * h


def attention_params(c: dict) -> int:
    """One attention operator: q, k, v, out and the two per-head norms."""
    d = _dims(c)
    h, hd = d["h"], d["hd"]
    return 2 * h * d["heads"] * hd + 2 * h * d["kv"] * hd + 2 * hd


def expert_params(c: dict) -> int:
    """One SwiGLU expert: gate, up and down."""
    d = _dims(c)
    return 3 * d["h"] * d["ff"]


def params_here(c: dict) -> int:
    """Parameters this chip holds: each layer held (its operator, its two
    norms, the dense SwiGLU or the router and the held experts), the
    embedding slice (the head too) and the final norm."""
    d = _dims(c)
    h = d["h"]
    total = d["vocab"] * h + h
    for kind, dense in d["layers"]:
        total += conv_params(c) if kind == "conv" else attention_params(c)
        total += 2 * h
        total += 3 * h * d["dense_ff"] if dense else (
            d["held"] * expert_params(c) + h * d["experts"])
    return total


def matmul_params_a_token(c: dict) -> float:
    """Matmul weights one token meets in a step (taps and norm scales left
    out)."""
    d = _dims(c)
    h = d["h"]
    total = float(d["vocab"] * h)
    for kind, dense in d["layers"]:
        total += (conv_params(c) - TAPS * h if kind == "conv"
                  else attention_params(c) - 2 * d["hd"])
        total += 3 * h * d["dense_ff"] if dense else (
            h * d["experts"]
            + d["top_k"] * d["held"] / d["experts"] * expert_params(c))
    return total


def attention_flops_per_step(c: dict, tokens: int) -> float:
    d = _dims(c)
    forward = d["heads"] * 2 * d["hd"] * tokens ** 2
    return 3.0 * forward * d["attn_layers"]


def model_flops_per_step(c: dict, tokens: int) -> float:
    return (6.0 * matmul_params_a_token(c) * tokens
            + attention_flops_per_step(c, tokens))


def short_conv_bytes(c: dict, tokens: int) -> dict:
    """Least bytes of one call of each short-convolution kernel."""
    h = _dims(c)["h"]
    acts = BF16_BYTES * tokens * h
    taps = F32_BYTES * TAPS * h
    return {"short_conv_fwd": 4 * acts + taps,
            "short_conv_bwd": 7 * acts + 2 * taps}


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
