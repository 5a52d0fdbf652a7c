"""Plain reference of the hybrid decoder's first training step (gated short
convolutions and grouped-query attention with sigmoid-routed experts,
LFM2-MoE), in float32 ``jax.numpy``: no kernels, no batching, no cache,
and nothing of the program imported.

It follows the published description (the transformers ``lfm2_moe``
modelling, the configuration's source) read from the configuration's
Hugging Face keys, on the same share of the layers, the experts and the
vocabulary as the program (``deployment.layers_held``, their kinds from
``layer_types``):

- A layer: ``h = x + op(RMSNorm(x))``, ``out = h + ffn(RMSNorm(h))``.
- Short convolution: ``[B | C | x] = u W_in``; ``z = B * x``; ``c_t =
  w[0] z_{t-2} + w[1] z_{t-1} + w[2] z_t`` (zeros before the first
  position); ``W_out (C * c)``.
- Attention: ``q``, ``k``, ``v`` projections, RMSNorm over each head of
  ``q`` and ``k``, rotary position on split halves (``rotate_half``),
  each query head attending with K/V head ``h // (heads / kv heads)``, full
  causal softmax at ``1/sqrt(head width)`` (one head and a block of
  queries at a time, so the scores fit the chip), ``W_out``.
- Feed-forward: a dense SwiGLU on the leading dense layers; else ``s =
  sigmoid(x W_r)`` over every expert, the top-k of ``s + b``, weights
  ``routed_scaling * s / (sum + router_norm_eps)``, every held expert's
  SwiGLU computed on every token and weighted by its gate (0 where not
  chosen; one expert at a time).  Experts held elsewhere are left out, as
  in the program.  A layer's loads count, for every expert, the tokens
  that chose it.
- A final RMSNorm and the head, the embedding itself; the loss is the mean
  cross-entropy of ``t_{i+1}`` over the sequence's positions.
- The step: AdamW from zero moments at the first step's rate of the
  configuration's linear warmup (``lr / warmup_steps``), and the bias move,
  as ``reference/mla_moe.py`` makes them (its helpers are shared here).

Departure from the source, as in the program: in a packed sequence the
convolution and the attention run across document boundaries.

Everything runs under ``jax.default_matmul_precision("highest")``; each
decoder layer is rematerialised (``jax.checkpoint``) so the gradient of a
full-size sequence fits one chip beside the parameters.  The control: a
configuration with ``"reference_operands": "float8_e4m3fn"`` rounds every
matmul operand (not the router's) to that type on the way.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

from chipbench.reference.mla_moe import (  # noqa: F401 - seed_key re-exported
    adamw_first_change, dot, operands, rms_norm, seed_key, swiglu)

GROUPS = ("embed", "attn", "conv", "dense_ffn", "routed", "router", "norms")
TAPS = 3
QUERY_ROWS = 4096


def layers_held(c) -> list:
    """``(kind, dense)`` of each layer held: kind ``"conv"`` or ``"attn"``."""
    kinds = {"conv": "conv", "full_attention": "attn"}
    return [(kinds[c["layer_types"][i]], i < c["num_dense_layers"])
            for i in c["deployment"]["layers_held"]]


def param_shapes(c) -> dict:
    """Every parameter's shape on this share, a dict a layer; each
    SwiGLU's gate and up side by side (gate first)."""
    h, heads, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, f, held = h // heads, c["moe_intermediate_size"], c["num_experts"]
    ops = {
        "conv": {"in_proj": (h, TAPS * h), "taps": (TAPS, h),
                 "out_proj": (h, h)},
        "attn": {"q_proj": (h, heads * hd), "k_proj": (h, kv * hd),
                 "v_proj": (h, kv * hd), "q_norm": (hd,), "k_norm": (hd,),
                 "out_proj": (heads * hd, h)},
    }
    layers = []
    for kind, dense in layers_held(c):
        layer = {"op_norm": (h,), kind: ops[kind], "ffn_norm": (h,)}
        if dense:
            ff = c["intermediate_size"]
            layer["ffn"] = {"w_gate_up": (h, 2 * ff), "w_down": (ff, h)}
        else:
            layer["routed"] = {
                "router": (h, c["deployment"]["experts_per_layer"]),
                "w_gate_up": (held, h, 2 * f), "w_down": (held, f, h)}
        layers.append(layer)
    return {"embed": (c["vocab_size"], h), "final_norm": (h,),
            "layers": layers}


def _name(path) -> list:
    return [str(getattr(k, "key", k)) for k in path]


def moe_layers(c) -> int:
    return sum(not dense for _, dense in layers_held(c))


def init_params(c, key):
    """``(params, bias)`` at step 0: norm scales 1, the embedding normal
    with ``assumed.embed_init_std``, every other weight normal with
    ``assumed.init_std``, each leaf from a key of its own; the selection
    bias ``(MoE layers, experts)`` 0."""
    leaves, tree = jax.tree.flatten_with_path(
        param_shapes(c), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    assumed = c["assumed"]

    def init(path, shape, k):
        name = _name(path)
        if name[-1].endswith("norm"):
            return jnp.ones(shape, jnp.float32)
        std = assumed["embed_init_std"] if name[0] == "embed" \
            else assumed["init_std"]
        return float(std) * jax.random.normal(k, shape, jnp.float32)

    params = jax.tree.unflatten(tree, [init(p, s, k) for (p, s), k
                                       in zip(leaves, keys)])
    bias = jnp.zeros((moe_layers(c), c["deployment"]["experts_per_layer"]),
                     jnp.float32)
    return params, bias


def group_of(keys) -> str:
    """A parameter's group from its key path (a list of strings)."""
    if keys[-1].endswith("norm"):
        return "norms"
    if keys[0] == "embed":
        return "embed"
    for group in ("attn", "conv"):
        if group in keys:
            return group
    if keys[-1] == "router":
        return "router"
    return "routed" if "routed" in keys else "dense_ffn"


def rope(x, theta):
    """Rotate the halves ``(x[..., i], x[..., i + width/2])`` by ``position
    * theta ** (-2i / width)``; positions along axis 0."""
    t, width = x.shape[0], x.shape[-1]
    i = jnp.arange(width // 2, dtype=jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (-2 * i
                                                                / width)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (width // 2,))
    first, second = x[..., :width // 2], x[..., width // 2:]
    return jnp.concatenate([first * jnp.cos(ang) - second * jnp.sin(ang),
                            second * jnp.cos(ang) + first * jnp.sin(ang)],
                           -1)


def causal_attention(c, q, k, v):
    """``q``: (T, H, D); ``k``, ``v``: (T, Hkv, D); query head ``h`` with
    K/V head ``h // (H / Hkv)``; one head and ``QUERY_ROWS`` queries at a
    time."""
    t, h, width = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    rows = QUERY_ROWS if t % QUERY_ROWS == 0 else t
    scale = 1.0 / math.sqrt(width)

    def head(args):
        qh, kh, vh = args

        @jax.checkpoint
        def block(qs):
            qb, start = qs
            qb, ke = operands(c, qb, kh)
            s = qb @ ke.T * scale
            seen = (start + jnp.arange(rows))[:, None] >= jnp.arange(t)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            p, ve = operands(c, p, vh)
            return p @ ve

        starts = jnp.arange(0, t, rows)
        return jax.lax.map(block, (qh.reshape(-1, rows, width), starts))\
            .reshape(t, width)

    out = jax.lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                             v.swapaxes(0, 1)))
    return out.swapaxes(0, 1)


def attention(c, p, x):
    t, heads, kv = x.shape[0], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, eps = c["hidden_size"] // heads, c["norm_eps"]
    theta = float(c["rope_parameters"]["rope_theta"])
    q = rms_norm(dot(c, x, p["q_proj"]).reshape(t, heads, hd), p["q_norm"],
                 eps)
    k = rms_norm(dot(c, x, p["k_proj"]).reshape(t, kv, hd), p["k_norm"], eps)
    v = dot(c, x, p["v_proj"]).reshape(t, kv, hd)
    o = causal_attention(c, rope(q, theta), rope(k, theta), v)
    return dot(c, o.reshape(t, heads * hd), p["out_proj"])


def shift(z, k):
    """``z`` moved ``k`` positions later, zeros before the first."""
    return jnp.concatenate([jnp.zeros((k,) + z.shape[1:], z.dtype),
                            z[:z.shape[0] - k]])


def short_conv(c, p, x):
    b, gate_c, xt = jnp.split(dot(c, x, p["in_proj"]), TAPS, -1)
    z = b * xt
    w = p["taps"]
    conv = w[0] * shift(z, 2) + w[1] * shift(z, 1) + w[2] * z
    return dot(c, gate_c * conv, p["out_proj"])


def routed_experts(c, p, bias, x):
    """The held experts' part of the routed sum, every held expert
    computed on every token (one expert at a time) and weighted by its
    gate; and the loads."""
    first, held = c["deployment"]["held_first"], c["num_experts"]
    s = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(s + bias, c["num_experts_per_tok"])
    picked = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                  chosen].set(1.0)
    gates = float(c["routed_scaling_factor"]) * s * picked / (
        jnp.sum(s * picked, -1, keepdims=True)
        + float(c["modelling"]["router_norm_eps"]))

    @jax.checkpoint
    def expert(y, weights):
        w_gate_up, w_down, gate = weights
        return y + gate[:, None] * swiglu(c, w_gate_up, w_down, x), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["w_gate_up"], p["w_down"],
                         gates[:, first:first + held].T))
    return y, jnp.sum(picked, 0)


def layer(c, p, bias, x):
    """One decoder layer: ``(x, loads)``; ``bias`` None makes it a dense
    one (loads None)."""
    eps = c["norm_eps"]
    u = rms_norm(x, p["op_norm"], eps)
    h = x + (attention(c, p["attn"], u) if "attn" in p
             else short_conv(c, p["conv"], u))
    xn = rms_norm(h, p["ffn_norm"], eps)
    if bias is None:
        return h + swiglu(c, p["ffn"]["w_gate_up"], p["ffn"]["w_down"],
                          xn), None
    routed, loads = routed_experts(c, p["routed"], bias, xn)
    return h + routed, loads


def hidden(c, params, bias, tokens):
    """The last hidden state, before the final norm, at the first ``T``
    positions of ``T + 1`` ids; and the MoE layers' loads."""
    run = jax.checkpoint(lambda p, b, x: layer(c, p, b, x))
    x = params["embed"][tokens[:-1]]
    loads = []
    for p, (_, dense) in zip(params["layers"], layers_held(c)):
        x, layer_loads = run(p, None if dense else bias[len(loads)], x)
        if not dense:
            loads.append(layer_loads)
    return x, jnp.stack(loads)


def losses(c, params, bias, tokens):
    """``(ce, loads)`` of one sequence of ``T + 1`` ids; ``loads`` (MoE
    layers, experts)."""
    x, loads = hidden(c, params, bias, tokens)
    logits = dot(c, rms_norm(x, params["final_norm"], c["norm_eps"]),
                 params["embed"].T)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ce = jnp.mean(lse - jnp.take_along_axis(logits, tokens[1:, None],
                                            axis=-1)[:, 0])
    return ce, loads


def first_step(c, params, bias, tokens) -> dict:
    """One training step of the configuration ``c`` from ``params`` and
    ``bias`` on one sequence of ``T + 1`` ids: ``{"ce", "grad_norms":
    {group: norm}, "update_norms": {leaf: norm of its change},
    "bias_move": (MoE layers, experts)}``."""
    with jax.default_matmul_precision("highest"):
        ce, norms, changes, move = jax.device_get(_first_step(
            json.dumps(c, sort_keys=True), params, bias, tokens))
    return {"ce": float(ce),
            "grad_norms": {n: float(v) for n, v in norms.items()},
            "update_norms": {n: float(v) for n, v in changes.items()},
            "bias_move": move}


# the configuration passed as its JSON text, a static argument: one compiled
# program serves every seed of a process
@functools.partial(jax.jit, static_argnums=0)
def _first_step(config, params, bias, tokens):
    c = json.loads(config)
    rate = float(c["assumed"]["bias_update_rate"])
    adam = c["assumed"]["adamw"]
    # the first step's learning rate under the linear warmup
    adam["lr"] = float(adam["lr"]) * min(1.0, 1.0 / adam["warmup_steps"])
    (ce, loads), grads = jax.value_and_grad(
        lambda p: losses(c, p, bias, tokens), has_aux=True)(params)
    sq = dict.fromkeys(GROUPS, 0.0)
    changes = {}
    for (path, g), p in zip(jax.tree.flatten_with_path(grads)[0],
                            jax.tree.leaves(params)):
        sq[group_of(_name(path))] += jnp.sum(jnp.square(g))
        changes[jax.tree_util.keystr(path)] = jnp.linalg.norm(
            adamw_first_change(c, path, p, g))
    move = rate * jnp.sign(jnp.mean(loads, -1, keepdims=True) - loads)
    return ce, {n: jnp.sqrt(v) for n, v in sq.items()}, changes, move
