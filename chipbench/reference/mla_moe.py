"""Plain reference of the MLA + sigmoid-routed MoE + MTP decoder's first
training step, in float32 ``jax.numpy``: no kernels, no batching, no
cache, and nothing of the program imported.

It follows the published description (DeepSeek-V3, the configuration's
source) read from the configuration's Hugging Face keys, on the same
share of the experts and the vocabulary as the program:

- MLA: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` split ``[nope | rope]``
  a head; ``[c_kv | k_pe] = x W_kva``, ``k_nope, v`` from ``RMSNorm(c_kv)
  W_kvb``; rotary position on interleaved pairs of ``q_pe`` and the one
  shared ``k_pe``; full causal softmax at ``1/sqrt(qk width)`` (a few heads
  at a time, so the scores fit the chip); ``W_o``.
- MoE: ``s = sigmoid(x W_r)`` over every expert, the top-k of ``s + b``,
  weights ``routed_scaling * s / sum``; every held expert's SwiGLU computed
  on every token and weighted by its gate (0 where not chosen); the shared
  SwiGLU expert added.  Experts held elsewhere are left out, as in the
  program.  A layer's loads count, for every expert, the tokens that chose
  it.
- MTP: ``W_eh [RMSNorm(h); RMSNorm(Emb(t_{i+1}))]``, one MLA + MoE layer,
  its norm and the shared head predict ``t_{i+2}``.
- Loss ``CE_main + mtp_loss_weight * CE_mtp``, each a mean over the
  sequence's positions.
- The step: AdamW from zero moments (bias-corrected, decoupled weight
  decay on every weight but the norm scales), float32 parameters; each
  selection bias moved by ``bias_update_rate * sign(mean load - load)``.

The initial state is made here, from the seed and the configuration's
``assumed`` standard deviations (:func:`init_params`), in the layout the
program takes, and handed to both.  Everything runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 product
otherwise runs in bfloat16 passes.  Each decoder layer is rematerialised
(``jax.checkpoint``) so the gradient of a full-size sequence fits one chip
beside the parameters.

The control: a configuration with ``"reference_operands":
"float8_e4m3fn"`` rounds every matmul operand (not the router's, which the
configuration keeps in float32) to that type on the way, the gradient
passing straight through.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

GROUPS = ("embed", "head", "attn", "dense_ffn", "shared", "routed", "router",
          "mtp_proj", "norms")
HEADS_AT_A_TIME = 4


def seed_key(seed: int):
    """A key from a seed of any size (the benchmark's exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def param_shapes(c) -> dict:
    """Every parameter's shape on this share: MoE layers stacked on a
    leading axis, each SwiGLU's gate and up side by side (gate first)."""
    h, f, heads = c["hidden_size"], c["moe_intermediate_size"], \
        c["num_attention_heads"]
    nope, rot, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    attn = {"w_qa": (h, c["q_lora_rank"]), "q_norm": (c["q_lora_rank"],),
            "w_qb": (c["q_lora_rank"], heads * (nope + rot)),
            "w_kva": (h, c["kv_lora_rank"] + rot),
            "kv_norm": (c["kv_lora_rank"],),
            "w_kvb": (c["kv_lora_rank"], heads * (nope + vd)),
            "w_o": (heads * vd, h)}
    held = c["n_routed_experts"]
    block = {"attn_norm": (h,), "attn": attn, "ffn_norm": (h,),
             "shared": {"w_gate_up": (h, 2 * f), "w_down": (f, h)},
             "routed": {"router": (h, c["deployment"]["experts_per_layer"]),
                        "w_gate_up": (held, h, 2 * f),
                        "w_down": (held, f, h)}}
    layers = c["num_hidden_layers"] - 1
    ff = c["intermediate_size"]
    return {
        "embed": (c["vocab_size"], h), "head": (h, c["vocab_size"]),
        "final_norm": (h,),
        "dense": {"attn_norm": (h,), "attn": attn, "ffn_norm": (h,),
                  "ffn": {"w_gate_up": (h, 2 * ff), "w_down": (ff, h)}},
        "moe": jax.tree.map(lambda s: (layers,) + s, block,
                            is_leaf=lambda s: isinstance(s, tuple)),
        "mtp": {"h_norm": (h,), "e_norm": (h,), "eh_proj": (2 * h, h),
                "block": block, "final_norm": (h,)},
    }


def _name(path) -> list:
    return [str(getattr(k, "key", k)) for k in path]


def init_params(c, key):
    """``(params, bias)`` at step 0: norm scales 1, the embedding normal
    with ``assumed.embed_init_std``, every other weight normal with
    ``assumed.init_std``, each leaf from a key of its own; every selection
    bias 0.  ``key`` is an argument, so one compiled program serves every
    seed."""
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
    experts = c["deployment"]["experts_per_layer"]
    bias = {"moe": jnp.zeros((c["num_hidden_layers"] - 1, experts),
                             jnp.float32),
            "mtp": jnp.zeros((experts,), jnp.float32)}
    return params, bias


def group_of(keys) -> str:
    """A parameter's group from its key path (a list of strings)."""
    if keys[-1].endswith("norm"):
        return "norms"
    if keys[0] in ("embed", "head"):
        return keys[0]
    if "attn" in keys:
        return "attn"
    if keys[-1] == "router":
        return "router"
    if "routed" in keys:
        return "routed"
    if "shared" in keys:
        return "shared"
    if "ffn" in keys:
        return "dense_ffn"
    return "mtp_proj"


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def rounded(x, dtype: str):
    """``x`` through ``dtype`` and back; the gradient passes straight."""
    return x.astype(dtype).astype(x.dtype)


rounded.defvjp(lambda x, dtype: (rounded(x, dtype), None),
               lambda dtype, _, g: (g,))


def operands(c, *xs):
    """Matmul operands as the configuration's reference takes them: as
    they are, or rounded for the control."""
    dtype = c.get("reference_operands")
    return xs if dtype is None else tuple(rounded(x, dtype) for x in xs)


def dot(c, a, b):
    a, b = operands(c, a, b)
    return a @ b


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate pairs ``(x[..., 2i], x[..., 2i + 1])`` by ``position *
    theta ** (-2i / width)``; positions along axis 0."""
    t, width = x.shape[0], x.shape[-1]
    i = jnp.arange(width // 2, dtype=jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (-2 * i
                                                                / width)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (width // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    rot_even = even * jnp.cos(ang) - odd * jnp.sin(ang)
    rot_odd = even * jnp.sin(ang) + odd * jnp.cos(ang)
    return jnp.stack([rot_even, rot_odd], -1).reshape(x.shape)


def causal_attention(c, q, k, v):
    """``q``, ``k``: (T, H, Dqk); ``v``: (T, H, Dv); a few heads at a
    time."""
    t, h = q.shape[0], q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    scale = 1.0 / math.sqrt(q.shape[-1])
    step = math.gcd(h, HEADS_AT_A_TIME)

    @jax.checkpoint
    def heads(args):
        qh, kh, vh = operands(c, *args[:2]) + args[2:]
        s = jnp.einsum("qhd,khd->hqk", qh, kh) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        p, vh = operands(c, p, vh)
        return jnp.einsum("hqk,khd->qhd", p, vh)

    def split(a):
        return a.reshape(t, h // step, step, a.shape[-1]).swapaxes(0, 1)

    out = jax.lax.map(heads, (split(q), split(k), split(v)))
    return out.swapaxes(0, 1).reshape(t, h, v.shape[-1])


def mla(c, p, x):
    t, h = x.shape[0], c["num_attention_heads"]
    nope, rot = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    vd, kv_rank, eps = c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"]
    theta = float(c["rope_theta"])
    q = dot(c, rms_norm(dot(c, x, p["w_qa"]), p["q_norm"], eps),
            p["w_qb"]).reshape(t, h, nope + rot)
    kv = dot(c, x, p["w_kva"])
    kvb = dot(c, rms_norm(kv[:, :kv_rank], p["kv_norm"], eps),
              p["w_kvb"]).reshape(t, h, nope + vd)
    k_pe = rope(kv[:, None, kv_rank:], theta)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_pe, (t, h, rot))],
                        -1)
    o = causal_attention(c, q, k, kvb[..., nope:])
    return dot(c, o.reshape(t, h * vd), p["w_o"])


def swiglu(c, w_gate_up, w_down, x):
    gate, up = jnp.split(dot(c, x, w_gate_up), 2, axis=-1)
    return dot(c, jax.nn.silu(gate) * up, w_down)


def routed_experts(c, p, bias, x):
    """The held experts' part of the routed sum, every held expert
    computed on every token and weighted by its gate; and the loads."""
    first, held = c["deployment"]["held_first"], c["n_routed_experts"]
    s = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(s + bias, c["num_experts_per_tok"])
    picked = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                  chosen].set(1.0)
    gates = c["routed_scaling_factor"] * s * picked / jnp.sum(
        s * picked, -1, keepdims=True)
    xe, w_gate_up, w_down = operands(c, x, p["w_gate_up"], p["w_down"])
    gate, up = jnp.split(jnp.einsum("td,edf->etf", xe, w_gate_up), 2, -1)
    (act,) = operands(c, jax.nn.silu(gate) * up)
    out = jnp.einsum("etf,efd->etd", act, w_down)
    y = jnp.einsum("te,etd->td", gates[:, first:first + held], out)
    return y, jnp.sum(picked, 0)


def layer(c, p, bias, x):
    """One decoder layer: ``(x, loads)``; ``bias`` None makes it the dense
    one (loads None)."""
    eps = c["rms_norm_eps"]
    h = x + mla(c, p["attn"], rms_norm(x, p["attn_norm"], eps))
    xn = rms_norm(h, p["ffn_norm"], eps)
    if bias is None:
        return h + swiglu(c, p["ffn"]["w_gate_up"], p["ffn"]["w_down"],
                          xn), None
    routed, loads = routed_experts(c, p["routed"], bias, xn)
    return (h + swiglu(c, p["shared"]["w_gate_up"], p["shared"]["w_down"], xn)
            + routed), loads


def cross_entropy(c, h, norm, head, targets):
    logits = dot(c, rms_norm(h, norm, c["rms_norm_eps"]), head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, targets[:, None],
                                              axis=-1)[:, 0])


def hidden(c, params, bias, tokens):
    """The main model's last hidden state, before its final norm, at the
    first ``T`` positions of ``T + 2`` ids; and the MoE layers' loads."""
    run = jax.checkpoint(lambda p, b, x: layer(c, p, b, x))
    x, _ = run(params["dense"], None, params["embed"][tokens[:-2]])

    def moe(x, pb):
        return run(pb[0], pb[1], x)

    return jax.lax.scan(moe, x, (params["moe"], bias["moe"]))


def losses(c, params, bias, tokens):
    """``((ce_main, ce_mtp), loads)`` of one sequence of ``T + 2`` ids;
    ``loads`` (MoE layers + the MTP block, experts)."""
    t = tokens.shape[0] - 2
    eps = c["rms_norm_eps"]
    x, loads = hidden(c, params, bias, tokens)
    ce_main = cross_entropy(c, x, params["final_norm"], params["head"],
                            tokens[1:t + 1])
    m = params["mtp"]
    joined = jnp.concatenate(
        [rms_norm(x, m["h_norm"], eps),
         rms_norm(params["embed"][tokens[1:t + 1]], m["e_norm"], eps)], -1)
    y, mtp_loads = jax.checkpoint(lambda p, b, x: layer(c, p, b, x))(
        m["block"], bias["mtp"], dot(c, joined, m["eh_proj"]))
    ce_mtp = cross_entropy(c, y, m["final_norm"], params["head"], tokens[2:])
    return (ce_main, ce_mtp), jnp.concatenate([loads, mtp_loads[None]])


def adamw_first_change(c, path, p, g):
    """The change AdamW's first step makes to one float32 parameter: the
    moments from 0, bias-corrected, decoupled weight decay on every weight
    but the norm scales."""
    adam = c["assumed"]["adamw"]
    b1, b2 = float(adam["beta1"]), float(adam["beta2"])
    lr = float(adam["lr"])
    m = (1.0 - b1) * g
    v = (1.0 - b2) * g * g
    step = (m / (1.0 - b1)) / (jnp.sqrt(v / (1.0 - b2)) + float(adam["eps"]))
    if not _name(path)[-1].endswith("norm"):
        step = step + float(adam["weight_decay"]) * p
    return (p - lr * step) - p


def first_step(c, params, bias, tokens) -> dict:
    """One training step of the configuration ``c`` from ``params`` and
    ``bias`` on one sequence: ``{"ce_main", "ce_mtp", "grad_norms": {group:
    norm}, "update_norms": {leaf: norm of its change}, "bias_move": (MoE
    layers + 1, experts)}``."""
    with jax.default_matmul_precision("highest"):
        ce_main, ce_mtp, norms, changes, move = jax.device_get(_first_step(
            json.dumps(c, sort_keys=True), params, bias, tokens))
    return {"ce_main": float(ce_main), "ce_mtp": float(ce_mtp),
            "grad_norms": {n: float(v) for n, v in norms.items()},
            "update_norms": {n: float(v) for n, v in changes.items()},
            "bias_move": move}


# the configuration passed as its JSON text, a static argument: one compiled
# program serves every seed of a process
@functools.partial(jax.jit, static_argnums=0)
def _first_step(config, params, bias, tokens):
    c = json.loads(config)
    weight = float(c["assumed"]["mtp_loss_weight"])
    rate = float(c["assumed"]["bias_update_rate"])

    def objective(p):
        (ce_main, ce_mtp), loads = losses(c, p, bias, tokens)
        return ce_main + weight * ce_mtp, (ce_main, ce_mtp, loads)

    grads, (ce_main, ce_mtp, loads) = jax.grad(objective, has_aux=True)(
        params)
    sq = dict.fromkeys(GROUPS, 0.0)
    changes = {}
    for (path, g), p in zip(jax.tree.flatten_with_path(grads)[0],
                            jax.tree.leaves(params)):
        sq[group_of(_name(path))] += jnp.sum(jnp.square(g))
        changes[jax.tree_util.keystr(path)] = jnp.linalg.norm(
            adamw_first_change(c, path, p, g))
    move = rate * jnp.sign(jnp.mean(loads, -1, keepdims=True) - loads)
    return ce_main, ce_mtp, {n: jnp.sqrt(v) for n, v in sq.items()}, \
        changes, move
