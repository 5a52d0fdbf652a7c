"""A hybrid decoder of gated short convolutions and grouped-query attention
with sigmoid-routed experts, and its AdamW training step: the share of
one chip in a job whose expert layers are divided over several chips.

The architecture is LFM2-MoE's, read from a configuration dict with the
Hugging Face keys (``hidden_size``, ``layer_types``, ``conv_L_cache``, ...):

- Each decoder layer is ``h = x + op(RMSNorm(x))``, then ``h +
  ffn(RMSNorm(h))``; its ``layer_types`` entry makes ``op`` attention or a
  gated short convolution.
- **Short convolution** (``conv_L_cache`` 3, no bias): ``[B | C | x] = u
  W_in``, ``y = W_out (C * conv3(B * x))`` with a depthwise causal
  convolution of three taps; the middle runs in ``kernels/short_conv.py``
  (kernels ``short_conv_fwd`` / ``short_conv_bwd``), which reads ``u
  W_in`` as one operand.
- **Attention**: grouped-query (``num_key_value_heads`` K/V heads shared by
  the query heads), bias-free projections, RMSNorm over each head of ``q``
  and ``k`` before rotary position on split halves (``rotate_half``),
  causal softmax at ``1/sqrt(head width)``, then ``W_out``.  It runs in
  ``kernels/flash_attention.py`` as ``gqa_attention_fwd`` / ``_bwd_dq`` /
  ``_bwd_dkv``, which read each K/V head once for its group.
- **Feed-forward**: a dense SwiGLU on the first ``num_dense_layers``
  layers; after them ``parallel/moe.py``'s ``held_experts_layer`` — sigmoid
  top-k routing over every expert with a selection bias (the weights
  normalised with the configuration's ``router_norm_eps``), the experts
  this chip holds as grouped products (``moe_gmm``), no shared expert, no
  token dropped.  The bias moves after each step by ``rate * sign(mean
  load - load)``.
- A final RMSNorm and the head, which is the embedding (tied).

**The chip's share** (``deployment`` in the configuration): the layers
``layers_held`` of the published stack (their kinds read from
``layer_types``), experts ``[held_first, held_first + num_experts)`` of each
MoE layer with the router over all ``experts_per_layer``, and a slice of
the vocabulary.  What the absent experts would add is left out.

**Precision** as ``mla_moe`` runs it: float32 parameters, gradients and
Adam's moments; bfloat16 matmul and kernel operands with float32
accumulation; norms, softmax, router, loss and update in float32.  Each
decoder layer is rematerialised in the backward pass but for the attention
kernel's partials, which an attention layer keeps; the cross-entropy runs
in chunks of tokens.  The convolution and the attention run across the
documents a packed sequence holds.

:func:`init_state` wraps the caller's initial parameters in the step-0
training state; :func:`make_train_step` returns the per-rank step for
``mpx.compile`` (state donated); :func:`train_plan` says which kernels a
step calls and how often.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import flash_block_partials, partials_name
from ..kernels.short_conv import TAPS, short_conv
from ..parallel.moe import HeldExperts, held_experts_layer
from . import training
from .training import is_norm, rms_norm

__all__ = [
    "Dims",
    "GRAD_GROUPS",
    "grad_group",
    "init_state",
    "loss_and_aux",
    "make_train_step",
    "param_count",
    "train_plan",
]

GRAD_GROUPS = ("embed", "attn", "conv", "dense_ffn", "routed", "router",
               "norms")
ATTENTION_KERNEL = "gqa_attention"
CONV_KERNEL = "short_conv"
EXPERT_KERNEL = "moe_gmm"
# an attention layer's rematerialisation keeps the kernel's partials, so
# the forward kernel runs once a layer and step
_LAYER_POLICY = jax.checkpoint_policies.save_only_these_names(
    partials_name(ATTENTION_KERNEL))


def _remat_layer(f):
    return jax.checkpoint(f, policy=_LAYER_POLICY)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes and settings a step is built from."""

    hidden: int
    heads: int
    kv_heads: int
    dense_ff: int
    expert_ff: int
    experts: int          # the router's width: every expert of a layer
    held_first: int
    held: int             # experts this chip holds in each MoE layer
    top_k: int
    routed_scaling: float
    router_eps: float
    kinds: tuple          # "conv" or "attn", one a layer held, in order
    dense: tuple          # whether each layer held has the dense SwiGLU
    vocab: int
    rope_theta: float
    eps: float
    bias_rate: float
    lr: float
    beta1: float
    beta2: float
    adam_eps: float
    weight_decay: float
    warmup_steps: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        dep, assumed = c["deployment"], c["assumed"]
        fixed = {"conv_L_cache": TAPS, "conv_bias": False,
                 "norm_topk_prob": True, "use_expert_bias": True}
        for key, want in fixed.items():
            if c.get(key) != want:
                raise ValueError(f"{key} = {c.get(key)!r}: this model is "
                                 f"built for {want!r}")
        if assumed.get("tie_word_embeddings") is not True:
            raise ValueError("this model ties the head to the embedding")
        if c["rope_parameters"].get("rope_type") != "default":
            raise ValueError("only the default rotary position is supported")
        if c["precision"]["matmul_operands"] != "bfloat16":
            raise ValueError("matmul operands are bfloat16 in this model")
        layers = dep["layers_held"]
        if len(layers) != c["num_hidden_layers"]:
            raise ValueError(f"{len(layers)} layers held, "
                             f"num_hidden_layers {c['num_hidden_layers']}")
        kinds = {"conv": "conv", "full_attention": "attn"}
        adam = assumed["adamw"]
        return cls(
            hidden=c["hidden_size"], heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            dense_ff=c["intermediate_size"],
            expert_ff=c["moe_intermediate_size"],
            experts=dep["experts_per_layer"], held_first=dep["held_first"],
            held=c["num_experts"], top_k=c["num_experts_per_tok"],
            routed_scaling=float(c["routed_scaling_factor"]),
            router_eps=float(c["modelling"]["router_norm_eps"]),
            kinds=tuple(kinds[c["layer_types"][i]] for i in layers),
            dense=tuple(i < c["num_dense_layers"] for i in layers),
            vocab=c["vocab_size"],
            rope_theta=float(c["rope_parameters"]["rope_theta"]),
            eps=float(c["norm_eps"]),
            bias_rate=float(assumed["bias_update_rate"]),
            lr=float(adam["lr"]), beta1=float(adam["beta1"]),
            beta2=float(adam["beta2"]), adam_eps=float(adam["eps"]),
            weight_decay=float(adam["weight_decay"]),
            warmup_steps=int(adam["warmup_steps"]))

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def moe_layers(self) -> int:
        return self.dense.count(False)


# ---------------------------------------------------------------------------
# parameters and state
# ---------------------------------------------------------------------------


def _shapes(d: Dims) -> dict:
    """Every parameter's shape (this chip's share), a dict a layer."""
    h, hd, f = d.hidden, d.head_dim, d.expert_ff
    ops = {
        "conv": {"in_proj": (h, TAPS * h), "taps": (TAPS, h),
                 "out_proj": (h, h)},
        "attn": {"q_proj": (h, d.heads * hd), "k_proj": (h, d.kv_heads * hd),
                 "v_proj": (h, d.kv_heads * hd), "q_norm": (hd,),
                 "k_norm": (hd,), "out_proj": (d.heads * hd, h)},
    }
    layers = []
    for kind, dense in zip(d.kinds, d.dense):
        layer = {"op_norm": (h,), kind: ops[kind], "ffn_norm": (h,)}
        if dense:
            layer["ffn"] = {"w_gate_up": (h, 2 * d.dense_ff),
                            "w_down": (d.dense_ff, h)}
        else:
            layer["routed"] = {"router": (h, d.experts),
                               "w_gate_up": (d.held, h, 2 * f),
                               "w_down": (d.held, f, h)}
        layers.append(layer)
    return {"embed": (d.vocab, h), "final_norm": (h,), "layers": layers}


def grad_group(path) -> str:
    """The group of :data:`GRAD_GROUPS` a parameter belongs to, by its key
    path."""
    keys = [str(getattr(k, "key", k)) for k in path]
    if is_norm(path):
        return "norms"
    if keys[0] == "embed":
        return "embed"
    for group in ("attn", "conv"):
        if group in keys:
            return group
    if keys[-1] == "router":
        return "router"
    return "routed" if "routed" in keys else "dense_ffn"


def param_count(d: Dims) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        _shapes(d), is_leaf=lambda s: isinstance(s, tuple)))


def init_state(d: Dims, params, bias) -> dict:
    """The training state at step 0 around the caller's initial ``params``
    (float32, this chip's share: ``{"embed", "final_norm", "layers": [a
    dict a layer]}``) and selection ``bias`` (``(moe_layers, experts)``):
    Adam's ``m`` and ``v`` at 0 and ``count`` 0.  Raises where a shape is
    not the configuration's."""
    want = {"params": _shapes(d), "bias": (d.moe_layers, d.experts)}
    got = jax.tree.map(lambda a: tuple(a.shape),
                       {"params": params, "bias": bias})
    if got != want:
        raise ValueError("the initial parameters or bias do not have this "
                         "configuration's shapes")
    return {"params": params, "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32), "bias": bias}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _rope(x, theta):
    """Rotary position on the two halves of the last axis (``x1 cos - x2
    sin``, ``x2 cos + x1 sin``); positions along axis 0."""
    t, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * freq).reshape(
        (t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class _Model:
    """The forward pass of one configuration."""

    mm = staticmethod(training.mm)

    def __init__(self, d: Dims, interpret: bool):
        self.d = d
        self.interpret = interpret

    def conv(self, p, u):
        bcx = self.mm(u, p["in_proj"]).astype(jnp.bfloat16)
        return self.mm(short_conv(bcx, p["taps"], interpret=self.interpret),
                       p["out_proj"])

    def attention(self, p, u):
        d, t = self.d, u.shape[0]
        hd = d.head_dim
        q = rms_norm(self.mm(u, p["q_proj"]).reshape(t, d.heads, hd),
                     p["q_norm"], d.eps)
        k = rms_norm(self.mm(u, p["k_proj"]).reshape(t, d.kv_heads, hd),
                     p["k_norm"], d.eps)
        v = self.mm(u, p["v_proj"]).reshape(t, d.kv_heads, hd)
        q, k = _rope(q, d.rope_theta), _rope(k, d.rope_theta)
        q, k, v = (a.astype(jnp.bfloat16)[None] for a in (q, k, v))
        o_part, _, l = flash_block_partials(
            q, k, v, None, scale=1.0 / math.sqrt(hd), causal=True,
            name=ATTENTION_KERNEL, interpret=self.interpret)
        o = o_part / jnp.moveaxis(jnp.where(l == 0.0, 1.0, l), 1, 2)[..., None]
        return self.mm(o[0].reshape(t, d.heads * hd), p["out_proj"])

    def swiglu(self, p, x):
        gate, up = jnp.split(self.mm(x, p["w_gate_up"]), 2, axis=-1)
        return self.mm(jax.nn.silu(gate) * up, p["w_down"])

    def layer(self, p, bias, x):
        """One decoder layer; ``bias`` None makes it a dense one.  Returns
        ``(x, loads)``."""
        d = self.d
        u = rms_norm(x, p["op_norm"], d.eps)
        op = self.attention(p["attn"], u) if "attn" in p else \
            self.conv(p["conv"], u)
        h = x + op
        xn = rms_norm(h, p["ffn_norm"], d.eps)
        if bias is None:
            return h + self.swiglu(p["ffn"], xn), None
        r = p["routed"]
        routed, loads = held_experts_layer(
            xn, HeldExperts(r["router"], r["w_gate_up"], r["w_down"]),
            bias, held=(d.held_first, d.held), top_k=d.top_k,
            routed_scaling=d.routed_scaling, norm_eps=d.router_eps,
            interpret=self.interpret)
        return h + routed, loads

    def cross_entropy(self, h, params, targets):
        """Mean cross-entropy over the slice's logits (the head is the
        embedding), a chunk of tokens at a time."""
        norm, embed = params["final_norm"], params["embed"]
        return training.cross_entropy(
            h, targets,
            lambda hc: self.mm(rms_norm(hc, norm, self.d.eps), embed.T))

    def losses(self, params, bias, tokens):
        """``tokens``: ``(T + 1,)`` ids of the slice; the model reads the
        first ``T`` and predicts ``t_{i+1}``.  Returns ``(ce, loads)``,
        ``loads`` ``(moe_layers, experts)``."""
        x = params["embed"][tokens[:-1]]
        loads = []
        for p, dense in zip(params["layers"], self.d.dense):
            if dense:
                x, _ = _remat_layer(lambda p, x: self.layer(p, None, x))(p, x)
            else:
                x, layer_loads = _remat_layer(self.layer)(
                    p, bias[len(loads)], x)
                loads.append(layer_loads)
        return self.cross_entropy(x, params, tokens[1:]), jnp.stack(loads)


def loss_and_aux(d: Dims, params, bias, tokens, *, interpret=False):
    """``(loss, loads)`` of one sequence of ``T + 1`` ids."""
    return _Model(d, interpret).losses(params, bias, tokens)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


# the module's own name, so that a test can put a planted fault in its place
_adamw = training.adamw


def make_train_step(d: Dims, *, interpret=False):
    """The per-rank training step ``step(state, tokens) -> (state, out)``:
    one AdamW step on one sequence of ``T + 1`` ids, at the learning rate
    of a linear warmup over ``warmup_steps``, and the selection bias moved
    by the loads it saw.  ``out``: ``loss`` (one value),
    ``grad_norms`` (one a group of :data:`GRAD_GROUPS`), ``loads``
    (moe_layers, experts) and ``dropped`` (held assignments the dispatch
    could not place: 0 by construction).  Pin it with
    ``mpx.compile(step, state, tokens, donate_argnums=(0,))``."""

    def train_step(state, tokens):
        (loss, loads), grads = jax.value_and_grad(
            lambda p: loss_and_aux(d, p, state["bias"], tokens,
                                   interpret=interpret),
            has_aux=True)(state["params"])
        (params, m, v), count = _adamw(
            d, state, grads, training.warmup_lr(d, state["count"] + 1))
        bias = state["bias"] + training.bias_move(loads, d.bias_rate)
        out = {"loss": loss[None],
               "grad_norms": training.group_norms(grads, grad_group,
                                                  GRAD_GROUPS),
               "loads": loads,
               "dropped": training.dropped(loads, d.held_first, d.held,
                                           tokens.shape[0] - 1, d.top_k)}
        return ({"params": params, "m": m, "v": v, "count": count,
                 "bias": bias}, out)

    return train_step


def train_plan(d: Dims) -> dict:
    """Kernel calls one step makes, by kernel name: each attention layer's
    forward once (its rematerialisation keeps the kernel's partials) and
    its two backward kernels once; each convolution layer's forward twice
    (the pass and the rematerialisation) and its backward once; each MoE
    layer's two grouped products twice, their input and weight gradients
    once."""
    attn, conv = d.kinds.count("attn"), d.kinds.count("conv")
    moe = d.moe_layers
    plan = {f"{ATTENTION_KERNEL}_fwd": attn,
            f"{ATTENTION_KERNEL}_bwd_dq": attn,
            f"{ATTENTION_KERNEL}_bwd_dkv": attn,
            f"{CONV_KERNEL}_fwd": 2 * conv, f"{CONV_KERNEL}_bwd": conv,
            EXPERT_KERNEL: 4 * moe, f"{EXPERT_KERNEL}_dx": 2 * moe,
            f"{EXPERT_KERNEL}_dw": 2 * moe}
    return {name: n for name, n in plan.items() if n}
