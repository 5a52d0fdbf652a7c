"""A decoder of latent attention and sigmoid-routed experts with one
multi-token-prediction module, and its AdamW training step: the share of
one chip in a job whose expert layers are divided over several chips.

The architecture is the DeepSeek-V3 family's, read from a configuration
dict with the Hugging Face keys (``hidden_size``, ``q_lora_rank``, ...):

- **MLA** (training form, not absorbed): ``c_q = RMSNorm(x W_qa)``,
  ``[q_nope | q_pe] = c_q W_qb`` a head; ``[c_kv | k_pe] = x W_kva``,
  ``c_kv <- RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` a head; ``q_pe``
  and the one shared ``k_pe`` rotated on interleaved pairs; causal softmax
  of ``[q_nope|q_pe] . [k_nope|k_pe] / sqrt(qk width)`` applied to ``v``,
  then ``W_o``.  The attention runs in ``kernels/flash_attention.py``
  (kernels ``mla_attention_fwd`` / ``_bwd_dq`` / ``_bwd_dkv``), whose value
  width differs from its query/key width.
- **Experts**: layer 0 a dense SwiGLU; the others a shared SwiGLU expert
  plus ``parallel/moe.py``'s ``held_experts_layer`` — sigmoid top-k
  routing over every expert with a selection bias, the experts this chip
  holds as grouped products (``moe_gmm``), no token dropped.  The bias
  moves after each step by ``rate * sign(mean load - load)``.
- **MTP** (DeepSeek-V3 section 2.2): ``h' = W_eh [RMSNorm(h_i);
  RMSNorm(Emb(t_{i+1}))]``, one MLA + MoE block, a norm and the shared
  head predict ``t_{i+2}``.  The loss is ``CE_main + weight * CE_mtp``.

**The chip's share** (``deployment`` in the configuration): the router
keeps every expert's column; this chip holds experts ``[first, first +
n_routed_experts)`` of each MoE layer and a slice of the vocabulary (ids,
embedding, head, logits and losses over the slice).  What the absent
experts would add is left out.

**Precision** as a training job runs it: parameters, gradients and Adam's
moments in float32; matmul operands in bfloat16 (``precision.
matmul_operands``) with float32 accumulation; norms, softmax, router,
losses and the update in float32.  Every decoder layer is rematerialised
in the backward pass but for the attention kernel's partials (``o_part``,
``m``, ``l``), which the layer keeps, so the forward kernel runs once a
layer and step; the MoE layers run as one ``lax.scan``, and the
cross-entropy is computed in chunks of tokens.

:func:`init_state` wraps the caller's initial parameters in the step-0
training state; :func:`make_train_step` returns the per-rank step for
``mpx.compile`` (state donated); :func:`train_plan` says which kernels a
step calls and how often, for a trace to be held against.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import flash_block_partials, partials_name
from ..parallel.moe import HeldExperts, held_experts_layer
from . import training
from .training import is_norm as _is_norm
from .training import rms_norm as _rms_norm

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

GRAD_GROUPS = ("embed", "head", "attn", "dense_ffn", "shared", "routed",
               "router", "mtp_proj", "norms")
ATTENTION_KERNEL = "mla_attention"
EXPERT_KERNEL = "moe_gmm"
# what a decoder layer's rematerialisation keeps: the attention kernel's
# partials (about 69 MB a layer at 8192 tokens and 32 heads of value width
# 128), so the backward reads them instead of running the kernel again;
# the projections, the experts and the norms are recomputed
_LAYER_POLICY = jax.checkpoint_policies.save_only_these_names(
    partials_name(ATTENTION_KERNEL))


def _remat_layer(f):
    return jax.checkpoint(f, policy=_LAYER_POLICY)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes and settings a step is built from."""

    hidden: int
    q_rank: int
    kv_rank: int
    heads: int
    qk_nope: int
    qk_rope: int
    v_head: int
    dense_ff: int
    expert_ff: int
    experts: int          # the router's width: every expert of a layer
    held_first: int
    held: int             # experts this chip holds in each MoE layer
    top_k: int
    routed_scaling: float
    router_eps: float     # the routing weights' normaliser's epsilon
    moe_layers: int       # after the one dense layer, before the MTP block
    vocab: int
    rope_theta: float
    eps: float
    mtp_weight: float
    bias_rate: float
    lr: float
    beta1: float
    beta2: float
    adam_eps: float
    weight_decay: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        fixed = {"first_k_dense_replace": 1, "n_shared_experts": 1,
                 "num_nextn_predict_layers": 1, "n_group": 1,
                 "topk_group": 1, "scoring_func": "sigmoid",
                 "norm_topk_prob": True, "rope_interleave": True,
                 "hidden_act": "silu", "tie_word_embeddings": False}
        for key, want in fixed.items():
            if c.get(key) != want:
                raise ValueError(f"{key} = {c.get(key)!r}: this model is "
                                 f"built for {want!r}")
        if c.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not supported")
        if c["precision"]["matmul_operands"] != "bfloat16":
            raise ValueError("matmul operands are bfloat16 in this model")
        dep, assumed = c["deployment"], c["assumed"]
        adam = assumed["adamw"]
        return cls(
            hidden=c["hidden_size"], q_rank=c["q_lora_rank"],
            kv_rank=c["kv_lora_rank"], heads=c["num_attention_heads"],
            qk_nope=c["qk_nope_head_dim"], qk_rope=c["qk_rope_head_dim"],
            v_head=c["v_head_dim"], dense_ff=c["intermediate_size"],
            expert_ff=c["moe_intermediate_size"],
            experts=dep["experts_per_layer"],
            held_first=dep["held_first"], held=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            routed_scaling=float(c["routed_scaling_factor"]),
            # DeepSeek-V3's modelling adds 1e-20 (no config.json key)
            router_eps=1e-20,
            moe_layers=c["num_hidden_layers"] - 1, vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            mtp_weight=float(assumed["mtp_loss_weight"]),
            bias_rate=float(assumed["bias_update_rate"]),
            lr=float(adam["lr"]), beta1=float(adam["beta1"]),
            beta2=float(adam["beta2"]), adam_eps=float(adam["eps"]),
            weight_decay=float(adam["weight_decay"]))

    @property
    def qk_head(self) -> int:
        return self.qk_nope + self.qk_rope


# ---------------------------------------------------------------------------
# parameters and state
# ---------------------------------------------------------------------------


def _shapes(d: Dims) -> dict:
    """Every parameter's shape (this chip's share); the MoE layers'
    stacked on a leading axis."""
    h, f = d.hidden, d.expert_ff
    attn = {"w_qa": (h, d.q_rank), "q_norm": (d.q_rank,),
            "w_qb": (d.q_rank, d.heads * d.qk_head),
            "w_kva": (h, d.kv_rank + d.qk_rope), "kv_norm": (d.kv_rank,),
            "w_kvb": (d.kv_rank, d.heads * (d.qk_nope + d.v_head)),
            "w_o": (d.heads * d.v_head, h)}
    moe_block = {"attn_norm": (h,), "attn": attn, "ffn_norm": (h,),
                 "shared": {"w_gate_up": (h, 2 * f), "w_down": (f, h)},
                 "routed": {"router": (h, d.experts),
                            "w_gate_up": (d.held, h, 2 * f),
                            "w_down": (d.held, f, h)}}
    stack = jax.tree.map(lambda s: (d.moe_layers,) + s, moe_block,
                         is_leaf=lambda s: isinstance(s, tuple))
    return {
        "embed": (d.vocab, h), "head": (h, d.vocab), "final_norm": (h,),
        "dense": {"attn_norm": (h,), "attn": attn, "ffn_norm": (h,),
                  "ffn": {"w_gate_up": (h, 2 * d.dense_ff),
                          "w_down": (d.dense_ff, h)}},
        "moe": stack,
        "mtp": {"h_norm": (h,), "e_norm": (h,), "eh_proj": (2 * h, h),
                "block": moe_block, "final_norm": (h,)},
    }


def grad_group(path) -> str:
    """The group of :data:`GRAD_GROUPS` a parameter belongs to, by its key
    path."""
    keys = [str(getattr(k, "key", k)) for k in path]
    if _is_norm(path):
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


def param_count(d: Dims) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        _shapes(d), is_leaf=lambda s: isinstance(s, tuple)))


def init_state(d: Dims, params, bias) -> dict:
    """The training state at step 0 around the caller's initial ``params``
    (float32, the layout of this chip's share: the MoE layers' leaves
    stacked on a leading axis) and selection ``bias`` (``{"moe":
    (moe_layers, experts), "mtp": (experts,)}``): Adam's ``m`` and ``v`` at
    0 and ``count`` 0.  Raises where a shape is not the configuration's."""
    want = {"params": _shapes(d),
            "bias": {"moe": (d.moe_layers, d.experts), "mtp": (d.experts,)}}
    got = jax.tree.map(lambda a: tuple(a.shape),
                       {"params": params, "bias": bias})
    if got != want:
        raise ValueError("the initial parameters or bias do not have this "
                         "configuration's shapes")
    # m and v are buffers of their own: the step donates both
    return {"params": params, "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32), "bias": bias}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _rope(x, theta):
    """Rotary position on interleaved pairs ``(x[2i], x[2i+1])`` of the
    last axis; positions along axis 0."""
    t, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    shape = (t,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


class _Model:
    """The forward pass of one configuration."""

    def __init__(self, d: Dims, interpret: bool):
        self.d = d
        self.interpret = interpret

    mm = staticmethod(training.mm)

    def mla(self, p, x):
        d, t = self.d, x.shape[0]
        cq = _rms_norm(self.mm(x, p["w_qa"]), p["q_norm"], d.eps)
        q = self.mm(cq, p["w_qb"]).reshape(t, d.heads, d.qk_head)
        kv = self.mm(x, p["w_kva"])
        ckv = _rms_norm(kv[:, :d.kv_rank], p["kv_norm"], d.eps)
        kvb = self.mm(ckv, p["w_kvb"]).reshape(t, d.heads,
                                               d.qk_nope + d.v_head)
        k_pe = _rope(kv[:, None, d.kv_rank:], d.rope_theta)
        q = jnp.concatenate([q[..., :d.qk_nope],
                             _rope(q[..., d.qk_nope:], d.rope_theta)], -1)
        k = jnp.concatenate([kvb[..., :d.qk_nope],
                             jnp.broadcast_to(k_pe, (t, d.heads, d.qk_rope))],
                            -1)
        v = kvb[..., d.qk_nope:]
        q, k, v = (a.astype(jnp.bfloat16)[None] for a in (q, k, v))
        o_part, _, l = flash_block_partials(
            q, k, v, None, scale=1.0 / math.sqrt(d.qk_head), causal=True,
            name=ATTENTION_KERNEL, interpret=self.interpret)
        o = o_part / jnp.moveaxis(jnp.where(l == 0.0, 1.0, l), 1, 2)[..., None]
        return self.mm(o[0].reshape(t, d.heads * d.v_head), p["w_o"])

    def swiglu(self, p, x):
        gate, up = jnp.split(self.mm(x, p["w_gate_up"]), 2, axis=-1)
        return self.mm(jax.nn.silu(gate) * up, p["w_down"])

    def block(self, p, bias, x):
        """One decoder layer; ``bias`` None makes it the dense one.
        Returns ``(x, loads)``."""
        d = self.d
        h = x + self.mla(p["attn"], _rms_norm(x, p["attn_norm"], d.eps))
        xn = _rms_norm(h, p["ffn_norm"], d.eps)
        if bias is None:
            return h + self.swiglu(p["ffn"], xn), None
        r = p["routed"]
        routed, loads = held_experts_layer(
            xn, HeldExperts(r["router"], r["w_gate_up"], r["w_down"]),
            bias, held=(d.held_first, d.held), top_k=d.top_k,
            routed_scaling=d.routed_scaling, norm_eps=d.router_eps,
            interpret=self.interpret)
        return h + self.swiglu(p["shared"], xn) + routed, loads

    def cross_entropy(self, h, norm, head, targets):
        """Mean cross-entropy over the slice's logits, a chunk of tokens
        at a time (each chunk rematerialised)."""
        return training.cross_entropy(
            h, targets,
            lambda hc: self.mm(_rms_norm(hc, norm, self.d.eps), head))

    def trunk(self, params, bias, tokens):
        """The main model's last hidden state (before its final norm) at
        positions ``0..T-1`` of ``tokens`` (``T + 2`` ids), and the MoE
        layers' loads ``(moe_layers, experts)``."""
        x = params["embed"][tokens[:-2]]
        x, _ = _remat_layer(lambda p, x: self.block(p, None, x))(
            params["dense"], x)

        @_remat_layer
        def moe_layer(x, pb):
            return self.block(pb[0], pb[1], x)

        return jax.lax.scan(moe_layer, x, (params["moe"], bias["moe"]))

    def mtp(self, params, bias, h, tokens):
        """The MTP module on the trunk's ``h``: ``(ce_mtp, loads)``, the
        loss of predicting ``t_{i+2}``."""
        m, eps = params["mtp"], self.d.eps
        joined = jnp.concatenate(
            [_rms_norm(h, m["h_norm"], eps),
             _rms_norm(params["embed"][tokens[1:-1]], m["e_norm"], eps)], -1)
        y, loads = _remat_layer(lambda p, b, x: self.block(p, b, x))(
            m["block"], bias["mtp"], self.mm(joined, m["eh_proj"]))
        return self.cross_entropy(y, m["final_norm"], params["head"],
                                  tokens[2:]), loads

    def losses(self, params, bias, tokens):
        """``tokens``: ``(T + 2,)`` ids of the slice.  The main head reads
        positions ``0..T-1`` and predicts ``t_{i+1}``; the MTP module
        predicts ``t_{i+2}``.  Returns ``((ce_main, ce_mtp), loads)``,
        ``loads`` ``(moe_layers + 1, experts)``."""
        h, loads = self.trunk(params, bias, tokens)
        ce_main = self.cross_entropy(h, params["final_norm"], params["head"],
                                     tokens[1:-1])
        ce_mtp, mtp_loads = self.mtp(params, bias, h, tokens)
        return (ce_main, ce_mtp), jnp.concatenate([loads, mtp_loads[None]])


def loss_and_aux(d: Dims, params, bias, tokens, *, interpret=False):
    """``(loss, ((ce_main, ce_mtp), loads))`` of one sequence."""
    model = _Model(d, interpret)
    (ce_main, ce_mtp), loads = model.losses(params, bias, tokens)
    return ce_main + d.mtp_weight * ce_mtp, ((ce_main, ce_mtp), loads)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def _adamw(d, state, grads):
    """AdamW at the constant rate ``d.lr``; the module's own name, so that
    a test can put a planted fault in its place."""
    return training.adamw(d, state, grads, d.lr)


def make_train_step(d: Dims, *, interpret=False):
    """The per-rank training step ``step(state, tokens) -> (state, out)``:
    one AdamW step on one sequence of ``T + 2`` ids, and the selection
    bias moved by the loads it saw.  ``out``: ``loss`` (ce_main, ce_mtp),
    ``grad_norms`` (one a group of :data:`GRAD_GROUPS`), ``loads``
    (moe_layers + 1, experts) and ``dropped`` (held assignments the
    dispatch could not place: 0 by construction).  Pin it with
    ``mpx.compile(step, state, tokens, donate_argnums=(0,))``."""

    def train_step(state, tokens):
        grad_fn = jax.grad(
            lambda p: loss_and_aux(d, p, state["bias"], tokens,
                                   interpret=interpret),
            has_aux=True)
        grads, (losses, loads) = grad_fn(state["params"])
        (params, m, v), count = _adamw(d, state, grads)
        move = training.bias_move(loads, d.bias_rate)
        bias = {"moe": state["bias"]["moe"] + move[:-1],
                "mtp": state["bias"]["mtp"] + move[-1]}
        out = {"loss": jnp.stack(losses),
               "grad_norms": training.group_norms(grads, grad_group,
                                                  GRAD_GROUPS),
               "loads": loads,
               "dropped": training.dropped(loads, d.held_first, d.held,
                                           tokens.shape[0] - 2, d.top_k)}
        return ({"params": params, "m": m, "v": v, "count": count,
                 "bias": bias}, out)

    return train_step


def train_plan(d: Dims) -> dict:
    """Kernel calls one step makes, by kernel name: each attention layer's
    forward once (its rematerialisation in the backward keeps the kernel's
    partials and does not run it again) and its two backward kernels once;
    each MoE layer's two grouped products twice (the pass and the
    rematerialisation), their input and weight gradients once."""
    attn_layers = 1 + d.moe_layers + 1
    moe_layers = d.moe_layers + 1
    return {f"{ATTENTION_KERNEL}_fwd": attn_layers,
            f"{ATTENTION_KERNEL}_bwd_dq": attn_layers,
            f"{ATTENTION_KERNEL}_bwd_dkv": attn_layers,
            EXPERT_KERNEL: 4 * moe_layers,
            f"{EXPERT_KERNEL}_dx": 2 * moe_layers,
            f"{EXPERT_KERNEL}_dw": 2 * moe_layers}


