"""What the training steps of the package's models share: the bfloat16
matmul and the float32 RMSNorm they are built of, the cross-entropy over a
vocabulary slice a chunk of tokens at a time, the gradient norms by group,
AdamW, and the selection bias's move after a step.

A model's hyperparameters reach these through its ``Dims``: AdamW reads
``beta1``, ``beta2``, ``adam_eps`` and ``weight_decay`` (the rate is the
caller's); ``warmup_lr`` reads ``lr`` and ``warmup_steps``.
"""

import jax
import jax.numpy as jnp

__all__ = ["adamw", "bias_move", "cross_entropy", "dropped", "group_norms",
           "is_norm", "mm", "rms_norm", "warmup_lr"]

CE_CHUNK = 1024


def mm(a, w):
    """``a @ w`` with bfloat16 operands and float32 accumulation."""
    return jnp.dot(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def is_norm(path) -> bool:
    """Whether the parameter at key ``path`` is a norm's scale (no weight
    decay, group ``norms``)."""
    return str(getattr(path[-1], "key", path[-1])).endswith("norm")


def cross_entropy(h, targets, logits_of):
    """Mean cross-entropy of ``targets`` under ``logits_of(h chunk)`` (the
    slice's logits), ``CE_CHUNK`` tokens at a time, each chunk
    rematerialised so no more than one chunk's logits are ever held."""
    t = h.shape[0]
    chunk = CE_CHUNK if t % CE_CHUNK == 0 else t

    @jax.checkpoint
    def one(xs):
        hc, tc = xs
        logits = logits_of(hc)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - hit)

    sums = jax.lax.map(one, (h.reshape(-1, chunk, h.shape[-1]),
                             targets.reshape(-1, chunk)))
    return jnp.sum(sums) / t


def group_norms(grads, group_of, groups):
    """The norm of the gradients of each group of ``groups`` (in that
    order); ``group_of(key path)`` names a parameter's group."""
    sq = dict.fromkeys(groups, jnp.zeros((), jnp.float32))
    for path, g in jax.tree.flatten_with_path(grads)[0]:
        name = group_of(path)
        sq[name] = sq[name] + jnp.sum(jnp.square(g))
    return jnp.sqrt(jnp.stack([sq[n] for n in groups]))


def warmup_lr(d, count):
    """The learning rate of optimizer step ``count`` (1 for the first)
    under a linear warmup from 0 to ``d.lr`` over ``d.warmup_steps``
    steps."""
    return d.lr * jnp.minimum(1.0, count.astype(jnp.float32)
                              / d.warmup_steps)


def adamw(d, state, grads, lr):
    """AdamW with decoupled weight decay (not on norm scales): ``([params,
    m, v], count)`` after one step, at the learning rate ``lr`` (the
    constant ``d.lr``, or a traced value under a schedule)."""
    count = state["count"] + 1
    step_count = count.astype(jnp.float32)
    c1 = 1.0 - d.beta1 ** step_count
    c2 = 1.0 - d.beta2 ** step_count

    def update(path, p, g, m, v):
        m = d.beta1 * m + (1.0 - d.beta1) * g
        v = d.beta2 * v + (1.0 - d.beta2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + d.adam_eps)
        if not is_norm(path):
            step = step + d.weight_decay * p
        return p - lr * step, m, v

    out = jax.tree.map_with_path(update, state["params"], grads, state["m"],
                                 state["v"])
    pick = [jax.tree.map(lambda t, i=i: t[i], out,
                         is_leaf=lambda t: isinstance(t, tuple))
            for i in range(3)]
    return pick, count


def bias_move(loads, rate):
    """The selection bias's move after a step, from the loads it saw
    (``(..., experts)`` tokens an expert): ``rate * sign(mean load -
    load)``, so an under-loaded expert is chosen more often."""
    loads = loads.astype(jnp.float32)
    return rate * jnp.sign(jnp.mean(loads, -1, keepdims=True) - loads)


def dropped(loads, held_first, held, tokens, top_k):
    """Held assignments the dispatch could not place: the held experts'
    loads past the buffer's ``tokens x min(top_k, held)`` rows (0 by
    construction of ``held_capacity_rows``)."""
    held_loads = jax.lax.dynamic_slice_in_dim(loads, held_first, held, 1)
    capacity = tokens * min(top_k, held)
    return jnp.sum(jnp.maximum(held_loads.sum(-1) - capacity, 0))
