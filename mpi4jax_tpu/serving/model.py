"""The serving workload: a tiny tensor-parallel transformer decoder.

Megatron-style tensor parallelism over the serving comm: QKV and the
MLP up-projection are COLUMN-parallel (each rank holds ``heads / k``
attention heads and ``ffn / k`` hidden units), the attention output and
MLP down-projections ROW-parallel — each rank computes a partial sum
that exactly TWO allreduces per layer complete.  Those two allreduces
(payload ``[batch, dim]``) are the serving hot path's whole
communication surface, so the batch dimension every collective carries
is the BUCKET shape (leading dim = padded batch — what the MPX136
advisory checks).

Both step functions are **module-level and shape-polymorphic** (every
size is derived from the argument shapes, no closed-over config), so
the cache-warming CLI can name them in a manifest
(``mpi4jax_tpu.serving.model:prefill_step``) and warm the exact
programs the engine pins — same function, same abstract shapes, same
persistent-cache key (docs/serving.md "Fleet cold start").

Conventions (per-rank views; ``B`` = bucket, ``L`` = max_len, ``S`` =
KV slots, ``Hl`` = local heads, ``dh`` = head dim, ``Fl`` = local ffn):

- ``kk``/``vv`` ``[S+1, L, Hl, dh]`` — the sharded KV pool; row ``S``
  is the padding-lane scratch row (serving/kvcache.py);
- ``tok_table [S+1, L] int32`` — token ``i`` of a sequence at column
  ``i`` (prompt at ``0..plen-1``, generated from ``plen`` on);
- ``lens [B] int32`` — KV entries present per lane; the lane's latest
  token sits at column ``lens`` and its KV is written by the NEXT
  decode step (so after prefill ``lens == plen`` with the first
  generated token already at column ``plen``);
- sampling is greedy argmax: bit-deterministic, and identical on every
  rank because the logits are computed from allreduced (replicated)
  activations.

``decode_step`` obeys the megastep carry contract (11 dynamic arguments
in, like-structured 11-tuple out) so ``mpx.compile(..., unroll=N)``
drives it as a device-resident multi-token program.
"""

from __future__ import annotations

__all__ = ["decode_step", "init_master", "prefill_step", "shard_params"]

NEG_INF = -1e9


def _attention_mix(x, wo, w1, w2):
    """Row-parallel attention-out + MLP: the two partial-sum matmuls and
    their completing allreduces (the serving comm pattern)."""
    import jax

    from ..ops import SUM, allreduce

    attn_full, _ = allreduce(x @ wo, op=SUM)
    return attn_full, lambda y: allreduce(
        jax.nn.relu(y @ w1) @ w2, op=SUM)[0]


def decode_step(emb, wqkv, wo, w1, w2, kk, vv, tok_table, last_tok, lens,
                slots):
    """One token step for a bucketed batch of lanes (per-rank body).

    Embeds each lane's latest token (column ``lens``), writes its K/V at
    position ``lens``, attends over ``0..lens``, and records the
    sampled next token at column ``lens + 1``.  Returns the full carry
    (params included, unchanged) — the megastep contract.
    """
    import jax.numpy as jnp

    from ..ops import varying
    from .kvcache import scatter_step

    n_local_heads, head_dim = kk.shape[2], kk.shape[3]
    max_len = kk.shape[1]

    x = emb[last_tok]                              # [B, D]
    qkv = (x @ wqkv).reshape(x.shape[0], 3, n_local_heads, head_dim)
    q = qkv[:, 0] * (head_dim ** -0.5)
    kk = scatter_step(kk, slots, lens, qkv[:, 1])
    vv = scatter_step(vv, slots, lens, qkv[:, 2])

    krows = kk[slots]                              # [B, L, Hl, dh]
    vrows = vv[slots]
    scores = jnp.einsum("bhd,blhd->bhl", q, krows)
    live = jnp.arange(max_len, dtype=jnp.int32)[None, :] <= lens[:, None]
    scores = jnp.where(live[:, None, :], scores, NEG_INF)
    att = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    att = att / att.sum(axis=-1, keepdims=True)
    ctx = jnp.einsum("bhl,blhd->bhd", att, vrows)
    ctx = ctx.reshape(x.shape[0], n_local_heads * head_dim)

    attn_full, mlp = _attention_mix(ctx, wo, w1, w2)
    x = x + attn_full
    x = x + mlp(x)

    logits = x @ emb.T                             # [B, V], replicated math
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tok_table = tok_table.at[slots, lens + 1].set(nxt)
    return varying((emb, wqkv, wo, w1, w2, kk, vv, tok_table, nxt,
                    lens + jnp.int32(1), slots))


def _prefill_forward(emb, wqkv, wo, w1, w2, kk, vv, prompts, plens, slots):
    """The prefill body up to the logits: ``(kk, vv, logits [B, V])``."""
    import jax.numpy as jnp

    from .kvcache import scatter_prefill

    n_local_heads, head_dim = kk.shape[2], kk.shape[3]
    batch, pad_len = prompts.shape

    x = emb[prompts]                               # [B, P, D]
    qkv = (x @ wqkv).reshape(batch, pad_len, 3, n_local_heads, head_dim)
    q = qkv[:, :, 0] * (head_dim ** -0.5)
    kk = scatter_prefill(kk, slots, qkv[:, :, 1])
    vv = scatter_prefill(vv, slots, qkv[:, :, 2])

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, qkv[:, :, 1])
    causal = jnp.tril(jnp.ones((pad_len, pad_len), bool))
    scores = jnp.where(causal[None, None, :, :], scores, NEG_INF)
    att = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    att = att / att.sum(axis=-1, keepdims=True)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", att, qkv[:, :, 2])
    ctx = ctx.reshape(batch, pad_len, n_local_heads * head_dim)

    attn_full, mlp = _attention_mix(ctx, wo, w1, w2)
    x = x + attn_full
    x = x + mlp(x)

    x_last = x[jnp.arange(batch), plens - 1]       # [B, D]
    return kk, vv, x_last @ emb.T


def prefill_step(emb, wqkv, wo, w1, w2, kk, vv, tok_table, prompts, plens,
                 slots):
    """Prompt processing for a bucketed batch (per-rank body).

    Causal self-attention over the padded prompt buffer ``[B, L]``,
    K/V written for every position (garbage beyond ``plen`` is masked
    by ``lens`` downstream and overwritten as the sequence grows), and
    the FIRST generated token sampled from the last live position and
    recorded at column ``plen``.  Returns ``(kk, vv, tok_table,
    first_token)``.
    """
    import jax.numpy as jnp

    from ..ops import varying

    kk, vv, logits = _prefill_forward(emb, wqkv, wo, w1, w2, kk, vv,
                                      prompts, plens, slots)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tok_table = tok_table.at[slots, plens].set(first)
    return varying((kk, vv, tok_table, first))


def prefill_logits(emb, wqkv, wo, w1, w2, kk, vv, tok_table, prompts, plens,
                   slots):
    """``prefill_step``'s arguments -> the last-position logits ``[B, V]``
    it samples from (per-rank body; replicated content): what
    ``chip_smoke.py`` checks against an unsharded forward."""
    from ..ops import varying

    return varying(_prefill_forward(emb, wqkv, wo, w1, w2, kk, vv,
                                    prompts, plens, slots)[2])


# ---------------------------------------------------------------------------
# parameters: one unsharded master copy, re-sharded per world size
# ---------------------------------------------------------------------------
#
# The master lives host-side (numpy) and is what the elastic ShardStore
# commits: after a drain shrinks the tensor-parallel group, survivors
# re-derive the k'-way shards from the same master — deterministic on
# every rank, no exchange needed.


def init_master(vocab: int, dim: int, heads: int, head_dim: int, ffn: int,
                seed: int = 0) -> dict:
    """Seeded unsharded parameters (numpy, float32)."""
    import numpy as np

    if dim != heads * head_dim:
        raise ValueError(
            f"dim ({dim}) must equal heads * head_dim "
            f"({heads} * {head_dim})"
        )
    rng = np.random.default_rng(seed)

    def w(*shape, scale):
        return rng.normal(0.0, scale, shape).astype(np.float32)

    return {
        "emb": w(vocab, dim, scale=0.1),
        "wqkv": w(dim, 3, heads, head_dim, scale=dim ** -0.5),
        "wo": w(heads, head_dim, dim, scale=dim ** -0.5),
        "w1": w(dim, ffn, scale=dim ** -0.5),
        "w2": w(ffn, dim, scale=ffn ** -0.5),
    }


def shard_params(master: dict, k: int) -> tuple:
    """Master -> the 5 GLOBAL param arrays (leading rank axis, numpy):
    ``emb`` replicated, QKV/MLP-up column-parallel (head / hidden-unit
    blocks), attention-out/MLP-down row-parallel."""
    import numpy as np

    heads, head_dim = master["wqkv"].shape[2], master["wqkv"].shape[3]
    dim, ffn = master["w1"].shape
    if heads % k or ffn % k:
        raise ValueError(
            f"heads ({heads}) and ffn ({ffn}) must both divide by the "
            f"tensor-parallel world size {k} (docs/serving.md)"
        )
    hl, fl = heads // k, ffn // k
    emb_g = np.tile(master["emb"][None], (k, 1, 1))
    wqkv_g = np.stack([
        master["wqkv"][:, :, r * hl:(r + 1) * hl, :].reshape(
            dim, 3 * hl * head_dim)
        for r in range(k)
    ])
    wo_g = np.stack([
        master["wo"][r * hl:(r + 1) * hl].reshape(hl * head_dim, dim)
        for r in range(k)
    ])
    w1_g = np.stack([master["w1"][:, r * fl:(r + 1) * fl]
                     for r in range(k)])
    w2_g = np.stack([master["w2"][r * fl:(r + 1) * fl, :]
                     for r in range(k)])
    return emb_g, wqkv_g, wo_g, w1_g, w2_g
