"""Expert-parallel mixture-of-experts: capacity-bucketed alltoall
dispatch/combine around a per-expert MLP (docs/moe.md), and the
held-experts layer of a top-k sigmoid-routed MoE (``held_experts_layer``).

The workload class ROADMAP item 5a names: ``k`` ranks each own ONE
expert, tokens are routed by a top-1 gate, and the two hottest
collectives in the program are **alltoalls** —

- **dispatch**: every rank buckets its local tokens by destination
  expert into a ``(experts, capacity, d)`` buffer (tokens beyond the
  capacity are dropped, the standard top-1 discipline) and one alltoall
  ships bucket ``e`` to rank ``e``;
- **expert compute**: each rank runs ITS expert's MLP over the
  ``k · capacity`` tokens it received;
- **combine**: the mirror alltoall ships every processed bucket back to
  its source rank, where the gate probability weighs it into the output
  (dropped tokens contribute zero).

The combine is where Tutel/FasterMoE-style overlap pays: the per-expert
compute and the combine-exchange split into
``MPI4JAX_TPU_MOE_CAPACITY_CHUNKS`` capacity chunks, chunk ``i``'s
combine issued via :func:`~mpi4jax_tpu.alltoall_start` while chunk
``i+1``'s MLP runs — the exchange rides the async alltoall fast path
(ops/_async.py), hierarchical over ICI/DCN where the topology layer
selects it (ops/_hierarchy.py).

**Determinism contract**: the gate and capacity math is pure and seeded
(``init_moe_params``), every bucket operation is a one-hot einsum (no
data-dependent gather ordering), and dispatch/combine are fixed
permutations — so the 8-device layer output is BIT-IDENTICAL to the
single-device :func:`reference_moe` fold (pinned by tests/test_moe.py),
and the overlapped pipeline is bit-identical to the synchronous one.

The gate math helpers are numpy-polymorphic (they take the array module
as an argument), so tests/test_moe_pure.py drives the SAME functions
through plain numpy under any installed JAX.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

__all__ = [
    "MoEParams",
    "capacity_for",
    "init_moe_params",
    "gate_tokens",
    "dispatch_tensor",
    "expert_mlp",
    "moe_layer",
    "reference_moe",
    "HeldExperts",
    "route_sigmoid_topk",
    "held_capacity_rows",
    "held_experts_layer",
]


def capacity_for(tokens: int, experts: int, factor: float = 1.25) -> int:
    """Per-expert token capacity of one rank's dispatch bucket:
    ``ceil(tokens / experts · factor)``, at least 1 — the padded bucket
    shape every rank shares, so the dispatch alltoall is shape-uniform
    across ranks (rank-divergent capacities are exactly the MPX120
    fixture under examples/broken/)."""
    if tokens < 1 or experts < 1:
        raise ValueError(
            f"capacity_for needs tokens >= 1 and experts >= 1, got "
            f"tokens={tokens}, experts={experts}"
        )
    if factor <= 0:
        raise ValueError(f"capacity factor must be > 0, got {factor}")
    return max(1, -(-int(tokens * factor) // experts))


class MoEParams(NamedTuple):
    """One rank's MoE parameters: the (replicated) router plus THIS
    rank's expert MLP (expert-parallel: rank ``e`` owns expert ``e``)."""

    w_gate: object   # (d, experts) — replicated router
    w_in: object     # (d, d_ff)    — this rank's expert, layer 1
    w_out: object    # (d_ff, d)    — this rank's expert, layer 2


def init_moe_params(d: int, d_ff: int, experts: int, rank: int = 0,
                    seed: int = 0):
    """Seeded numpy parameter init (float32).  Pure and deterministic:
    the router is identical on every rank (same seed), the expert MLP is
    seeded per ``rank`` — so a single-device reference can rebuild every
    expert's weights exactly (``reference_moe``)."""
    gate_rng = np.random.default_rng(seed)
    w_gate = gate_rng.standard_normal((d, experts)).astype(np.float32) * 0.3
    ex_rng = np.random.default_rng(seed * 7919 + 31 + rank)
    w_in = ex_rng.standard_normal((d, d_ff)).astype(np.float32) * 0.2
    w_out = ex_rng.standard_normal((d_ff, d)).astype(np.float32) * 0.2
    return MoEParams(w_gate=w_gate, w_in=w_in, w_out=w_out)


def gate_tokens(xp, x, w_gate):
    """Top-1 gating: returns ``(assignment, gate_prob)`` for local
    tokens ``x`` (shape ``(tokens, d)``) — the expert index each token
    routes to and its softmax probability.  ``xp`` is the array module
    (``jax.numpy`` traced, ``numpy`` in the pure tests): the math is
    identical, which is what makes the 8-device pins possible."""
    logits = x @ w_gate
    a = xp.argmax(logits, axis=-1)
    z = xp.exp(logits - xp.max(logits, axis=-1, keepdims=True))
    probs = z / xp.sum(z, axis=-1, keepdims=True)
    gate = xp.take_along_axis(probs, a[:, None], axis=-1)[:, 0]
    return a, gate


def dispatch_tensor(xp, assignment, experts: int, capacity: int):
    """The one-hot dispatch tensor ``D[t, e, c]`` = 1 iff local token
    ``t`` is the ``c``-th token (in position order) routed to expert
    ``e`` and ``c < capacity``.  Everything downstream is an einsum
    against ``D`` — bucketing, un-bucketing, and the gate-weighted
    combine — so there is no data-dependent gather order to diverge
    across ranks or between the traced and reference paths."""
    onehot = (assignment[:, None] ==
              xp.arange(experts)[None, :]).astype(xp.float32)
    pos = xp.cumsum(onehot, axis=0) * onehot - onehot  # 0-based in-bucket
    slot = (pos[:, :, None] ==
            xp.arange(capacity)[None, None, :]).astype(xp.float32)
    return slot * onehot[:, :, None]


def expert_mlp(xp, z, w_in, w_out):
    """One expert's feed-forward over a token block: ``tanh`` MLP —
    smooth, bounded, and bit-reproducible across the traced and numpy
    reference paths (no erf/gelu implementation divergence)."""
    return xp.tanh(z @ w_in) @ w_out


def moe_layer(x, params: MoEParams, *, comm=None, token=None,
              capacity_factor: float = 1.25,
              chunks: Optional[int] = None):
    """The expert-parallel MoE layer (rank-local view, inside a managed
    parallel region): gate → capacity-bucketed dispatch alltoall →
    per-expert MLP → combine alltoall → gate-weighted output.

    ``chunks`` (default ``MPI4JAX_TPU_MOE_CAPACITY_CHUNKS``) pipelines
    the combine: the received buckets split into capacity chunks, chunk
    ``i``'s combine-exchange is issued with ``alltoall_start`` and
    chunk ``i+1``'s expert MLP runs in the gap; the waits land after
    the last chunk's compute.  ``chunks=1`` is the synchronous layer —
    bit-identical output either way (pinned by tests/test_moe.py).

    Returns ``(y, token)`` with ``y`` shaped like ``x``; dropped tokens
    (beyond an expert's capacity) produce zero rows, the standard top-1
    capacity discipline.
    """
    import jax.numpy as jnp

    from ..ops import _async
    from ..ops.alltoall import alltoall
    from ..parallel.region import resolve_comm
    from ..utils import config

    comm = resolve_comm(comm)
    k = comm.Get_size()
    tokens, d = x.shape
    capacity = capacity_for(tokens, k, capacity_factor)
    if chunks is None:
        chunks = config.moe_capacity_chunks()
    chunks = max(1, min(int(chunks), capacity))

    a, gate = gate_tokens(jnp, x, jnp.asarray(params.w_gate))
    D = dispatch_tensor(jnp, a, k, capacity)           # (tokens, k, cap)
    dispatch = jnp.einsum("tec,td->ecd", D, x)         # (k, cap, d)
    received, tok = alltoall(dispatch, comm=comm, token=token)
    # received[g, c] = rank g's c-th token for MY expert

    w_in = jnp.asarray(params.w_in)
    w_out = jnp.asarray(params.w_out)
    sizes = _async.overlap_chunk_split(capacity, chunks)
    if len(sizes) == 1:
        # synchronous: one MLP, one combine exchange
        processed = expert_mlp(jnp, received, w_in, w_out)
        combined, tok = alltoall(processed, comm=comm, token=tok)
    else:
        # the overlap pipeline: chunk i's combine-alltoall is in flight
        # while chunk i+1's expert MLP runs (docs/moe.md)
        handles = []
        off = 0
        for csz in sizes:
            block = received[:, off:off + csz]
            off += csz
            out = expert_mlp(jnp, block, w_in, w_out)
            h, tok = _async.alltoall_start(out, comm=comm, token=tok)
            handles.append(h)
        parts = []
        for h in handles:
            part, tok = _async.alltoall_wait(h, token=tok)
            parts.append(part)
        combined = jnp.concatenate(parts, axis=1)
    # combined[e, c] = my c-th token as processed by expert e
    y = jnp.einsum("tec,ecd->td", D, combined) * gate[:, None]
    return y, tok


def reference_moe(x_global, d_ff: int, experts: int, *, seed: int = 0,
                  capacity_factor: float = 1.25):
    """Single-device numpy reference of the whole expert-parallel layer:
    ``x_global`` is ``(ranks, tokens, d)`` (rank-major, the eager global
    convention) and the return is the matching global output — the
    8-device dryrun pin (tests/test_moe.py compares bit-for-bit).

    Rebuilds every expert's weights from the same seeded init the ranks
    use, replays the same capacity discipline, and never simulates the
    wire: dispatch/combine are fixed permutations, so equality with the
    distributed layer is exact.
    """
    k, tokens, d = x_global.shape
    assert k == experts, (k, experts)
    capacity = capacity_for(tokens, experts, capacity_factor)
    params = [init_moe_params(d, d_ff, experts, rank=r, seed=seed)
              for r in range(k)]
    # per-rank gating + dispatch buckets
    disp = np.zeros((k, experts, capacity, d), np.float32)
    Ds = []
    gates = []
    for r in range(k):
        a, gate = gate_tokens(np, x_global[r], params[r].w_gate)
        D = dispatch_tensor(np, a, experts, capacity)
        Ds.append(D)
        gates.append(gate)
        disp[r] = np.einsum("tec,td->ecd", D, x_global[r])
    # alltoall: expert e receives bucket e of every rank
    received = np.stack([disp[:, e] for e in range(experts)])  # (e, k, c, d)
    processed = np.stack([
        expert_mlp(np, received[e], params[e].w_in, params[e].w_out)
        for e in range(experts)
    ])
    # combine alltoall back: rank r's view of expert e's output bucket
    out = np.zeros_like(x_global)
    for r in range(k):
        combined = processed[:, r]  # (e, c, d): my tokens at each expert
        out[r] = np.einsum("tec,ecd->td", Ds[r],
                           combined) * gates[r][:, None]
    return out


# ---------------------------------------------------------------------------
# the held-experts layer: top-k sigmoid routing over every expert, grouped
# products over the experts this chip holds, no token dropped
# ---------------------------------------------------------------------------


class HeldExperts(NamedTuple):
    """One chip's share of a routed-expert layer: the router over ALL
    experts and the SwiGLU weights of the experts it holds."""

    router: object     # (d, experts) — every expert's router column
    w_gate_up: object  # (held, d, 2 * d_ff) — gate and up, side by side
    w_down: object     # (held, d_ff, d)


def route_sigmoid_topk(x, router, bias, *, top_k: int,
                       routed_scaling: float, norm_eps: float):
    """Sigmoid top-k routing with a selection bias, in float32 (the
    DeepSeek-V3 ``noaux_tc`` rule with one group): ``s = sigmoid(x @
    router)``; the experts chosen are the ``top_k`` largest of ``s +
    bias``; their weights are ``routed_scaling * s_i / (sum_chosen s +
    norm_eps)`` (the models' own epsilon: DeepSeek-V3's modelling adds
    1e-20, LFM2's 1e-6).
    ``bias`` only selects (it carries no gradient).  Returns ``(experts
    (t, k) int32, weights (t, k) f32, loads (experts,) int32)``: ``loads``
    counts, for every expert, the tokens that chose it."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = routed_scaling * chosen / (chosen.sum(-1, keepdims=True)
                                         + norm_eps)
    loads = jnp.zeros(router.shape[1], jnp.int32).at[idx.reshape(-1)].add(1)
    return idx.astype(jnp.int32), weights, loads


def held_capacity_rows(tokens: int, top_k: int, held: int) -> int:
    """Rows of the dispatch buffer: every held assignment of ``tokens``
    (a token chooses at most ``min(top_k, held)`` held experts) in whole
    tiles, a group's last tile padded, each held expert given at least
    one tile — so no routing, however uneven, drops a token."""
    from ..kernels.grouped_matmul import TILE_ROWS

    tiles = tokens * min(top_k, held) // TILE_ROWS + held + 1
    return tiles * TILE_ROWS


def held_experts_layer(x, params: HeldExperts, bias, *, held, top_k: int,
                       routed_scaling: float, norm_eps: float,
                       interpret: bool = False):
    """The routed part of a top-k sigmoid MoE layer on the chip that holds
    experts ``held = (first, count)`` of ``params.router.shape[1]``.

    Every token is routed over all experts (``route_sigmoid_topk``, with
    ``norm_eps`` in its weights' normaliser); the
    assignments to held experts are sorted by expert into whole tiles of
    a dispatch buffer (``held_capacity_rows``: nothing is dropped) and
    run through the grouped products of ``kernels/grouped_matmul.py``
    (``moe_gmm``: gate and up side by side, then down, a SwiGLU expert);
    each output row, weighted, is added back to its token.  Assignments
    to experts held elsewhere are left out: they are the other chips'
    part of the result.  A shared expert, which every chip computes
    alike, is the caller's.

    The products take bfloat16 operands and accumulate in float32.  The
    layer runs on one chip's tokens without exchange; the dispatch over
    ranks is not implemented.  Returns ``(y, loads)``: ``y`` float32 like
    ``x``,
    ``loads`` (experts,) int32 — how many of this chip's tokens chose
    each expert (the selection bias's update reads it)."""
    import jax
    import jax.numpy as jnp

    from ..kernels.grouped_matmul import TILE_ROWS, grouped_matmul

    first, count = held
    tokens, d = x.shape
    idx, weights, loads = route_sigmoid_topk(
        x, params.router, bias, top_k=top_k, routed_scaling=routed_scaling,
        norm_eps=norm_eps)

    # assignments sorted by held expert; the others sort last
    local = idx.reshape(-1) - first
    is_held = (local >= 0) & (local < count)
    key = jnp.where(is_held, local, count)
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    held_loads = jax.lax.dynamic_slice_in_dim(loads, first, count)
    tiles = jnp.maximum(1, -(-held_loads // TILE_ROWS))
    tile_start = jnp.cumsum(tiles) - tiles
    group_start = jnp.cumsum(held_loads) - held_loads
    rows = held_capacity_rows(tokens, top_k, count)
    safe_key = jnp.minimum(sorted_key, count - 1)
    rank = jnp.arange(order.shape[0]) - group_start[safe_key]
    dest = jnp.where(sorted_key < count,
                     tile_start[safe_key] * TILE_ROWS + rank, rows)
    row_token = jnp.full((rows,), tokens, jnp.int32).at[dest].set(
        (order // top_k).astype(jnp.int32), mode="drop")
    row_weight = jnp.zeros((rows,), jnp.float32).at[dest].set(
        weights.reshape(-1)[order], mode="drop")
    n_tiles = jnp.sum(tiles).astype(jnp.int32).reshape(1)
    tile_expert = jnp.repeat(jnp.arange(count, dtype=jnp.int32), tiles,
                             total_repeat_length=rows // TILE_ROWS)

    xo = x.astype(jnp.bfloat16)
    x_rows = jnp.concatenate([xo, jnp.zeros((1, d), xo.dtype)])[row_token]
    gate_up = grouped_matmul(x_rows, params.w_gate_up, tile_expert, n_tiles,
                             name="moe_gmm", interpret=interpret)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    h = (jax.nn.silu(gate) * up).astype(jnp.bfloat16)
    out = grouped_matmul(h, params.w_down, tile_expert, n_tiles,
                         name="moe_gmm", interpret=interpret)
    # rows past the live tiles are not written: weight 0 and a select keep
    # whatever they hold out of the sum
    live = jnp.arange(rows) < n_tiles[0] * TILE_ROWS
    contrib = jnp.where(live[:, None], out * row_weight[:, None], 0.0)
    y = jnp.zeros((tokens + 1, d), jnp.float32).at[row_token].add(contrib)
    return y[:tokens], loads
