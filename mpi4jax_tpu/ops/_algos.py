"""Payload-aware collective algorithms: ring lowerings + the auto-selector.

The butterfly lowerings in ``_base.py`` ship the FULL payload every round —
O(size·log k) bytes per rank.  That is latency-optimal for small payloads
(``ceil(log2 k)`` neighbor hops) but a bandwidth disaster for large ones:
the well-known ring algorithms move only O(size) bytes per rank, the
bandwidth-optimal bound ``benchmarks/micro.py`` normalizes against
(``2·(n-1)/n·size`` for an allreduce).  This module provides:

- ``apply_ring_allreduce`` — ring reduce-scatter + ring allgather, for all
  10 ``Op``s and associative callables (ascending group-rank fold order is
  preserved for non-commutative callables via a lo/hi accumulator pair —
  see ``rs_update_pair``);
- ``apply_ring_reduce_scatter`` — the reduce-scatter building block, also
  the lowering of the public ``reduce_scatter`` op (ops/reduce_scatter.py);
- ``apply_ring_allgather`` — the allgather building block;
- ``apply_vdg_bcast`` — binomial-halving scatter + ring allgather broadcast
  (van de Geijn), ~2·size bytes per rank vs the doubling broadcast's
  size·log2(k);
- ``resolve_algo`` / ``algo_cache_token`` — per-call butterfly-vs-ring
  selection from STATIC payload bytes and group size, forced via
  ``MPI4JAX_TPU_COLLECTIVE_ALGO={auto,butterfly,ring}`` and folded into the
  compiled-program cache keys exactly like the resilience flags.

Ring lowerings need a static uniform group size (the chunk count); unequal
color-split groups keep the butterfly.  Chunks are padded to ``k·chunk``
elements so payloads not divisible by ``k`` lower cleanly; padding lanes
are discarded after the final reshape, so garbage combines never leak.

**Callable caveat**: ``apply_ring_allreduce`` splits the flattened payload
into chunks and applies the reduction per chunk, so a callable op must be
ELEMENTWISE (the ``MPI_User_function`` contract).  Whole-array callables
(e.g. ``jnp.matmul``) are only valid with the butterfly — ``auto`` never
routes callables to the ring; only an explicit ``ring`` override does.
``reduce_scatter`` has no such caveat: its chunks are the user's own
blocks, so block-wise callables (including ``jnp.matmul``) work there.

The index formulas and update rules below are polymorphic over Python ints
and traced values, so ``tests/test_algos.py`` drives the SAME functions
through a pure-Python lockstep simulator (symbolic string folds pin the
exact combine order; numpy folds pin all 10 ops) without needing a
multi-device mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import config

# ``auto`` never picks the ring below this group size: with k < 4 the ring's
# 2·(k-1) rounds don't beat the butterfly's 2·ceil(log2 k) and the byte
# volumes are comparable.
RING_MIN_GROUP = 4


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def algo_cache_token() -> tuple:
    """Hashable fingerprint of the algorithm-selection configuration —
    folded into every compiled-program cache key that caches op lowerings
    (mirrors ``resilience.runtime.cache_token``), so toggling
    ``MPI4JAX_TPU_COLLECTIVE_ALGO`` — or the topology override / DCN
    crossover the hierarchical layer reads — retraces instead of silently
    serving the old program.  (The mesh-derived half of the topology is
    already in both cache keys via the mesh itself.)

    The tuning layer's content stamp (``config.tuning_stamp()``,
    docs/autotune.md) folds in whenever a layer is active — every
    ``mpx.load_tuning`` of new content retraces even where its values
    happen to match the defaults (the env route pins a file's content
    at first read per process, so an in-place edit needs the explicit
    ``load_tuning(path)`` refresh); with no layer the token is exactly
    the flat 5-tuple below (the alltoall crossover joined the base in
    PR 15, deliberately moving every cache key once) with no trailing
    stamp entry (pinned by tests/test_autotune_pure.py).

    The DCN wire codec (``MPI4JAX_TPU_COMPRESS``, docs/compression.md)
    folds the same conditional way: only when a codec is active — so
    ``off`` (the default) keeps the token EXACTLY the pre-compression
    value (byte-identical HLO and cache keys, pinned by
    tests/test_compress_pure.py), while flipping to bf16/fp8 (or
    loading a tuning file that tunes the knob — already covered by the
    stamp) retraces every program."""
    base = (config.collective_algo(), config.ring_crossover_bytes(),
            config.dcn_crossover_bytes(), config.topology_spec(),
            config.alltoall_crossover_bytes())
    compress = config.compress_mode()
    if compress != "off":
        base = base + (("compress", compress),)
    stamp = config.tuning_stamp()
    return base if stamp is None else base + (("tuning", stamp),)


def static_group_size(comm):
    """The comm's uniform static group size, or ``None`` when group sizes
    differ (unequal color splits cannot ring: the chunk count is the group
    size and one SPMD program cannot express per-rank chunk counts).
    Plain delegation to ``Comm.uniform_size`` — the explicit accessor that
    replaced catching ``Get_size``'s ``RuntimeError`` as control flow —
    with the one remaining exceptional case (an unbound whole-axes comm
    outside any trace has no size at all) still mapped to ``None``."""
    try:
        return comm.uniform_size()
    except RuntimeError:  # unbound comm outside any trace
        return None


def resolve_algo(algo: str, payload_bytes: int, k: int, ring_ok: bool,
                 hier_ok: bool = False) -> str:
    """Pick ``"butterfly"``, ``"ring"``, or ``"hier"`` for one call.

    ``algo`` is the configured value (``config.collective_algo()``); forced
    values win, except that a forced algorithm falls back where it is not
    expressible — a forced ring to the butterfly (``ring_ok=False``:
    unequal groups, k <= 1, or a callable op on the chunked-allreduce
    path), a forced hier to the ``auto`` rules (``hier_ok=False``: no
    derivable topology, single-host comm, or a non-uniform /
    non-contiguous host partition — see ``_hierarchy.hier_plan``); never
    an error.  ``auto`` picks the two-level hierarchical lowering when the
    comm spans more than one host (``hier_ok``) and the payload clears the
    ring crossover, the flat ring for single-host payloads at/above
    ``ring_crossover_bytes()`` on groups of at least ``RING_MIN_GROUP``,
    and the butterfly otherwise.
    """
    if algo == "hier":
        if hier_ok:
            return "hier"
        algo = "auto"  # inexpressible: fall back to the auto rules
    if not ring_ok or algo == "butterfly":
        return "butterfly"
    if algo == "ring":
        return "ring"
    if k >= RING_MIN_GROUP and payload_bytes >= config.ring_crossover_bytes():
        return "hier" if hier_ok else "ring"
    return "butterfly"


def resolve_dcn_algo(shard_bytes: int, h: int, ring_ok: bool = True) -> str:
    """Inter-host (DCN) phase selection for the hierarchical lowerings:
    ring when the per-host shard clears ``dcn_crossover_bytes()`` on at
    least ``RING_MIN_GROUP`` hosts (DCN rounds are expensive — see the
    flag's default rationale in utils/config.py), butterfly otherwise.
    ``ring_ok=False`` (callable reductions: the DCN ring would re-chunk
    the shard) keeps the butterfly."""
    if (ring_ok and h >= RING_MIN_GROUP
            and shard_bytes >= config.dcn_crossover_bytes()):
        return "ring"
    return "butterfly"


def resolve_alltoall_algo(algo: str, payload_bytes: int, hier_ok: bool,
                          flat: str = "native") -> str:
    """Lowering pick for one alltoall: ``"hier"`` (the two-level
    ICI/DCN split of ops/_hierarchy.py) or ``flat`` (the single-level
    exchange — ``"native"`` for the one-AllToAll-HLO whole-axes path,
    ``"pairwise"`` for the chunked ppermute rounds the async split
    uses on color-split comms).

    ``MPI4JAX_TPU_COLLECTIVE_ALGO=hier`` forces the hierarchy where
    expressible (``hier_ok``); the forced flat algorithms
    (``butterfly``/``ring``) force the flat lowering — alltoall is a
    fixed permutation, so "flat" is the only single-level shape and
    both spellings mean it.  ``auto`` picks the hierarchy on a
    multi-host comm when the payload clears
    ``MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES`` (below it, one monolithic
    exchange's latency wins; above it, the intra-host aggregation cuts
    the DCN message count to 1/r of flat — docs/moe.md).  Bit-identical
    either way: no arithmetic, only routing.
    """
    if algo == "hier":
        return "hier" if hier_ok else flat
    if algo in ("butterfly", "ring"):
        return flat
    if hier_ok and payload_bytes >= config.alltoall_crossover_bytes():
        return "hier"
    return flat


def algorithm_bytes_per_rank(algo: str, nbytes: int, k: int,
                             preserve_order: bool = False) -> int:
    """Algorithmic bytes one rank ships for an allreduce of ``nbytes``
    (the docs/microbenchmarks.md byte-volume table; also pinned by
    tests/test_algos.py against the simulated lowerings)."""
    if k <= 1:
        return 0
    if algo == "butterfly":
        rounds = (k - 1).bit_length()  # ceil(log2 k)
        return 2 * rounds * nbytes  # fold + doubling broadcast, full payload
    chunk = -(-nbytes // k)
    pair = 2 if preserve_order else 1
    # reduce-scatter ships the accumulator (pair or single chunk) k-1
    # times; the allgather ships one chunk k-1 times
    return (k - 1) * chunk * (pair + 1)


# ---------------------------------------------------------------------------
# static structure: chunk layout, ring routing, index formulas
# (polymorphic over Python ints and traced values — shared with the
# lockstep simulator in tests/test_algos.py)
# ---------------------------------------------------------------------------


def chunk_layout(n: int, k: int):
    """(elements per chunk, padded element count ``k·chunk``) for an
    ``n``-element payload split into ``k`` ring chunks."""
    chunk = -(-n // k)
    return chunk, chunk * k


def ring_pairs(groups):
    """Static ppermute pairs of the ring: every rank sends to its group
    ring-successor, every round (only the circulating chunk indices
    rotate).  Singleton groups need no edges."""
    return [
        (members[p], members[(p + 1) % len(members)])
        for members in groups
        if len(members) > 1
        for p in range(len(members))
    ]


def rs_send_chunk(pos, r, k):
    """Chunk index group-position ``pos`` sends in reduce-scatter round
    ``r`` (chunk ``c``'s journey starts at position ``(c+1) % k`` and walks
    the ring ascending, ending at position ``c`` after ``k-1`` hops)."""
    return (pos - r - 1) % k


def rs_recv_chunk(pos, r, k):
    """Chunk index group-position ``pos`` receives in reduce-scatter round
    ``r`` (= the predecessor's ``rs_send_chunk``)."""
    return (pos - r - 2) % k


def ag_recv_chunk(pos, r, k):
    """Chunk index received in allgather round ``r`` at position ``pos``
    (entering round ``r`` each position holds chunk ``(pos - r) % k``)."""
    return (pos - r - 1) % k


def rs_update_pair(where, fn, pos, c, k, lo_in, hi_in, mine):
    """Order-preserving reduce-scatter accumulator update at the receiving
    position ``pos`` for chunk ``c``.

    Chunk ``c``'s ring journey visits positions ``c+1 … k-1`` then (after
    wrapping past the ring seam) ``0 … c``.  Associativity alone cannot
    repair a cyclically rotated fold, so the accumulator is a pair:
    ``hi`` folds the pre-wrap segment ``x_{c+1} … x_{k-1}`` and ``lo`` the
    post-wrap segment ``x_0 … x_c``, each in ascending group order; the
    final value is ``lo ∘ hi`` (``rs_finish_pair``) — the exact ascending
    fold the butterfly produces, commutativity never required.

    ``where(cond, a, b)`` is supplied by the caller: ``jnp.where`` when
    traced, a plain Python select in the simulator tests.  Both branches
    are evaluated; discarded garbage (the ``lo`` placeholder before the
    wrap) never reaches a kept lane.
    """
    pre = (pos > c) | (c == k - 1)  # chunk k-1's journey never wraps
    lo = where(pre, lo_in, where(pos == 0, mine, fn(lo_in, mine)))
    hi = where(pre, fn(hi_in, mine), hi_in)
    return lo, hi


def rs_finish_pair(where, fn, pos, k, lo, hi):
    """Final order-preserving reduce-scatter value at position ``pos``
    (which owns chunk ``pos``): ``lo ∘ hi``, except chunk ``k-1`` whose
    journey never wrapped (``lo`` still holds its placeholder)."""
    return where(pos == k - 1, hi, fn(lo, hi))


def rotation_pairs(groups, t: int):
    """Static ppermute pairs of alltoall pairwise-exchange round ``t``:
    every group position ``p`` sends to position ``(p + t) % k`` — one
    rotation per round, ``k - 1`` rounds total (round 0 is the local
    own-block copy).  Singleton groups need no edges."""
    return [
        (members[p], members[(p + t) % len(members)])
        for members in groups
        if len(members) > 1
        for p in range(len(members))
    ]


def a2a_send_block(pos, t, k):
    """Block index group-position ``pos`` ships in pairwise-exchange
    round ``t``: the block addressed to its round-``t`` partner
    ``(pos + t) % k``."""
    return (pos + t) % k


def a2a_recv_slot(pos, t, k):
    """Source position whose block arrives at ``pos`` in round ``t`` (=
    the output slot it fills): the rotation's inverse, ``(pos - t) % k``."""
    return (pos - t) % k


def next_pow2(k: int) -> int:
    return 1 << max(0, (k - 1).bit_length())


def vdg_widths(K: int):
    """Binomial-scatter half-widths for ``K = next_pow2(k)`` virtual
    chunks: K/2, K/4, …, 1."""
    w = K >> 1
    out = []
    while w >= 1:
        out.append(w)
        w >>= 1
    return out


def vdg_scatter_pairs(groups, root, w, K):
    """Static ppermute pairs of one binomial-scatter round: every holder at
    relative position ``r0`` (``r0 % 2w == 0``) sends virtual chunks
    ``[r0+w, r0+2w)`` to relative position ``r0+w``; pairs whose receiver
    falls outside the (uniform) group carry only padding chunks and are
    dropped.  Relative positions are group positions rotated by ``root``
    (the same convention as ``apply_doubling_bcast``)."""
    pairs = []
    for members in groups:
        kk = len(members)
        for r0 in range(0, K, 2 * w):
            if r0 + w < kk:
                pairs.append((members[(root + r0) % kk],
                              members[(root + r0 + w) % kk]))
    return pairs


# ---------------------------------------------------------------------------
# traced appliers
# ---------------------------------------------------------------------------


# One ``jax.named_scope`` per phase, under the calling op's own
# ``mpi4jax_tpu.<op>`` scope (utils/debug.op_scope): a ring allreduce reads
# ``mpi4jax_tpu.allreduce/ring_reduce_scatter/...`` then ``.../ring_allgather``
# in HLO metadata and in xprof.  Metadata only: the operations are the same.

@jax.named_scope("ring_reduce_scatter")
def apply_ring_reduce_scatter(blocks, op, comm, k: int):
    """Ring reduce-scatter of ``blocks`` (shape ``(k, *s)``) over ``comm``:
    group position ``p`` receives ``fold_j blocks_j[p]`` in ascending group
    order (MPI_Reduce_scatter_block semantics), shape ``(*s,)``.

    ``k-1`` ppermute rounds, each carrying one block (two for
    order-preserving callables) — O(size·(k-1)/k) bytes per rank.  Enum
    ``Op``s are commutative, so they circulate a single accumulator in the
    ring's natural (cyclically rotated) fold order; callables get the
    lo/hi pair that preserves the ascending fold (``rs_update_pair``).
    """
    from ._base import Op, _comm_groups, _permute_axis, combine_fn

    if k == 1:
        return blocks[0]
    fn = combine_fn(op)
    pos = comm.Get_rank()
    axis = _permute_axis(comm)
    pairs = ring_pairs(_comm_groups(comm))
    preserve = not isinstance(op, Op)
    start = jnp.take(blocks, (pos - 1) % k, axis=0)
    if preserve:
        lo, hi = start, start  # lo is a placeholder until the wrap entry
        for r in range(k - 1):
            c = rs_recv_chunk(pos, r, k)
            mine = jnp.take(blocks, c, axis=0)
            recvd = lax.ppermute(jnp.stack([lo, hi]), axis, pairs)
            lo, hi = rs_update_pair(
                jnp.where, fn, pos, c, k, recvd[0], recvd[1], mine
            )
        return rs_finish_pair(jnp.where, fn, pos, k, lo, hi)
    acc = start
    for r in range(k - 1):
        c = rs_recv_chunk(pos, r, k)
        mine = jnp.take(blocks, c, axis=0)
        acc = fn(lax.ppermute(acc, axis, pairs), mine)
    return acc


@jax.named_scope("ring_allgather")
def apply_ring_allgather(v, comm, k: int, pos):
    """Ring allgather: position ``pos`` contributes ``v`` (shape ``(*s,)``)
    as chunk ``pos``; every position receives ``(k, *s)`` in group order.
    ``k-1`` ppermute rounds of one chunk each."""
    from ._base import _comm_groups, _permute_axis

    out = jnp.zeros((k,) + v.shape, v.dtype).at[pos].set(v)
    if k == 1:
        return out
    axis = _permute_axis(comm)
    pairs = ring_pairs(_comm_groups(comm))
    cur = v
    for r in range(k - 1):
        cur = lax.ppermute(cur, axis, pairs)
        out = out.at[ag_recv_chunk(pos, r, k)].set(cur)
    return out


def _pad_to(flat, total):
    n = flat.shape[0]
    if total == n:
        return flat
    return jnp.concatenate([flat, jnp.zeros((total - n,), flat.dtype)])


def apply_ring_allreduce(x, op, comm, k=None):
    """Bandwidth-optimal allreduce: ring reduce-scatter + ring allgather.

    Moves ``~2·(k-1)/k·size`` bytes per rank (``3·(k-1)/k`` for
    order-preserving callables) over ``2·(k-1)`` chunk-sized ppermute
    rounds, vs the butterfly's ``2·ceil(log2 k)`` full-payload rounds —
    the asymptotic win for gradient buckets and halo frames.  Same
    contract as ``apply_butterfly_allreduce``: all 10 ``Op``s plus
    associative callables folded in ascending group-rank order (callables
    must be ELEMENTWISE here — the payload is chunked; see module
    docstring).  Requires a uniform static group size.
    """
    from ._base import as_varying

    if k is None:
        k = comm.Get_size()
    x = as_varying(x, comm.axes)
    if k == 1:
        return x
    shape, n = x.shape, x.size
    chunk, padded = chunk_layout(n, k)
    blocks = _pad_to(x.reshape(-1), padded).reshape(k, chunk)
    mine = apply_ring_reduce_scatter(blocks, op, comm, k)
    full = apply_ring_allgather(mine, comm, k, comm.Get_rank())
    return full.reshape(-1)[:n].reshape(shape)


@jax.named_scope("pairwise_exchange")
def apply_pairwise_alltoall(blocks, comm, k: int):
    """Pairwise-exchange alltoall of ``blocks`` (shape ``(k, *s)``,
    block ``i`` addressed to group position ``i``) over ``comm``:
    position ``p`` receives ``(k, *s)`` where ``out[q]`` is position
    ``q``'s block addressed to ``p``.

    ``k - 1`` ppermute rounds, round ``t`` rotating every position's
    round-``t`` block one ``t``-step around the group
    (``rotation_pairs``) — one chunk-sized message per rank per round,
    the classic pairwise schedule.  This is the expressible-anywhere
    building block of the hierarchical alltoall (ops/_hierarchy.py) and
    the chunked async split (ops/_async.py): unlike the native AllToAll
    HLO it works on color-split comms, and unlike the allgather-based
    group lowering it ships each rank only its own O(size) bytes.
    Requires a uniform static group size.  Pure routing — bit-identical
    to any other alltoall lowering by construction.
    """
    from ._base import _comm_groups, _permute_axis, as_varying

    blocks = as_varying(blocks, comm.axes)
    if k == 1:
        return blocks
    pos = comm.Get_rank()
    axis = _permute_axis(comm)
    groups = _comm_groups(comm)
    out = jnp.zeros_like(blocks)
    out = out.at[pos].set(jnp.take(blocks, pos, axis=0))  # own block
    for t in range(1, k):
        pairs = rotation_pairs(groups, t)
        send = jnp.take(blocks, a2a_send_block(pos, t, k), axis=0)
        recvd = lax.ppermute(send, axis, pairs)
        out = out.at[a2a_recv_slot(pos, t, k)].set(recvd)
    return out


@jax.named_scope("binomial_scatter")
def apply_binomial_scatter(buf, groups, root: int, axis, relpos, K: int):
    """The binomial-halving scatter phase shared by ``apply_vdg_bcast``
    (over the whole comm) and the hierarchical broadcast (over the
    intra-host blocks — ops/_hierarchy.py): ``buf`` holds ``K`` virtual
    chunk rows addressed by ABSOLUTE chunk index, ``relpos`` is this
    rank's position in the root-rotated frame (= the chunk index it ends
    up owning).  Each round halves the in-flight span; pairs whose
    receiver falls outside a group carry only padding and are dropped by
    ``vdg_scatter_pairs``; non-participants' clamped slices are garbage
    no pair routes and the ``where`` discards."""
    for w in vdg_widths(K):
        pairs = vdg_scatter_pairs(groups, root, w, K)
        if not pairs:
            continue
        slab = lax.dynamic_slice_in_dim(buf, relpos + w, w, axis=0)
        recvd = lax.ppermute(slab, axis, pairs)
        is_recv = (relpos % (2 * w)) == w
        buf = jnp.where(
            is_recv,
            lax.dynamic_update_slice_in_dim(buf, recvd, relpos, axis=0),
            buf,
        )
    return buf


def apply_vdg_bcast(x, comm, root: int, k=None):
    """Large-payload broadcast: binomial-halving scatter from ``root`` +
    ring allgather (van de Geijn).

    The scatter tree halves the in-flight payload every round (root ships
    ``~size`` bytes total; ``ceil(log2 k)`` rounds), then the ring
    allgather circulates one chunk per round (``k-1`` rounds,
    ``(k-1)/k·size`` bytes per rank) — ~2·size bytes per rank end to end,
    vs ``size·ceil(log2 k)`` for ``apply_doubling_bcast``.  The chunk
    count is padded to the next power of two so any uniform group size
    lowers cleanly; padding chunks ride the scatter slabs but are dropped
    by the final reshape.  Requires a uniform static group size.
    """
    from ._base import _comm_groups, _permute_axis, as_varying

    if k is None:
        k = comm.Get_size()
    groups = _comm_groups(comm)
    kmin = min(len(g) for g in groups)
    if not 0 <= root < kmin:
        raise ValueError(
            f"apply_vdg_bcast: root {root} out of range for the smallest "
            f"group (size {kmin}); root must be a valid group position in "
            "every group"
        )
    x = as_varying(x, comm.axes)
    if k == 1:
        return x
    pos = comm.Get_rank()
    relpos = (pos - root) % k
    axis = _permute_axis(comm)
    shape, n = x.shape, x.size
    chunk, _ = chunk_layout(n, k)
    K = next_pow2(k)
    buf = _pad_to(x.reshape(-1), K * chunk).reshape(K, chunk)
    # senders (relpos % 2w == 0) hold virtual chunks [relpos, relpos+2w)
    # and ship the far half; the receiver at relpos+w writes it at its
    # OWN relpos (see apply_binomial_scatter)
    buf = apply_binomial_scatter(buf, groups, root, axis, relpos, K)
    mine = jnp.take(buf, relpos, axis=0)  # this rank's real chunk (relpos < k)
    full = apply_ring_allgather(mine, comm, k, relpos)
    return full.reshape(-1)[:n].reshape(shape)


def apply_reduce_scatter(xl, op, comm):
    """Lowering of the public ``reduce_scatter`` op: ``(k, *s)`` blocks in,
    ``(*s,)`` out — group position ``p`` receives the ascending-group-order
    fold of every member's block ``p``.

    Native path: one ``psum_scatter`` HLO for SUM on a whole single-axis
    comm under ``auto``.  Otherwise butterfly (allreduce the block stack,
    keep own block — O(size·log k) bytes) vs ring (O(size·(k-1)/k) bytes)
    vs the two-level hierarchical split (``_hierarchy``: intra-host
    reduce-scatter of position super-blocks over ICI, inter-host
    reduce-scatter of the per-host partials over DCN) by the selector.
    Blocks are the user's own, so block-wise callables (including
    whole-block ops like ``jnp.matmul``, which batch over the leading
    axis) are valid on EVERY algorithm — the chunked-allreduce
    elementwise caveat does not apply here.
    """
    from ._base import Op, apply_butterfly_allreduce, as_varying
    from ..analysis.hook import annotate
    from ..telemetry.core import annotate as t_annotate
    from . import _hierarchy

    k = comm.Get_size()  # static; raises the clear error on unequal splits
    xl = as_varying(xl, comm.axes)
    if k == 1:
        return xl[0]
    algo = config.collective_algo()
    if (algo == "auto" and op is Op.SUM and comm.groups is None
            and len(comm.axes) == 1):
        try:
            res = lax.psum_scatter(
                xl, comm.axes[0], scatter_dimension=0, tiled=False
            )
            annotate(algo="native")
            t_annotate(algo="native")
            return res
        except NotImplementedError:  # shard_map/backend gap: fall through
            pass
    plan = _hierarchy.hier_plan(comm)
    nbytes = xl.size * xl.dtype.itemsize
    algo = resolve_algo(algo, nbytes, k, ring_ok=True,
                        hier_ok=plan is not None)
    _hierarchy.annotate_selection("reduce_scatter", algo, nbytes, k, plan,
                                  comm, preserve=not isinstance(op, Op),
                                  op=op, dtype=xl.dtype.name)
    if algo == "hier":
        return _hierarchy.apply_hier_reduce_scatter(xl, op, comm, plan)
    if algo == "ring":
        return apply_ring_reduce_scatter(xl, op, comm, k)
    full = apply_butterfly_allreduce(xl, op, comm)
    return jnp.take(full, comm.Get_rank(), axis=0)
