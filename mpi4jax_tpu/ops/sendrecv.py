"""sendrecv: paired exchange — the halo-exchange workhorse.

TPU-native re-design of ref mpi4jax/_src/collective_ops/sendrecv.py (495 LoC).
One matched send+receive per rank, described collectively by a static routing
spec (``shift``/dict/pairs — see parallel/rankspec.py), lowering to a single
CollectivePermute HLO over ICI.

Autodiff parity (ref sendrecv.py:417-480) comes from JAX's ppermute rules:

- transpose swaps source and dest (ppermute transposes to the inverse
  permutation — exactly the reference's ``_must_transpose`` source/dest swap);
- reverse-mode through jit/grad works (matvec acceptance suite);
- forward-mode: the reference *raises* because a tangent traced on one
  process would land on the wrong rank (ref sendrecv.py:150-155).  Here the
  SPMD program traces all ranks at once, so the tangent is permuted alongside
  the primal and forward-mode is simply correct — a documented improvement.

Ranks without a source in the routing receive their ``recvbuf`` template back
(MPI_PROC_NULL semantics); ranks without a destination send nothing.
"""

from typing import Optional

import jax.numpy as jnp

from jax import lax

from ..parallel.comm import Comm
from ..parallel.rankspec import resolve_routing
from ..utils.debug import log_op
from ..utils.validation import enforce_types
from ._base import _permute_axis, dispatch
from .status import Status
from .token import Token, consume, produce


def _apply_permute(xl, recvbuf, pairs, comm):
    """Run one CollectivePermute along GLOBAL pairs (routing specs are
    resolved through ``rankspec.resolve_routing`` before this).

    An identity routing — every pair ``(r, r)``, e.g. any wrapping
    ``shift`` on a size-1 axis — skips the collective entirely: the
    permutation is a per-rank no-op, and a CollectivePermute that moves
    nothing still costs a copy.
    Empty pairs (a non-wrapping shift on a size-1 axis) elide the same
    way — the receiver mask below already hands every rank its recvbuf.
    Transpose/AD semantics are unchanged (the inverse of the identity is
    the identity, matching ppermute's transpose rule)."""
    if all(s == d for s, d in pairs):
        permuted = xl
    else:
        # multi-axis comms permute over the linearized row-major rank
        # order — the same order Get_rank defines (parallel/comm.py)
        permuted = lax.ppermute(xl, _permute_axis(comm), list(pairs))
    # the output is typed by the recv buffer (ref sendrecv.py:369-377
    # abstract eval): a message with a matching element count but different
    # shape — e.g. exchange-row-for-column — lands in recvbuf's shape
    permuted = permuted.reshape(recvbuf.shape)
    receivers = sorted(d for _, d in pairs)
    if len(receivers) == comm.world_size():
        return permuted
    rank = comm.global_rank()
    is_recv = jnp.isin(rank, jnp.asarray(receivers))
    return jnp.where(is_recv, permuted, recvbuf)


def _fill_status(status, pairs, comm, count, dtype, tag):
    """``pairs`` are GLOBAL; ``Status.source`` reports the comm-local rank
    of the sender (on a color-split comm the two differ, per MPI)."""
    if status is None:
        return
    rank = comm.global_rank()
    size = comm.world_size()
    src_table = [-1] * size  # MPI_PROC_NULL analog for no-source ranks
    for s, d in pairs:
        src_table[d] = comm.local_rank_of(s)
    status.source = jnp.asarray(src_table)[rank]
    # the tag the matched message was sent with (ref recv.py:43-48 fills the
    # full MPI.Status); matching is SPMD-uniform so this is static
    status.tag = tag
    status.count = count
    status.dtype = dtype


@enforce_types(sendtag=int, recvtag=int, comm=(Comm, None),
               status=(Status, None), token=(Token, None))
def sendrecv(
    sendbuf,
    recvbuf,
    source=None,
    dest=None,
    *,
    sendtag: int = 0,
    recvtag: int = 0,
    comm: Optional[Comm] = None,
    status: Optional[Status] = None,
    token: Optional[Token] = None,
):
    """Simultaneously send ``sendbuf`` and receive into ``recvbuf``'s shape
    along a static routing pattern.

    ``dest`` maps sender→receiver (e.g. ``shift(1)``); ``source`` is the
    receiver-centric view.  Give either (the other is inferred) or both
    (validated for consistency).  Returns ``(received, token)``
    (ref API: sendrecv.py:46-128).

    Tags are accepted for API parity but are *inert* for matching: a
    ``sendrecv`` is self-contained (one fused CollectivePermute), so the
    incoming message always comes from this same call and always carries
    ``sendtag``.  Ported MPI idioms with differing send/recv tags (e.g.
    swapped-tag bidirectional exchanges) therefore route correctly;
    ``Status.tag`` reports ``sendtag`` — the tag the message was actually
    sent with.
    """
    from ..analysis.report import mpx_error

    if sendbuf.dtype != recvbuf.dtype:
        raise mpx_error(
            ValueError, "MPX106",
            f"sendrecv requires matching send/recv dtypes (MPI type-signature "
            f"rule); got {sendbuf.dtype} vs {recvbuf.dtype}",
        )
    if sendbuf.shape != recvbuf.shape and sendbuf.size != recvbuf.size:
        raise mpx_error(
            ValueError, "MPX106",
            f"sendrecv: send/recv buffers may differ in shape only when their "
            f"element counts match (the output is typed by recvbuf, ref "
            f"sendrecv.py:369; under SPMD every rank's recv shape is the same "
            f"static recvbuf shape, so mismatched counts cannot be routed); "
            f"got {sendbuf.shape} vs {recvbuf.shape}. See docs/sharp_bits.md.",
        )

    # Eager-path caching: resolve the routing spec to concrete pairs ONCE,
    # up front, and close the body over the *resolved* pairs — the cached
    # program can then never re-read a mutated spec object, even on a
    # shape-triggered internal retrace.  The cache key uses the same pairs,
    # so callables/dicts with identical routing share an entry.  A Status
    # out-param must be filled at trace time, so those calls are
    # uncacheable.  Inside a region, pairs resolve at trace time instead
    # (comm size may only be known from the axis environment there).
    static_key = None
    resolved_pairs = None
    if status is None:
        from ..parallel.region import in_parallel_region, resolve_comm

        c = resolve_comm(comm)
        if c.mesh is not None and not in_parallel_region(c):
            resolved_pairs = resolve_routing(c, source, dest, what="sendrecv")
            static_key = (resolved_pairs, sendtag, recvtag)

    def body(comm, arrays, token):
        from ..analysis.hook import annotate

        xl, rbuf = arrays
        pairs = resolved_pairs
        if pairs is None:  # in-region: resolve at trace time, already GLOBAL
            pairs = resolve_routing(comm, source, dest, what="sendrecv")
        annotate(pairs=pairs)
        xl = consume(token, xl)
        log_op("MPI_Sendrecv", comm.Get_rank(),
               f"{xl.size} items along {list(pairs)}")
        res = _apply_permute(xl, rbuf, pairs, comm)
        _fill_status(status, pairs, comm, xl.size, xl.dtype, sendtag)
        return res, produce(token, res)

    return dispatch(
        "sendrecv", comm, body, (sendbuf, recvbuf), token,
        static_key=static_key, ana={"tag": sendtag},
    )
