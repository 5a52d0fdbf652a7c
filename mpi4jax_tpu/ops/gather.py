"""gather: collect every rank's array at root.

TPU-native re-design of ref mpi4jax/_src/collective_ops/gather.py.  The
reference has a *rank-dependent output shape* — ``(size, *s)`` on root, the
input passed through on other ranks (ref gather.py:92-95, abstract
:270-284).  SPMD traces one program with one output type for all ranks, so
the shape is made uniform: **every rank receives the gathered ``(size, *s)``
array** (root's view is bit-identical to the reference's).  This is the
documented divergence for the gather family (see docs/sharp_bits.md); on ICI
the extra fan-out is handled by the AllGather HLO's bandwidth-optimal ring
schedule, so there is no latency cost over a rooted gather.
"""

from typing import Optional

from ..parallel.comm import Comm
from ..utils.debug import log_op
from ..utils.validation import enforce_types
from ._base import (all_gather_blocks, dispatch, gather_view,
                    group_select_gather)
from .token import Token, consume, produce


@enforce_types(root=int, comm=(Comm, None), token=(Token, None))
def gather(x, root: int, *, comm: Optional[Comm] = None,
           token: Optional[Token] = None):
    """Gather ``x`` from every rank to ``root`` (all ranks receive a copy —
    see module docstring).

    Returns ``(result, token)`` (ref API: gather.py:40-96).
    """

    def body(comm, arrays, token):
        (xl,) = arrays
        size = comm.Get_size()
        if not 0 <= root < size:
            from ..analysis.report import mpx_error

            raise mpx_error(
                ValueError, "MPX105",
                f"gather root {root} out of range for size {size}",
            )
        xl = consume(token, xl)
        log_op("MPI_Gather", comm.Get_rank(),
               f"sending {xl.size} items to root {root} "
               f"({gather_view(xl)} view)")
        if comm.groups is not None:
            # color split (uniform): same uniform-shape divergence as the
            # whole-axes form, selected per group
            res = group_select_gather(comm, xl)
        else:
            # multi-axis comms gather in row-major rank order
            res = all_gather_blocks(comm, xl)
        return res, produce(token, res)

    return dispatch("gather", comm, body, (x,), token, static_key=(root,),
                    ana={"root": root})
