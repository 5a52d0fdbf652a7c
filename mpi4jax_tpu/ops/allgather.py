"""allgather: gather every rank's array to all ranks.

TPU-native re-design of ref mpi4jax/_src/collective_ops/allgather.py.  Shape
contract preserved exactly: input ``s`` -> output ``(size, *s)`` on every rank
(ref allgather.py:229-236 abstract eval).  Lowering: one AllGather HLO
(``_base.all_gather_blocks``, which says why): a 1-D block whose length is a
multiple of 128 is gathered through its lane-shaped view ``(N // 128, 128)``
and the result reshaped to ``(size, N)``; every other block (another length,
or two or more dimensions, where the rank axis is major already) as it is.
"""

from typing import Optional

from ..parallel.comm import Comm
from ..utils.debug import log_op
from ..utils.validation import enforce_types
from ._base import (all_gather_blocks, dispatch, gather_view,
                    group_select_gather)
from .token import Token, consume, produce


@enforce_types(comm=(Comm, None), token=(Token, None))
def allgather(x, *, comm: Optional[Comm] = None, token: Optional[Token] = None):
    """Gather ``x`` from every rank; all ranks receive ``(size, *x.shape)``.

    Returns ``(result, token)`` (ref API: allgather.py:38-76).
    """

    def body(comm, arrays, token):
        (xl,) = arrays
        xl = consume(token, xl)
        log_op("MPI_Allgather", comm.Get_rank(),
               f"sending {xl.size} items ({gather_view(xl)} view)")
        if comm.groups is not None:
            # color split (uniform group sizes): output (group_size, *s)
            res = group_select_gather(comm, xl)
        else:
            res = all_gather_blocks(comm, xl)
        return res, produce(token, res)

    return dispatch("allgather", comm, body, (x,), token, static_key=())
