"""Plain NumPy reference of the chained-collective programs.

Each program of the sweep chains one collective ``chain`` times with a
fixed elementwise rescale between the links (see ``PROGRAMS`` in
``chipbench/drivers/collectives.py`` for the program side; the semantics
are stated here, from MPI's definitions, with no code shared).  Every
operation acts position by position inside a block, so the reference
follows a sample of positions through the whole chain: ``x`` holds, for
every rank (and for the k-block operations every block), the input's
values at the sampled positions only.

``k`` ranks; ``growth = 1 + 2**-8``; ``coef[j] = (1 + j * 2**-8) / k``.

- allreduce:      v'[r]    = growth / k * sum_q v[q]
- reduce_scatter: x'[r][j] = coef[j] * sum_q x[q][r]
- allgather:      v'[r]    = sum_j coef[j] * v[j]
- alltoall:       x'[r][i] = growth * x[i][r]
- sendrecv:       v'[r]    = v[(r - 1) mod k]          (dest = shift(1))

``dtype`` is the precision every value and sum is carried in: float32 as
the configuration states, or ``ml_dtypes.bfloat16`` for the control.
"""

import numpy as np

GROWTH = 1.0 + 2.0 ** -8


def coefficients(k: int) -> np.ndarray:
    return (1.0 + np.arange(k) * 2.0 ** -8) / k


def _link(op: str, x: np.ndarray, k: int, dtype) -> np.ndarray:
    growth = dtype(GROWTH)
    coef = coefficients(k).astype(dtype)
    if op == "allreduce":
        total = x.sum(axis=0, dtype=dtype)
        return np.broadcast_to(total * dtype(GROWTH / k), x.shape).copy()
    if op == "reduce_scatter":
        reduced = x.sum(axis=0, dtype=dtype)            # [block r] -> rank r
        return (reduced[:, None, :] * coef[None, :, None]).astype(dtype)
    if op == "allgather":
        mixed = (x * coef[:, None]).astype(dtype).sum(axis=0, dtype=dtype)
        return np.broadcast_to(mixed, x.shape).copy()
    if op == "alltoall":
        return (np.swapaxes(x, 0, 1) * growth).astype(dtype)
    if op == "sendrecv":
        return np.roll(x, 1, axis=0)
    raise ValueError(f"no reference for op {op!r}")


def run_chain(op: str, x: np.ndarray, chain: int, dtype=np.float32):
    """``x``: ``(k, m)`` for allreduce, allgather and sendrecv, ``(k, k,
    m)`` for reduce_scatter and alltoall (rank, block, sampled position).
    Returns the loop carry after ``chain`` links, which is what the
    program returns, at those positions, as float32."""
    k = x.shape[0]
    x = x.astype(dtype)
    for _ in range(chain):
        x = _link(op, x, k, dtype)
    return x.astype(np.float32)
