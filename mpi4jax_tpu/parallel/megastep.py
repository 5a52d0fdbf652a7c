"""Megastep execution: device-resident multi-step loops.

A pinned program still crosses Python once per step.
The megastep compiler ends that the way CUDA Graphs' capture-and-replay
amortizes launch overhead — ``mpx.compile(fn, unroll=N)`` (and
``mpx.spmd(..., unroll=N)``) rewrite the step body into a device-resident
``lax.fori_loop`` over N iterations, so ONE host dispatch executes N
steps and the per-step host cost falls as 1/N.

:func:`megastep_loop` is the shared loop builder (the ``spmd``/pin region
body in parallel/region.py and the elastic step adapter in
aot/pinning.py both call it):

- **carry contract**: the iteration body must map its carry pytree to an
  output of identical structure, shapes, and dtypes (state -> state; the
  ``lax.fori_loop`` requirement).  A mismatch raises a ``ValueError``
  naming the offending leaf at trace time.  Carries are re-typed
  rank-varying over the comm's axes each iteration, so collective
  results (replicated-typed in JAX's collective type system) are legal
  carries without a manual ``mpx.varying``;
- **per-iteration fusion**: the deferral queue (ops/_fusion.py) is
  flushed and every deferred result materialized at the END of the loop
  body, so fusion buckets formed inside the body stay per-iteration — no
  cross-iteration packing (the lockstep simulator pins bucketing per
  dispatch sequence, and a bucket straddling iterations would not exist
  at run time anyway: the body traces once);
- **span rule**: an async ``*_start``/``*_wait`` span may not straddle
  the loop boundary — a start without its wait inside the same iteration
  would arm instrumentation the next iteration cannot close.  Events
  recorded inside the body carry the loop scope, and the MPX130 checker
  (analysis/checkers.py) errors on straddling spans (``mpx.analyze`` or
  ``MPI4JAX_TPU_ANALYZE=error``);
- **watchdog**: when the collective watchdog is armed, one extra bracket
  wraps the WHOLE megastep with the deadline scaled by N (per-op arms
  inside the loop keep their per-collective deadline — a single hung
  collective still trips at the per-op timeout; the outer bracket covers
  the loop machinery itself);
- **telemetry**: in the ``events`` tier the megastep contributes ONE
  begin/end journal bracket (op ``megastep``, tagged with ``unroll``)
  per execution plus a synthesized per-step latency estimate
  (``latency / N`` fed into the ``megastep_step`` histogram by the
  journal — bucket math on the host, no extra io_callbacks on the hot
  path).

``unroll=1`` never reaches this module: callers keep their original body
construction, so the traced program and HLO are byte-identical to a
build without the megastep layer (pinned by tests/test_megastep.py).
"""

from __future__ import annotations

import itertools

__all__ = ["megastep_loop", "register_boundary_hook",
           "run_boundary_hooks", "tracing_megastep", "validate_unroll"]

_loop_ids = itertools.count(1)

# ---------------------------------------------------------------------------
# megastep boundary hooks (host-side)
# ---------------------------------------------------------------------------
#
# A megastep's BOUNDARY — the host-side gap between two device-resident
# dispatches — is the only point where anything outside the program can
# act: the serving runtime admits/evicts requests there
# (mpi4jax_tpu/serving/engine.py), the elastic layer executes planned
# drains there, tests observe cadence there.  The registry keeps those
# consumers decoupled from the loops that own the boundary: a driver
# calls ``run_boundary_hooks(step, **info)`` once per boundary and every
# registered hook fires in registration order.  Pure host Python — never
# traced, never in the program.

_boundary_hooks: list = []   # (name, fn)


def register_boundary_hook(name: str, fn):
    """Register ``fn(step, **info)`` to run at every megastep boundary a
    driver publishes.  Returns a zero-argument unregister callable.
    Hook exceptions propagate to the driver — a boundary consumer that
    fails must stop the loop, not be silently dropped."""
    if not callable(fn):
        raise TypeError(f"boundary hook {name!r} must be callable")
    entry = (str(name), fn)
    _boundary_hooks.append(entry)

    def unregister():
        try:
            _boundary_hooks.remove(entry)
        except ValueError:
            pass

    return unregister


def run_boundary_hooks(step: int, **info) -> list:
    """Fire every registered hook for boundary ``step``; returns
    ``[(name, result), ...]`` in registration order."""
    return [(name, fn(step, **info)) for name, fn in list(_boundary_hooks)]

# nesting depth of megastep loop-body traces (the config-snapshot twin
# of aot.pinning's _pinning_depth; the checker-facing discriminator is
# the per-event ``loop`` stamp, see tracing_megastep)
_megastep_depth = 0


def tracing_megastep() -> bool:
    """True while a megastep loop body is being traced.

    Informational: ``analysis.hook.config_snapshot`` records it as the
    ``megastep`` meta key (a graph snapshotted mid-body says so), but
    the MPX128/MPX130 checkers key on the PER-EVENT ``loop`` stamp —
    events recorded inside the body carry their loop id — because by
    the time a region's checkers run the body trace has already
    exited."""
    return _megastep_depth > 0


def validate_unroll(unroll) -> int:
    """Normalize an ``unroll=`` argument: a positive int (1 = no loop)."""
    try:
        n = int(unroll)
    except (TypeError, ValueError):
        raise TypeError(
            f"unroll must be a positive integer, got {unroll!r}"
        ) from None
    if n < 1:
        raise ValueError(f"unroll must be >= 1, got {unroll!r}")
    return n


class _loop_trace_scope:
    """Marks one loop body's trace: bumps the module depth and stamps the
    region context so ``analysis.hook.begin_event`` tags every event
    recorded inside with ``(loop_id, unroll)``."""

    __slots__ = ("ctx", "scope", "saved")

    def __init__(self, ctx, loop_id: int, unroll: int):
        self.ctx = ctx
        self.scope = (loop_id, unroll)
        self.saved = None

    def __enter__(self):
        global _megastep_depth
        _megastep_depth += 1
        self.saved = getattr(self.ctx, "megastep", None)
        self.ctx.megastep = self.scope
        return self

    def __exit__(self, *exc):
        global _megastep_depth
        _megastep_depth -= 1
        self.ctx.megastep = self.saved
        return False


def _carry_signature(jax, jnp, tree):
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple(
        (tuple(jnp.shape(leaf)), str(jnp.result_type(leaf)))
        for leaf in leaves
    )


def _check_carry(jax, jnp, treedef0, sig0, out, label: str):
    treedef1, sig1 = _carry_signature(jax, jnp, out)
    if treedef1 != treedef0:
        raise ValueError(
            f"megastep carry contract violated in {label!r}: the loop "
            f"body returned pytree structure {treedef1} but its carry "
            f"(the dynamic arguments) has structure {treedef0}.  With "
            "unroll > 1 the step must map its state to a like-structured "
            "state (docs/aot.md 'Megastep execution')."
        )
    for i, (got, want) in enumerate(zip(sig1, sig0)):
        if got != want:
            raise ValueError(
                f"megastep carry contract violated in {label!r}: carry "
                f"leaf {i} went in as shape/dtype {want} and came out as "
                f"{got} — a lax.fori_loop carry must keep its "
                "shapes/dtypes (docs/aot.md 'Megastep execution')."
            )


def megastep_loop(body_fn, carry, unroll: int, comm, label: str = "fn"):
    """Run ``carry = body_fn(i, carry)`` for ``unroll`` device-resident
    iterations inside the CURRENT parallel region's trace.

    ``body_fn(i, carry)`` is the per-rank iteration (``i`` is the traced
    loop index); ``carry`` is any pytree obeying the carry contract
    above.  Returns the final carry.  ``unroll == 1`` degenerates to a
    single direct call — no loop, no brackets, byte-identical trace.
    """
    n = validate_unroll(unroll)
    if n == 1:
        return body_fn(0, carry)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops import _fusion
    from ..ops._base import _next_call_id, as_varying
    from .region import current_context

    ctx = current_context()
    loop_id = next(_loop_ids)

    # stabilize the carry typing up front: region inputs are rank-varying
    # already (no-op), but replicated trace constants fed as initial state
    # must match the varying-typed body output
    carry = jax.tree.map(lambda v: as_varying(jnp.asarray(v), comm.axes),
                         carry)
    treedef0, sig0 = _carry_signature(jax, jnp, carry)

    def one(i, c):
        with _loop_trace_scope(ctx, loop_id, n):
            out = body_fn(i, c)
            # per-iteration drain: buckets formed inside the body stay
            # per-iteration, and deferred LazyResults never leak into the
            # fori_loop carry
            _fusion.flush_pending(ctx)
            out = _fusion.materialize_tree(out)
            if ctx.pending_sync is not None:
                # a trailing tokenless barrier inside the iteration: tie
                # it into the carry so each iteration's barrier survives
                from ..ops.token import tie

                sync = ctx.pending_sync
                ctx.pending_sync = None
                out = jax.tree.map(lambda v: tie(sync, v), out)
        _check_carry(jax, jnp, treedef0, sig0, out, label)
        return jax.tree.map(lambda v: as_varying(v, comm.axes), out)

    leaves = jax.tree.leaves(carry)

    # whole-megastep watchdog bracket, deadline scaled by the trip count
    # (resilience/runtime.py per-op arms inside the loop are untouched)
    from ..resilience import runtime as _resilience

    timeout = _resilience.effective_watchdog_timeout()
    wd_call_id = rank = None
    if timeout is not None and leaves:
        from ..resilience import watchdog as wd

        wd_call_id = _next_call_id()
        rank = comm.global_rank()
        armed = wd.arm_in_graph(f"MPI_Megastep[{label}]", wd_call_id, comm,
                                rank, timeout * n)
        carry = jax.tree.map(lambda v: wd.after_arm(v, armed), carry)

    # one events-tier journal bracket per megastep execution
    from ..telemetry import core as _tcore

    ev_call_id = None
    if _tcore.events_on() and leaves:
        ev_call_id = _next_call_id()
        carry = _bracket_begin(ev_call_id, comm, carry, n, label)

    final = lax.fori_loop(0, n, one, carry)

    # both closers were installed only when the carry has leaves, so the
    # anchor exists exactly when it is needed
    if ev_call_id is not None or wd_call_id is not None:
        dep = jax.tree.leaves(final)[0]
    if ev_call_id is not None:
        _bracket_end(ev_call_id, comm, dep)
    if wd_call_id is not None:
        from ..resilience import watchdog as wd

        wd.disarm_in_graph(f"MPI_Megastep[{label}]", wd_call_id, comm, rank,
                           dep)
    return final


# ---------------------------------------------------------------------------
# the events-tier megastep bracket (mirrors telemetry/bracket.py, with
# megastep meta: one begin/end pair per megastep EXECUTION; the journal
# synthesizes the per-step estimate from latency / unroll)
# ---------------------------------------------------------------------------


def _io_callback(fn, operand):
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    return io_callback(
        fn, jax.ShapeDtypeStruct((), jnp.uint32), operand, ordered=False
    )


def _bracket_begin(call_id: str, comm, carry, unroll: int, label: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import native
    from ..telemetry import journal

    meta = {
        "op": "megastep",
        "label": label,
        "unroll": unroll,
        "comm_uid": str(comm.uid),
        "axes": list(comm.axes),
        "bytes": 0,
        "dtype": "",
    }

    def _begin(r):
        journal.begin(call_id, int(r), meta)
        return np.uint32(r)

    rank = jnp.asarray(comm.global_rank(), jnp.uint32)
    rank = native._tie(rank, jax.tree.leaves(carry)[0])
    dep = _io_callback(_begin, rank)
    return jax.tree.map(lambda v: native._tie(v, dep), carry)


def _bracket_end(call_id: str, comm, dep):
    import jax.numpy as jnp
    import numpy as np

    from .. import native
    from ..telemetry import journal

    def _end(r):
        journal.end(call_id, int(r), {"algo": "loop"})
        return np.uint32(r)

    rank = jnp.asarray(comm.global_rank(), jnp.uint32)
    _io_callback(_end, native._tie(rank, dep))
