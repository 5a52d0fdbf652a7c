"""Parallel regions: the SPMD execution surface.

The reference runs one OS process per rank (``mpirun``), and every op executes
against the process-global MPI state.  The TPU-native model traces ONE program
for all ranks with ``jax.shard_map`` over a device mesh; a *parallel region*
is that traced body.  This module provides:

- ``spmd(...)`` — decorator turning a per-rank function into a jitted
  ``shard_map`` over a comm's mesh (global arrays carry a leading rank axis);
- the trace-time region context that (a) supplies the default communicator to
  ops called with ``comm=None`` and (b) holds the send/recv matching queues
  (see ops/send.py);
- ``run(fn, *args)`` — one-shot form of ``spmd``.

Because the region is a single program, every rank observes the same schedule
of collectives — the deadlock class the reference's token machinery exists to
prevent (ref docs/sharp-bits.rst, tests/collective_ops/test_send_and_recv.py:91-110
"this deadlocks without proper token management") cannot occur by construction.
Tokens are still honored: they pin the *relative order* of collectives through
``optimization_barrier`` data dependencies (see ops/token.py).
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from ..utils.profiling import span as _span, tracing as _tracing
from .comm import Comm
from .mesh import DEFAULT_AXIS, get_default_mesh


class RegionContext:
    """Trace-time state for one parallel region."""

    def __init__(self, comm: Comm):
        self.comm = comm
        # (comm_uid, tag) -> deque of pending _PendingSend (see ops/send.py)
        self.send_queues: Dict[Tuple[int, int], deque] = {}
        # implicit ordering handle for the tokenless API (the ordered-effects
        # analog, ref notoken abstract evals declare {ordered_effect}): a
        # tokenless barrier deposits its token here; the next op (or the
        # region's outputs) consumes it, so the synchronizing collective is
        # never dead-code-eliminated and subsequent ops are ordered after it.
        self.pending_sync = None
        # env-mode collective verifier sink, armed by analysis.hook when
        # MPI4JAX_TPU_ANALYZE != off (None otherwise — zero overhead)
        self.analysis_recorder = None
        # pending adjacent-collective fusion queue (ops/_fusion.py), only
        # ever non-None while MPI4JAX_TPU_FUSION is auto/force; drained by
        # any non-joining dispatch and at region exit
        self.fusion_queue = None

    def queue(self, comm_uid: int, tag: int) -> deque:
        return self.send_queues.setdefault((comm_uid, tag), deque())

    def check_drained(self) -> None:
        leftover = {k: len(q) for k, q in self.send_queues.items() if q}
        if leftover:
            from ..analysis.report import mpx_error

            raise mpx_error(
                RuntimeError, "MPX101",
                f"parallel region ended with unmatched send(s): "
                f"{{(comm_uid, tag): count}} = {leftover}. Every send must be "
                "matched by a recv on the same comm and tag within the same "
                "region (matching is FIFO per (comm, tag); the SPMD analog "
                "of the reference's matched-pair requirement).",
            )


_region_stack: List[RegionContext] = []

# Fallback context for ops used inside a *user's own* shard_map (no spmd
# wrapper). Queues here are keyed the same way; staleness across traces is
# caught by JAX's leaked-tracer errors.
_global_ctx = RegionContext(comm=None)  # type: ignore[arg-type]

_default_comm: Optional[Comm] = None


def current_context() -> RegionContext:
    return _region_stack[-1] if _region_stack else _global_ctx


def get_default_comm() -> Comm:
    """The world communicator (analog of ref ``get_default_comm``,
    mpi4jax/_src/comm.py:4-11): inside a region, the region's comm; outside,
    a cached comm over the default world mesh."""
    ctx = current_context()
    if ctx.comm is not None:
        return ctx.comm
    global _default_comm
    if _default_comm is None:
        _default_comm = Comm(DEFAULT_AXIS, mesh=get_default_mesh())
    return _default_comm


def resolve_comm(comm: Optional[Comm]) -> Comm:
    return comm if comm is not None else get_default_comm()


def region_axes_spec(c: Comm):
    """The default PartitionSpec of a comm's region: global arrays carry
    a leading axis sharded over the comm's mesh axes."""
    return P(c.axes if len(c.axes) > 1 else c.axes[0])


def shard_global(tree, comm: Optional[Comm] = None):
    """Commit a pytree of global ``(size, *local_shape)`` arrays (host or
    device) to ``comm``'s mesh in the layout ``spmd``/``mpx.compile``
    programs take by default: ``global[r]`` on rank ``r``'s device.

    Arrays made with ``jnp.asarray`` live whole on the first device and
    are re-sharded from there by every call they are passed to; placing
    long-lived state (model state, weights, KV pools) once avoids that
    per-call copy off one chip."""
    c = resolve_comm(comm)
    sharding = jax.sharding.NamedSharding(c.mesh, region_axes_spec(c))
    return jax.device_put(tree, sharding)


def make_region_body(f, c: Comm, statics, static_vals, kw_names, n_dyn,
                     squeeze_in: bool, squeeze_out: bool, unroll: int = 1,
                     name: Optional[str] = None):
    """Build the per-rank region body ``spmd`` traces: argument
    re-interleaving, the region context push/pop, fusion drain, pending
    tokenless-barrier tie-in, and the trace-time verifier hooks.

    Shared by the ``spmd`` program cache (below) and the AOT pinning
    layer (``mpi4jax_tpu/aot/pinning.py``), so a pinned program traces
    the IDENTICAL body a cached ``spmd`` program would — same HLO, same
    jaxpr fingerprint, same persistent-cache artifact.

    ``unroll > 1`` rewrites the body into a device-resident megastep
    loop (parallel/megastep.py): the dynamic positional arguments become
    the ``lax.fori_loop`` carry and ``f`` runs once per iteration — one
    host dispatch executes ``unroll`` steps.  ``f`` must map its dynamic
    arguments to a like-structured pytree (the carry contract;
    docs/aot.md "Megastep execution").  ``unroll == 1`` keeps the exact
    single-step body — trace and HLO byte-identical to before the
    megastep layer existed.

    The body is called ``name`` (default: ``f``'s own name), so the
    jitted program is the module ``jit_<name>`` in HLO and in a profiler
    trace, not ``jit_body`` like every other.
    """

    def body(*a):
        from ..analysis import hook as _analysis

        ctx = RegionContext(c)
        _analysis.arm_context(ctx)
        _region_stack.append(ctx)
        try:
            if squeeze_in:
                a = jax.tree.map(lambda v: v[0], a)
            pos, kwvals = a[:n_dyn], a[n_dyn:]
            kw = dict(zip(kw_names, kwvals))
            # re-interleave the closed-over static args
            full = list(pos)
            for i, v in zip(statics, static_vals):
                full.insert(i, v)
            if unroll > 1:
                from .megastep import megastep_loop

                label = getattr(f, "__name__", "fn")

                def one(_i, carry):
                    it_full = list(carry)
                    for si, v in zip(statics, static_vals):
                        it_full.insert(si, v)
                    r = f(*it_full)
                    if n_dyn == 1:
                        return (r,)
                    if (not isinstance(r, (tuple, list))
                            or len(r) != n_dyn):
                        raise ValueError(
                            f"megastep carry contract violated in "
                            f"{label!r}: with unroll={unroll} and "
                            f"{n_dyn} dynamic arguments the step must "
                            f"return a matching {n_dyn}-tuple of new "
                            "states, got "
                            f"{type(r).__name__} (docs/aot.md "
                            "'Megastep execution')"
                        )
                    return tuple(r)

                final = megastep_loop(one, tuple(pos), unroll, c,
                                      label=label)
                out = final[0] if n_dyn == 1 else final
            else:
                out = f(*full, **kw)
            # drain the fusion queue and force any deferred
            # results: region outputs must be real arrays
            # before they cross the shard_map boundary
            from ..ops import _fusion

            _fusion.flush_pending(ctx)
            out = _fusion.materialize_tree(out)
            if ctx.pending_sync is not None:
                # a trailing tokenless barrier: tie it into the
                # region outputs so it is not dead-code-eliminated
                from ..ops.token import tie

                sync = ctx.pending_sync
                ctx.pending_sync = None
                out = jax.tree.map(lambda v: tie(sync, v), out)
            if squeeze_out:
                out = jax.tree.map(lambda v: v[None], out)
            ctx.check_drained()
            _analysis.finish_context(
                ctx, f"spmd region {getattr(f, '__name__', f)!s}"
            )
            return out
        finally:
            _region_stack.pop()

    body.__name__ = body.__qualname__ = name or getattr(f, "__name__", "fn")
    return body


def _launched(program):
    """``program`` with its calls under the span ``mpx.launch``: jax's
    compiled call until it returns (its first call traces and compiles)."""

    def launch(*args):
        with _span("mpx.launch"):
            return program(*args)

    return launch


def _first_call(program, name, launch=False):
    """The first call of a region's new program, under the kept span
    ``mpx.build``: it holds jax's trace, lower and fetch or compile *and*
    that call's launch (utils/profiling.py says what of each).  The
    program cache keeps the program itself: a hit pays nothing for this.
    ``launch`` puts the call under ``mpx.launch`` here, in the place of
    ``_launched``'s frame and not above it: a Python frame more over a
    trace makes the trace slower (PERF.md section 6, PR 37)."""

    def build(*args):
        with _span("mpx.build", keep=True, program=name, kind="region"):
            if not launch:
                return program(*args)
            with _span("mpx.launch"):
                return program(*args)

    return build


def spmd(
    fn=None,
    *,
    comm: Optional[Comm] = None,
    in_specs: Any = None,
    out_specs: Any = None,
    jit: bool = True,
    static_argnums=(),
    unroll: Optional[int] = None,
):
    """Turn a per-rank function into an SPMD program over ``comm``'s mesh.

    The wrapped function sees rank-local arrays; global inputs/outputs carry a
    leading rank axis by default (``in_specs=P(axis)``), matching the
    convention that rank ``r``'s local value is ``global[r]``.  Custom
    ``in_specs``/``out_specs`` follow ``jax.shard_map``.

    Inside the body, ops called with ``comm=None`` use this region's comm, and
    ``send``/``recv`` matching is scoped to the region.

    ``unroll=N`` (N > 1) compiles a **megastep**: the body becomes a
    device-resident ``lax.fori_loop`` over N iterations with the dynamic
    positional arguments as the carry, so one host call runs N steps
    (docs/aot.md "Megastep execution").  The step must map its dynamic
    arguments to a like-structured pytree, and keyword arguments are not
    accepted in megastep mode.  ``None`` (default) resolves
    ``MPI4JAX_TPU_UNROLL_DEFAULT`` (1 = off — body and HLO unchanged).
    """

    def wrap(f):
        # One compiled program per (mesh, comm) — built lazily on first call
        # and reused, so host loops over an spmd function hit the jit cache
        # instead of re-tracing every iteration.
        program_cache = {}

        # normalize like jax.jit: accept a bare int, sort ascending (the
        # re-interleaving insert below requires ascending order); negative
        # indices are resolved against the actual call arity per call
        if static_argnums is None:
            statics_raw = ()
        elif isinstance(static_argnums, int):
            statics_raw = (static_argnums,)
        else:
            statics_raw = tuple(static_argnums)

        name = getattr(f, "__name__", "fn")

        def program_for(args, kwargs):
            """The compiled program of this call and its arguments: all
            the library does on a call before jax takes over."""
            c = resolve_comm(comm)
            if c.mesh is None:
                raise RuntimeError(
                    "spmd requires a comm bound to a mesh (comm.bind(mesh)) "
                    "or an available default mesh"
                )
            # static args are closed over (they never enter shard_map, whose
            # in_specs only describe arrays); the cache is keyed on their
            # values, mirroring jit's static_argnums semantics
            statics = tuple(sorted({
                i if i >= 0 else i + len(args) for i in statics_raw
            }))
            for i in statics:
                if not 0 <= i < len(args):
                    # like jax.jit: a static argument supplied by keyword is
                    # a dedicated error, not a confusing out-of-range one
                    import inspect

                    try:
                        names = list(inspect.signature(f).parameters)
                    except (TypeError, ValueError):
                        names = []
                    if 0 <= i < len(names) and names[i] in kwargs:
                        raise TypeError(
                            f"spmd static argument {names[i]!r} "
                            f"(static_argnums position {i}) was passed as a "
                            "keyword; pass it positionally"
                        )
                    raise ValueError(
                        f"static_argnums entry {i} out of range for "
                        f"{len(args)} positional arguments"
                    )
            static_vals = tuple(args[i] for i in statics)
            try:
                hash(static_vals)
            except TypeError as e:
                raise TypeError(
                    f"spmd static argument values must be hashable (like "
                    f"jax.jit static_argnums); got {static_vals!r}"
                ) from e
            dyn_args = tuple(a for i, a in enumerate(args) if i not in statics)
            # shard_map is positional-only: keyword arrays are appended as
            # trailing positionals (sorted by name) and rebound in the body
            kw_names = tuple(sorted(kwargs))
            if kw_names and in_specs is not None:
                raise TypeError(
                    "spmd with custom in_specs takes positional arguments "
                    f"only (got keyword argument(s) {kw_names}); in_specs "
                    "entries cannot be matched to keywords"
                )
            n_dyn = len(dyn_args)
            from .megastep import validate_unroll

            if unroll is not None:
                n_unroll = validate_unroll(unroll)
            else:
                from ..utils.config import unroll_default

                n_unroll = unroll_default()
            if n_unroll > 1 and (kw_names or n_dyn == 0):
                # only an EXPLICIT unroll= is a contract error here: a
                # fleet-wide MPI4JAX_TPU_UNROLL_DEFAULT must not break
                # unrelated programs that cannot carry a megastep loop —
                # those degrade to the single-step body
                if unroll is None:
                    n_unroll = 1
                elif kw_names:
                    raise TypeError(
                        "spmd(unroll=N) takes positional arguments only "
                        f"(got keyword argument(s) {kw_names}): the "
                        "megastep carry is the dynamic positional tuple"
                    )
                else:
                    raise ValueError(
                        "spmd(unroll=N) needs at least one dynamic "
                        "argument to carry through the device-resident "
                        "loop"
                    )
            # every dynamically-read flag that shapes the trace must be in
            # the key (mirrors _eager_cache in ops/_base.py), or toggling
            # tracing/logging/prefer_notoken after the first call would
            # silently keep serving the stale compiled program.  The flag
            # half comes pre-parsed and hash-cached from the dispatch fast
            # path (ops/_base.dynamic_cache_token): a warm call re-parses
            # no environment flags.
            from ..ops._base import _dynamic_state
            from ..telemetry import core as _telemetry

            dyn_token, analysis_off, _ = _dynamic_state()
            key = (c.mesh, c.uid, statics, static_vals, kw_names, n_dyn,
                   n_unroll, dyn_token)
            sm = program_cache.get(key)
            if not analysis_off:
                # ambient cross-rank pass (analysis/crossrank.py): runs
                # per CALL, not per program-cache miss — jit retraces
                # internally on new argument shapes without missing this
                # cache, and a shape-dependent rank-divergent path must
                # still be verified before it compiles.  Memoized by
                # avals + config inside, so warm calls cost one memo
                # lookup; with the verifier off (the default) this
                # branch is a single memoized-flag test.
                from ..analysis import crossrank as _crossrank

                _crossrank.verify_region_crossrank(
                    f, comm=comm, in_specs=in_specs, out_specs=out_specs,
                    static_argnums=statics_raw, c=c, args=args,
                    kwargs=kwargs)
            if sm is not None:
                _telemetry.meter("spmd_cache.hits")
            else:
                # per-function recompile meter: a retrace storm (e.g. a
                # flag flapping per step, or unhashed static args) shows
                # up as a climbing recompiles.spmd.<name> count
                _telemetry.meter("spmd_cache.misses")
                _telemetry.meter(f"recompiles.spmd.{name}")
            if sm is None:
                axes_spec = region_axes_spec(c)
                ispecs = in_specs if in_specs is not None else axes_spec
                ospecs = out_specs if out_specs is not None else axes_spec
                # Default-spec convention: a global array is
                # (size, *local_shape), global[r] being rank r's value — so
                # the body sees true local shapes, we squeeze the sharded
                # leading axis on the way in and restore it on the way out.
                # Custom specs disable this.
                body = make_region_body(
                    f, c, statics, static_vals, kw_names, n_dyn,
                    squeeze_in=in_specs is None,
                    squeeze_out=out_specs is None,
                    unroll=n_unroll,
                )
                sm = jax.shard_map(
                    body, mesh=c.mesh, in_specs=ispecs, out_specs=ospecs
                )
                if jit:
                    sm = jax.jit(sm)
                    # the persistent tier (docs/aot.md): with
                    # MPI4JAX_TPU_COMPILE_CACHE_DIR set, a program-cache
                    # MISS consults the on-disk compiled-program cache
                    # before XLA re-lowers — a multi-host cold start
                    # deserializes identical SPMD programs instead of
                    # compiling them on every rank.  Unset (default),
                    # the jitted program is used as-is: keys and HLO
                    # byte-identical to a build without the AOT layer.
                    from ..utils.config import compile_cache_dir

                    if compile_cache_dir():
                        from ..aot import pinning as _pinning

                        sm = _pinning.through_disk_cache(sm, c, label=name)
                        first = _first_call(sm, name)
                    else:
                        first = _first_call(sm, name, launch=True)
                        sm = _launched(sm)
                else:
                    first = sm
                program_cache[key] = sm
                sm = first
            return sm, (*dyn_args, *(kwargs[k] for k in kw_names))

        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            if _tracing():
                # a profiler session runs: the same call under a span,
                # whose self time is the flag stamp and the cache probe
                with _span("mpx.region_call", program=name):
                    sm, call_args = program_for(args, kwargs)
                    return sm(*call_args)
            sm, call_args = program_for(args, kwargs)
            return sm(*call_args)

        # breadcrumbs for mpx.analyze: it rebuilds an UN-jitted twin from
        # the underlying per-rank function, because jit's trace cache
        # would otherwise serve a cached jaxpr and record no events
        wrapped._mpx_spmd = True
        wrapped._mpx_fn = f
        wrapped._mpx_spmd_kwargs = dict(
            comm=comm, in_specs=in_specs, out_specs=out_specs,
            static_argnums=statics_raw, unroll=unroll,
        )
        return wrapped

    if fn is not None:
        return wrap(fn)
    return wrap


def run(f, *args, comm: Optional[Comm] = None, **spmd_kwargs):
    """One-shot ``spmd``: ``run(f, x)`` == ``spmd(f, ...)(x)``."""
    return spmd(comm=comm, **spmd_kwargs)(f)(*args)


def in_parallel_region(comm: Comm) -> bool:
    """True if the comm's axes are bound in the current trace (i.e. we are
    inside a shard_map body over those axes)."""
    from ..utils.jax_compat import axis_bound

    return all(axis_bound(a) for a in comm.axes)
