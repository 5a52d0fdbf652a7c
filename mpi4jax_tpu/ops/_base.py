"""Shared machinery for the 12 collective ops.

This is the TPU-native replacement for the reference's entire L2-L4 stack
(per-op primitives + abstract evals + per-platform lowerings + the Cython
custom-call bridge, ref: mpi4jax/_src/collective_ops/*.py and
_src/xla_bridge/*.pyx).  Here each op is a thin composition of ``jax.lax``
collectives, so:

- abstract eval, batching, and differentiation rules come from JAX itself
  (and were verified to match the reference's contracts — see tests);
- lowering emits native XLA collective HLO (AllReduce, AllGather, AllToAll,
  CollectivePermute) scheduled over ICI/DCN — no custom calls, no libmpi;
- "eager" execution outside a parallel region auto-wraps the op in a one-op
  ``shard_map`` over the comm's bound mesh — the analog of the reference's
  eager path through ``xla.apply_primitive`` (ref _src/utils.py:34-35), with
  the convention that a global array's leading axis indexes ranks.
"""

from __future__ import annotations

import enum
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel.comm import Comm
from ..parallel.region import (
    RegionContext,
    _region_stack,
    in_parallel_region,
    resolve_comm,
)
from ..utils.debug import get_logging, get_runtime_tracing, op_scope
from ..utils.dtypes import check_dtype
from ..utils import profiling as _profiling
from ..utils.profiling import span as _span, tracing as _tracing

# the trace-time collective verifier and the telemetry layer ride the same
# single dispatch point as resilience and the algorithm selector (imported
# last: analysis and telemetry.core only depend on utils.config, so the
# package import order stays acyclic); the fusion deferral layer hooks the
# same point (flush-on-dispatch preserves program order)
from ..analysis import hook as _analysis
from ..telemetry import core as _telemetry
from . import _fusion


class Op(enum.Enum):
    """Reduction operations (replaces MPI.Op handles, ref _src/utils.py:141-145).

    SUM/MIN/MAX lower to native ``psum``/``pmin``/``pmax`` HLO; the rest
    lower to a log-depth doubling butterfly over ``CollectivePermute``
    (O(log n) depth and per-rank bandwidth — see ``apply_allreduce``).  A
    Python callable ``f(a, b)`` is also accepted anywhere an ``Op`` is —
    the analog of user-defined MPI ops, which the reference could only
    pass through to libmpi.  Callables must be associative (MPI's
    contract); commutativity is not required.
    """

    SUM = "sum"
    PROD = "prod"
    MIN = "min"
    MAX = "max"
    LAND = "land"
    LOR = "lor"
    LXOR = "lxor"
    BAND = "band"
    BOR = "bor"
    BXOR = "bxor"


SUM = Op.SUM
PROD = Op.PROD
MIN = Op.MIN
MAX = Op.MAX
LAND = Op.LAND
LOR = Op.LOR
LXOR = Op.LXOR
BAND = Op.BAND
BOR = Op.BOR
BXOR = Op.BXOR

OpLike = Union[Op, Callable]

# ops with a dedicated XLA collective
_NATIVE_COLLECTIVE = {
    Op.SUM: lax.psum,
    Op.MAX: lax.pmax,
    Op.MIN: lax.pmin,
}

_LOCAL_COMBINE = {
    Op.SUM: jnp.add,
    Op.PROD: jnp.multiply,
    Op.MIN: jnp.minimum,
    Op.MAX: jnp.maximum,
    Op.LAND: jnp.logical_and,
    Op.LOR: jnp.logical_or,
    Op.LXOR: jnp.logical_xor,
    Op.BAND: jnp.bitwise_and,
    Op.BOR: jnp.bitwise_or,
    Op.BXOR: jnp.bitwise_xor,
}


def reduction_name(op) -> str:
    """Static display name of a reduction for the trace-time verifier's
    event stream (``mpi4jax_tpu/analysis``)."""
    if isinstance(op, Op):
        return op.value
    return getattr(op, "__name__", "callable")


def combine_fn(op: OpLike) -> Callable:
    if isinstance(op, Op):
        return _LOCAL_COMBINE[op]
    if callable(op):
        return op
    raise TypeError(
        f"op must be an mpi4jax_tpu.Op or a binary callable, got {op!r}"
    )


def _comm_groups(comm: Comm):
    """Static group member lists (global ranks, group order): a whole-axes
    comm is one group of everyone."""
    if comm.groups is not None:
        return comm.groups
    return (tuple(range(comm.Get_size())),)


def _comm_pos_size(comm: Comm):
    """(group position, group size) of the calling rank — a traced pair on
    a color split (static table lookups, cached on the ``GroupComm`` at
    construction instead of rebuilt per collective trace), (traced, static
    int) otherwise."""
    if comm.groups is None:
        return comm.Get_rank(), comm.Get_size()
    table = comm.group_size_table()
    return comm.Get_rank(), jnp.asarray(table)[comm.global_rank()]


def _permute_axis(comm: Comm):
    """ppermute axis argument: linearized row-major over multi-axis comms
    (the same rank order ``Get_rank`` defines)."""
    axes = comm.axes
    return axes[0] if len(axes) == 1 else axes


def apply_doubling_bcast(xl, comm: Comm, root: int):
    """Log-depth broadcast from each group's ``root`` over ppermute rounds.

    Round ``t`` doubles the covered span: positions (relative to root,
    wrapped) ``[0, 2^t)`` hold the value and send to ``[2^t, 2^{t+1})``.
    ``ceil(log2 k)`` rounds, one message per rank per round — O(log k)
    per-rank bandwidth vs O(world) for an AllGather-based group broadcast.
    where-select (not multiply-by-mask) so non-participant payloads — the
    zeros ppermute delivers to pair-less ranks, or NaN/Inf garbage on
    non-root ranks — never poison the result.
    """
    groups = _comm_groups(comm)
    # ``members[(root + p) % kk]`` below would silently wrap an out-of-range
    # root into a *different* group position and misroute every round; fail
    # loudly here instead.  (bcast validates against ``comm.min_size()``
    # before dispatch, but this helper is callable on its own.)
    kmin = min(len(g) for g in groups)
    if not 0 <= root < kmin:
        from ..analysis.report import mpx_error

        raise mpx_error(
            ValueError, "MPX105",
            f"apply_doubling_bcast: root {root} out of range for the "
            f"smallest group (size {kmin}); root must be a valid group "
            "position in every group",
        )
    kmax = max(len(g) for g in groups)
    if kmax == 1:
        return xl
    pos, k = _comm_pos_size(comm)
    relpos = (pos - root) % k
    acc = xl
    axis = _permute_axis(comm)
    w = 1
    while w < kmax:
        perm = [
            (members[(root + p) % kk], members[(root + p + w) % kk])
            for members in groups
            if (kk := len(members)) > w
            for p in range(min(w, kk - w))
        ]
        recvd = lax.ppermute(acc, axis, perm)
        got = (relpos >= w) & (relpos < 2 * w)
        acc = jnp.where(got, recvd, acc)
        w *= 2
    return acc


def apply_allreduce(x, op: OpLike, comm: Comm):
    """All-reduce ``x`` over ``comm`` with reduction ``op``.

    Whole-axes comm, SUM/MIN/MAX: one native AllReduce HLO.  Every other
    case — PROD/logical/bitwise/callable ops, and ALL ops on a color-split
    comm (``axis_index_groups`` is unavailable under shard_map, see
    ``Comm.Split``) — picks per call between two CollectivePermute
    lowerings (``_algos.resolve_algo``, forced via
    ``MPI4JAX_TPU_COLLECTIVE_ALGO``):

    - the log-depth doubling **butterfly** (``apply_butterfly_allreduce``):
      ``2·ceil(log2 k)`` rounds shipping the FULL payload —
      latency-optimal, O(size·log k) bytes per rank;
    - the **ring** (``_algos.apply_ring_allreduce``): ``2·(k-1)`` rounds
      shipping one CHUNK (``size/k``) — bandwidth-optimal,
      ~``2·(k-1)/k·size`` bytes per rank, the win for large payloads
      (gradient buckets, halo frames).

    On a multi-host comm (derivable host topology spanning ``h > 1``
    hosts with uniform contiguous blocks — ``_hierarchy.hier_plan``),
    ``auto`` instead picks the two-level **hierarchical** lowering above
    the ring crossover: intra-host ring reduce-scatter over ICI →
    inter-host allreduce of the shards over DCN → intra-host allgather
    (``MPI4JAX_TPU_COLLECTIVE_ALGO=hier`` forces it; docs/topology.md).

    All three preserve the deterministic ascending group-rank fold for
    associative non-commutative callables; the ring and hierarchical
    paths additionally require an elementwise callable and a uniform
    static group size (see the ``_algos`` module docstring), so ``auto``
    only routes enum ``Op``s on uniform groups to them.
    """
    from . import _algos, _hierarchy
    from ..utils.config import collective_algo

    axes = comm.axes
    x = as_varying(x, axes)
    algo = collective_algo()
    if (algo == "auto" and comm.groups is None and isinstance(op, Op)
            and op in _NATIVE_COLLECTIVE):
        _analysis.annotate(algo="native")
        _telemetry.annotate(algo="native")
        return _NATIVE_COLLECTIVE[op](x, axes)
    k = _algos.static_group_size(comm)
    chunk_ok = isinstance(op, Op) or algo in ("ring", "hier")
    ring_ok = k is not None and k > 1 and (
        isinstance(op, Op) or algo == "ring"  # auto never chunks callables
    )
    plan = _hierarchy.hier_plan(comm) if k is not None and k > 1 else None
    nbytes = x.size * x.dtype.itemsize
    algo = _algos.resolve_algo(algo, nbytes, k or 1, ring_ok,
                               hier_ok=plan is not None and chunk_ok)
    # the annotation's plan is gated on chunk_ok too: a callable under
    # ``auto`` can never route to the hierarchy, so MPX113 must not
    # advise a choice that does not exist for this call
    _hierarchy.annotate_selection("allreduce", algo, nbytes, k or 1,
                                  plan if chunk_ok else None,
                                  comm, preserve=not isinstance(op, Op),
                                  op=op, dtype=x.dtype.name)
    if algo == "hier":
        return _hierarchy.apply_hier_allreduce(x, op, comm, plan)
    if algo == "ring":
        return _algos.apply_ring_allreduce(x, op, comm, k)
    return apply_butterfly_allreduce(x, op, comm)


def apply_butterfly_allreduce(x, op: OpLike, comm: Comm):
    """Log-depth doubling-butterfly allreduce: ``ceil(log2 k)`` suffix-fold
    rounds + a log-depth broadcast over CollectivePermute, O(log k) depth
    and O(size·log k) per-rank bytes (the round-3/4 lowering was AllGather
    + an O(world)-unrolled fold — O(world) bandwidth AND an O(world)
    serial dependency chain per call, which falls over at pod scale; see
    tests/test_scale.py's 64-device budget).  Works on ANY partition,
    unequal color-split groups included.

    The suffix fold combines in ascending group-rank order with plain
    associativity — no commutativity or identity element required, so
    arbitrary non-commutative callables keep MPI's deterministic
    same-result-everywhere contract (every rank receives group-position
    0's fold via the broadcast).
    """
    x = as_varying(x, comm.axes)
    fn = combine_fn(op)
    groups = _comm_groups(comm)
    kmax = max(len(g) for g in groups)
    if kmax == 1:
        return x
    pos, k = _comm_pos_size(comm)
    axis = _permute_axis(comm)
    # suffix-window doubling: after round t, acc at group position p folds
    # positions [p, min(p + 2^t, k)) in ascending order
    acc = x
    w = 1
    while w < kmax:
        perm = [
            (members[p + w], members[p])
            for members in groups
            for p in range(len(members) - w)
        ]
        recvd = lax.ppermute(acc, axis, perm)
        combine = pos + w < k
        acc = jnp.where(combine, fn(acc, recvd), acc)
        w *= 2
    # group position 0 now holds the full fold; distribute it
    return apply_doubling_bcast(acc, comm, 0)


def linear_rank(comm: Comm):
    return comm.Get_rank()


# ---------------------------------------------------------------------------
# eager wrapping
# ---------------------------------------------------------------------------


def varying(x, *, comm: Optional[Comm] = None):
    """Public helper: re-type a replicated value as rank-varying.

    Collective results (``allreduce``/``bcast``/…) are *replicated-typed* in
    JAX's collective type system — that typing is what gives the reference's
    transpose contract.  Structured control flow (``lax.while_loop`` /
    ``scan`` carries) requires stable types, so a carry that passes through a
    collective must be re-typed with this helper.  See docs/sharp_bits.md.
    """
    comm = resolve_comm(comm)
    # deferred fusion/overlap results materialize here: re-typing is a use
    return jax.tree.map(
        lambda v: as_varying(_fusion.materialize_value(v), comm.axes), x
    )


def as_varying(x, axes: Tuple[str, ...]):
    """Promote a replicated-typed value to varying over ``axes`` (VMA typing).

    JAX's variant/invariant collective typing requires ``psum`` inputs to be
    *varying* over the reduced axes; fresh trace constants (e.g. tangents of
    ``ones``) are replicated.  No-op for axes already varying.
    """
    vma = getattr(jax.typeof(x), "vma", frozenset())
    missing = tuple(a for a in axes if a not in vma)
    if not missing:
        return x
    return lax.pcast(x, missing, to="varying")


def _mpi_opname(opname: str) -> str:
    return "MPI_" + opname.capitalize()


# Call ids pair begin/end hooks and watchdog arm/disarm across one dispatch.
# A module-level monotonic counter (hoisted out of ``_run_body``, which runs
# on EVERY traced collective when tracing or resilience is on) — unique per
# process, which is all the FIFO-aliasing registries require; 8 hex chars to
# match the historical ``secrets.token_hex(4)`` format in log lines.
import itertools

_call_id_counter = itertools.count()


def _next_call_id() -> str:
    return f"{next(_call_id_counter) & 0xFFFFFFFF:08x}"


def _run_body(opname: str, comm: Comm, body, arrays, token, bare=False):
    """Run an op body, bracketed by the instrumentation every op shares:

    - native runtime begin/end hooks when tracing is on (host-side log +
      measured per-op wall-clock latency; see mpi4jax_tpu/native.py);
    - the resilience plan when any resilience feature is on (fault
      injection, numeric guards, collective watchdog; see
      mpi4jax_tpu/resilience/runtime.py) — this is the single dispatch
      point that makes all 12 ops injectable/guardable without per-op code;
    - the telemetry record and, in the ``events`` tier, the journal
      begin/end bracket (mpi4jax_tpu/telemetry/) — counters are pure
      host-side bookkeeping (no graph change); the events bracket threads
      journal callbacks with the same data dependencies as the trace
      hooks.

    Data dependencies pin everything around the collective: inputs are tied
    after ``op_begin``/fault probe/watchdog arm/journal begin, and
    ``op_end``/watchdog disarm/output guards/journal end are tied to the
    first output.  The journal begin sits AFTER the resilience probe so an
    injected straggler delay shows up as late *arrival* — exactly what the
    cross-rank skew column attributes.  With tracing off, every resilience
    feature off, and telemetry off or counters-only (the default is off)
    the body's traced program is untouched — the lowered HLO is
    byte-identical to an uninstrumented build (pinned by
    tests/test_resilience.py and tests/test_telemetry.py).

    ``bare=True`` keeps only the telemetry counter record: the async
    ``*_start``/``*_wait`` ops (ops/_async.py) carry their own
    pair-SPANNING resilience/trace/journal instrumentation (watchdog armed
    at start, disarmed at wait), which per-phase bracketing here would
    double-instrument."""
    from .. import native
    from ..resilience import runtime as _resilience
    from ..telemetry import bracket as _tbracket

    plan = None if bare else _resilience.plan_for(opname)
    tracing = (not bare) and get_runtime_tracing() \
        and native.runtime_tracing_supported()
    rec = _telemetry.open_op(opname, comm, arrays)
    if plan is None and not tracing and rec is None:
        return body(comm, arrays, token)

    try:
        call_id = _next_call_id()
        name = _mpi_opname(opname)
        ebr = None if bare else _tbracket.bracket_for(rec)
        if plan is not None:
            arrays, token = plan.before(name, call_id, comm, arrays, token)
        if ebr is not None:
            arrays, token = ebr.begin(call_id, comm, arrays, token)
        if tracing:
            # computed only when consumed: a dangling axis_index equation
            # would break the counters-mode HLO byte-identity pin
            rank = comm.Get_rank()
            begin = native.op_begin(name, call_id, rank, "")
            arrays = tuple(native._tie(a, begin) for a in arrays)
        out = body(comm, arrays, token)
        results = [r for r in out if r is not None]
        dep = results[0]
        from .token import Token

        if isinstance(dep, Token):
            dep = dep.value
        if tracing:
            native.op_end(name, call_id, rank, dep)
        if ebr is not None:
            ebr.end(call_id, comm, dep)
        if plan is not None:
            plan.after(name, call_id, comm, dep, results)
    except BaseException:
        _telemetry.abort_op(rec)
        raise
    _telemetry.close_op(rec)
    return out


# eager-mode compiled programs, keyed by
# (opname, mesh, comm uid, op-specific statics, observability flags) — the
# analog of jax caching `xla.apply_primitive` per primitive+params (ref
# _src/utils.py:34-35).  jit itself handles shape/dtype/token-structure
# retraces within one entry.  LRU-bounded: callers may produce unbounded
# distinct keys (e.g. many routing patterns), and each entry pins a
# compiled executable plus its mesh.
from collections import OrderedDict

_eager_cache: "OrderedDict" = OrderedDict()
_EAGER_CACHE_MAX = 128

# hit/miss/eviction accounting: _EAGER_CACHE_MAX eviction used to be
# silent, making cache thrash (many distinct routing patterns cycling 128
# entries) invisible.  Mirrored into the telemetry meters when telemetry
# is on; always available via cache_stats().
_eager_cache_stats = {"hits": 0, "misses": 0, "evictions": 0}


# ---------------------------------------------------------------------------
# the dispatch fast path
# ---------------------------------------------------------------------------
#
# The cache-HIT path used to re-parse ~10 environment flags (float,
# choice, and fault-spec grammars) and re-hash the full key tuple on
# every call.  Two memos remove that:
#
# - ``_dynamic_state()``: the flag-derived half of the cache key, parsed
#   once per configuration *stamp* (utils/config.config_stamp: programmatic
#   epoch + raw env fingerprint — one dict read per flag, no parsing);
# - ``_eager_prefix()``: the per-(op, comm, statics) half, interned with a
#   precomputed hash so a hit hashes two cached objects instead of
#   re-hashing mesh + statics.
#
# Toggling any flag (env or ``set_*``) changes the stamp, rebuilds the
# token, and misses the program cache — exactly the retrace-on-toggle
# contract the flat keys gave, at O(1) parse cost per toggle instead of
# per call.


class _Interned:
    """Hash-once wrapper for memoized cache-key halves.  Equality falls
    back to the wrapped key so logically-equal rebuilt wrappers (e.g.
    after ``clear_caches``) still match."""

    __slots__ = ("key", "_hash")

    def __init__(self, key):
        self.key = key
        self._hash = hash(key)

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return self is other or (
            isinstance(other, _Interned) and self.key == other.key
        )


_dyn_cell: list = [None, None, True, True]


def _dynamic_state():
    """``(interned flag token, analysis_off, telemetry_off)`` for the
    current configuration — every dynamically-read flag that shapes a
    trace, parsed only when the config stamp moves."""
    from ..utils import config as _config

    stamp = _config.config_stamp()
    if _dyn_cell[0] != stamp:
        from ..resilience.runtime import cache_token as resilience_token
        from ..utils.config import prefer_notoken
        from . import _async
        from ._algos import algo_cache_token

        tok = (get_runtime_tracing(), get_logging(), prefer_notoken(),
               resilience_token(), algo_cache_token(),
               _analysis.analysis_cache_token(),
               _telemetry.telemetry_cache_token(),
               _fusion.fusion_cache_token(),
               _async.overlap_cache_token())
        # publish the stamp LAST: a concurrent reader must never see the
        # new stamp paired with the previous token/gates
        _dyn_cell[1] = _Interned(tok)
        _dyn_cell[2] = _analysis.effective_mode() == "off"
        _dyn_cell[3] = _telemetry.effective_mode() == "off"
        _dyn_cell[0] = stamp
    return _dyn_cell[1], _dyn_cell[2], _dyn_cell[3]


def dynamic_cache_token() -> "_Interned":
    """The flag half of every compiled-program cache key (shared with the
    spmd program cache in parallel/region.py)."""
    return _dynamic_state()[0]


# LRU-bounded like the program cache it serves: callers may produce
# unbounded distinct static keys (many routing patterns), and each memo
# entry pins a mesh reference.  Sized above _EAGER_CACHE_MAX so every
# live program's prefix stays memoized.
_eager_prefix_memo: "OrderedDict" = OrderedDict()
_PREFIX_MEMO_MAX = 256


def _eager_prefix(opname: str, comm: Comm, static_key):
    """Interned ``(opname, mesh, comm uid, statics)`` key half + the
    comm's PartitionSpec, built once per (op, comm, statics).  The memo
    entry pins the mesh it was built against: re-binding a comm to a new
    mesh rebuilds (identity check, no hashing)."""
    k = (opname, comm.uid, static_key)
    ent = _eager_prefix_memo.get(k)
    if ent is not None and ent[0] is comm.mesh:
        _eager_prefix_memo.move_to_end(k)
        return ent[1], ent[2]
    axes_spec = P(comm.axes if len(comm.axes) > 1 else comm.axes[0])
    prefix = _Interned((opname, comm.mesh, comm.uid, static_key))
    _eager_prefix_memo[k] = (comm.mesh, prefix, axes_spec)
    if len(_eager_prefix_memo) > _PREFIX_MEMO_MAX:
        _eager_prefix_memo.popitem(last=False)
    return prefix, axes_spec


def cache_stats() -> dict:
    """Compiled-program cache accounting, all tiers in one call:

    - the eager one-op cache: ``{"hits", "misses", "evictions",
      "size"}`` — ``misses`` counts cacheable dispatches that compiled
      a new program (uncacheable dispatches — e.g. a Status out-param —
      count neither way); a high eviction rate means the working set
      exceeds the LRU bound and eager calls are recompiling in cycles;
    - ``"aot"``: the pinning layer (``mpx.compile`` — pins, pinned
      calls, MPX129 stale refusals, disk loads vs fresh compiles);
    - ``"disk_cache"``: the persistent tier
      (``MPI4JAX_TPU_COMPILE_CACHE_DIR`` — hits/misses/writes/
      evictions/bytes plus the on-disk entry count), the before/after
      evidence for cold-start behavior (docs/aot.md);
    - ``"builds"``: programs built in this process — a pin, the first
      call of a region's or an eager op's new program — ``by_kind``
      (``pin``, ``region``, ``eager``) and ``by_origin``: ``compiled``
      (XLA compiled here), ``jax_cache`` (fetched from jax's persistent
      cache), ``package_cache`` (from the disk tier above), ``memory``
      (jax had the executable already).  The records behind the counts,
      with the seconds of each stage: ``utils.profiling.builds()``.

    Reset by ``clear_caches()`` (on-disk artifacts are untouched).
    """
    out = dict(_eager_cache_stats, size=len(_eager_cache))
    from ..aot import stats as _aot_stats

    out.update(_aot_stats())
    out["builds"] = _profiling.build_counts()
    return out


def _bump_cache_stat(name: str, telemetry_off: bool = False) -> None:
    _eager_cache_stats[name] += 1
    if not telemetry_off:
        _telemetry.meter(f"eager_cache.{name}")


def clear_caches() -> None:
    """Drain the eager one-op compiled-program cache (resetting its
    hit/miss/eviction stats, the AOT counters and the kept build records
    with their counts) and the memoized ``mpx.analyze`` reports.

    Each eager entry pins a compiled executable plus its mesh; call this
    after retiring a mesh, or when flipping a trace-shaping environment
    variable mid-process by hand (the knobs this library reads —
    ``MPI4JAX_TPU_COLLECTIVE_ALGO``, the resilience flags,
    ``MPI4JAX_TPU_ANALYZE``, ``MPI4JAX_TPU_TELEMETRY``, tracing/logging —
    are already folded into the cache key, so toggling them retraces
    without an explicit clear).  ``spmd``-decorated functions hold their
    own per-function program caches keyed the same way; they are dropped
    with the function object.
    """
    _eager_cache.clear()
    _eager_prefix_memo.clear()
    _dyn_cell[0] = None
    for k in _eager_cache_stats:
        _eager_cache_stats[k] = 0
    _analysis.clear_analysis_caches()
    from ..aot import reset_stats as _aot_reset

    _aot_reset()
    _profiling.clear_builds()


# lanes of a TPU vector register: the minor extent of every tiled layout
_LANES = 128


def gather_view(xl) -> str:
    """Which view of a rank-local block :func:`all_gather_blocks` hands
    to the AllGather HLO: ``"lanes"`` for a 1-D block whose length is a
    multiple of 128, ``"block"`` (the block as it is) for every other."""
    lanes = xl.ndim == 1 and xl.size > 0 and xl.size % _LANES == 0
    return "lanes" if lanes else "block"


def all_gather_blocks(comm: Comm, xl):
    """One AllGather HLO over the comm's full mesh axes: output
    ``(size, *xl.shape)``, ranks in row-major order (axis tuples are
    supported natively by the AllGather lowering).

    The result of a 1-D block has the rank axis second-minor, which
    XLA:TPU tiles onto the sublanes (``(4, N)`` as ``T(4,128)``): no
    all-gather can write that, so the gather lands flat and a loop of
    partial-tile ``dynamic-update-slice``s rebuilds the result block by
    block.  Such a block is gathered as ``(N // 128, 128)`` instead —
    the same bytes, the rank axis major and untiled, the gather
    contiguous — and the closing reshape is left to XLA, which moves it
    through an elementwise or reducing consumer (one that needs the
    ``(size, N)`` array itself pays one relayout, as the plain line's
    did).  Pure movement either way: values, JVP / transpose and ``vmap``
    are ``lax.all_gather``'s.
    The view taken is recorded on the open analysis event and telemetry
    record (``view``; meter ``view.<op>.<view>``)."""
    view = gather_view(xl)
    _analysis.annotate(view=view)
    _telemetry.annotate(view=view)
    if view == "lanes":
        full = lax.all_gather(xl.reshape(-1, _LANES), comm.axes, axis=0,
                              tiled=False)
        return full.reshape(-1, *xl.shape)
    return lax.all_gather(xl, comm.axes, axis=0, tiled=False)


def group_select_gather(comm: Comm, xl):
    """AllGather over the comm's FULL mesh axes (:func:`all_gather_blocks`:
    a 1-D block of a multiple of 128 elements through its lane-shaped
    view), then select this rank's group members in group order: output
    ``(group_size, *xl.shape)``.

    The shared first step of every gather-family group lowering on a
    color-split comm (uniform group sizes only — ``my_group_members``
    raises the clear error otherwise)."""
    full = all_gather_blocks(comm, xl)
    return jnp.take(full, comm.my_group_members(), axis=0)


def check_global_shape(opname: str, a, size: int) -> None:
    """Validate the eager global-array convention: leading axis = ranks."""
    if getattr(a, "ndim", 0) == 0 or a.shape[0] != size:
        raise ValueError(
            f"{opname} (eager): expected a global array with leading rank "
            f"axis of size {size} (global[r] = rank r's value); got shape "
            f"{getattr(a, 'shape', None)}. Inside a parallel region, pass "
            "rank-local arrays instead."
        )


def dispatch(opname: str, comm: Optional[Comm], body, arrays, token,
             static_key: Optional[tuple] = None,
             ana: Optional[dict] = None, bare: bool = False):
    """Run op ``body`` either inline (inside a parallel region) or eagerly.

    ``body(comm, arrays, token) -> (outputs..., token)`` operates on
    rank-local values.  In eager mode (outside any region), ``arrays`` are
    global arrays whose leading axis indexes ranks — ``global[r]`` is rank
    ``r``'s local value — and the op is wrapped in a one-op jitted
    ``shard_map`` over the comm's mesh: the analog of the reference's eager
    path through ``xla.apply_primitive`` (ref _src/utils.py:34-35).  Outputs
    use the same convention, so eager results have shape
    ``(size, *local_out_shape)``.

    ``ana`` is the op's static structure as the trace-time verifier sees
    it (root, tag, reduction, ... — mpi4jax_tpu/analysis/): every op that
    flows through this dispatch point is recorded when ``mpx.analyze`` or
    ``MPI4JAX_TPU_ANALYZE`` is active, and recording is pure host-side
    bookkeeping — the traced program (and thus the HLO) is untouched.
    """
    comm = resolve_comm(comm)
    # a dispatch that reaches this point does not join the fusion queue:
    # drain it first so the fused collectives keep their program position,
    # and force any deferred results used as inputs
    if _region_stack:
        _fusion.flush_pending(_region_stack[-1])
    arrays = tuple(_fusion.materialize_value(a) for a in arrays)
    for a in arrays:
        check_dtype(a, opname)
    fused_ana = _fusion.take_pending_ana()
    if fused_ana is not None:
        ana = {**(ana or {}), **fused_ana}
    if in_parallel_region(comm):
        # a pending tokenless barrier (see RegionContext.pending_sync) is
        # folded into this op's token so the op is ordered after it
        ctx = _region_stack[-1] if _region_stack else None
        if ctx is not None and ctx.pending_sync is not None:
            sync = ctx.pending_sync
            ctx.pending_sync = None
            from .token import Token, tie

            token = sync if token is None else Token(tie(sync, token.value))
            # tie the op inputs directly too: consume() may be disabled by
            # MPI4JAX_TPU_PREFER_NOTOKEN, but barrier ordering must hold
            arrays = tuple(tie(sync, a) for a in arrays)
        # promote replicated trace-constants to rank-varying once, centrally,
        # so every op accepts them (collectives are variant->invariant typed)
        arrays = tuple(as_varying(a, comm.axes) for a in arrays)
        with op_scope(opname):
            evt = _analysis.begin_event(opname, comm, arrays, token, ana, ctx)
            try:
                out = _run_body(opname, comm, body, arrays, token, bare=bare)
            except BaseException:
                if evt is not None:
                    _analysis.abort_event(evt)
                raise
            if evt is not None:
                _analysis.end_event(evt, out)
            return out

    if _tracing() and not any(isinstance(a, jax.core.Tracer)
                              for a in arrays):
        # a profiler session runs: the eager call under a span, whose
        # self time is the checks and the cache probe.  Under an outer
        # trace the op runs once, at trace time, and ``op_scope`` names it
        with _span("mpx.eager." + opname):
            return _dispatch_eager(opname, comm, body, arrays, token,
                                   static_key, ana, bare)
    return _dispatch_eager(opname, comm, body, arrays, token, static_key,
                           ana, bare)


def _launch(sm, arrays, token):
    """One call of an eager op's jitted program, under ``mpx.launch``."""
    with _span("mpx.launch"):
        return sm(tuple(arrays), token)


def _dispatch_eager(opname: str, comm: Comm, body, arrays, token,
                    static_key, ana, bare):
    """The eager half of :func:`dispatch`: a one-op jitted ``shard_map``
    over the comm's mesh, from the eager cache where it can be."""
    if comm.mesh is None:
        raise RuntimeError(
            f"{opname}: called outside a parallel region with an unbound "
            "communicator. Either call inside mpi4jax_tpu.spmd / "
            "jax.shard_map, or bind the comm to a mesh (comm.bind(mesh))."
        )

    size = comm.world_size()
    for a in arrays:
        check_global_shape(opname, a, size)

    # ``static_key`` lists every closure value of ``body`` that shapes the
    # trace; ``None`` marks the call uncacheable (e.g. a Status out-param
    # that must be filled at trace time)
    cache_key = None
    dyn, analysis_off, telemetry_off = _dynamic_state()
    if static_key is not None and analysis_off and not _analysis.recording():
        # an active mpx.analyze recorder — or the ambient warn/error mode —
        # bypasses the cache entirely: a cache hit would skip tracing,
        # tracing is when events are recorded, and queue-state-dependent
        # findings (MPX110) can differ between calls that share a program.
        # Both key halves are memoized with precomputed hashes (see "the
        # dispatch fast path" above): a hit re-parses no flags and
        # re-hashes no mesh/statics.
        prefix, axes_spec = _eager_prefix(opname, comm, static_key)
        cache_key = (prefix, dyn)
        cached = _eager_cache.get(cache_key)
        if cached is not None:
            _eager_cache.move_to_end(cache_key)
            _bump_cache_stat("hits", telemetry_off)
            sm_hit, tele_cell = cached
            if telemetry_off:
                results, tok_out = _launch(sm_hit, arrays, token)
                return (*results, tok_out)
            # dispatch runs per call even on a hit, so the eager tier
            # counts per call — from the entry's stash for THIS call's
            # signature (jit retraces per signature; each retrace lands
            # its records under its own signature inside capture_eager)
            sig = _telemetry.call_signature(arrays)
            with _telemetry.capture_eager(tele_cell, sig):
                results, tok_out = _launch(sm_hit, arrays, token)
            _telemetry.count_eager_call(tele_cell, sig)
            return (*results, tok_out)
        _bump_cache_stat("misses", telemetry_off)
        if not telemetry_off:
            _telemetry.meter(f"recompiles.eager.{opname}")
    else:
        axes_spec = P(comm.axes if len(comm.axes) > 1 else comm.axes[0])

    def wrapped(arrs, tok):
        ctx = RegionContext(comm)
        _analysis.arm_context(ctx)
        _region_stack.append(ctx)
        try:
            with op_scope(opname):
                # shard_map hands us (1, *local); body wants (*local,)
                locals_ = tuple(a[0] for a in arrs)
                evt = _analysis.begin_event(opname, comm, locals_, tok, ana,
                                            ctx, eager=True)
                try:
                    out = _run_body(opname, comm, body, locals_, tok,
                                    bare=bare)
                except BaseException:
                    if evt is not None:
                        _analysis.abort_event(evt)
                    raise
                if evt is not None:
                    _analysis.end_event(evt, out)
            ctx.check_drained()
            _analysis.finish_context(ctx, f"eager {opname}")
        finally:
            _region_stack.pop()
        *results, tok_out = out
        if tok_out is not None:
            # make the global token replicated (and dependent on every
            # rank's completion) so it round-trips through out_specs=P()
            from .token import Token

            tok_out = Token(lax.psum(as_varying(tok_out.value, comm.axes), comm.axes))
        return tuple(r[None] for r in results), tok_out

    sm = jax.jit(jax.shard_map(
        wrapped,
        mesh=comm.mesh,
        in_specs=(tuple(axes_spec for _ in arrays), P()),
        out_specs=(axes_spec, P()),
    ))
    # insert into the cache only after the first call succeeds — a
    # trace/compile failure must not leave a broken entry to be replayed
    tele_cell = _telemetry.EagerCell()
    # the new program's first call, under a kept span: jax's trace, lower
    # and fetch or compile and that call's launch (utils/profiling.py)
    with _span("mpx.build", keep=True, program=opname, kind="eager"):
        if telemetry_off:
            results, tok_out = _launch(sm, arrays, token)
        else:
            sig = _telemetry.call_signature(arrays)
            with _telemetry.capture_eager(tele_cell, sig):
                results, tok_out = _launch(sm, arrays, token)
            _telemetry.count_eager_call(tele_cell, sig)
    if cache_key is not None:
        _eager_cache[cache_key] = (sm, tele_cell)
        if len(_eager_cache) > _EAGER_CACHE_MAX:
            _eager_cache.popitem(last=False)
            _bump_cache_stat("evictions")
    return (*results, tok_out)
