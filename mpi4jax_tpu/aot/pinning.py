"""AOT program pinning: ``mpx.compile`` and the persistent-tier glue.

Even after the flag-parse fast path (PR 5), a cache-HIT ``spmd`` call still
normalizes statics, rebuilds the key, probes the program cache, and
meters — per call, forever.  The AOT layer ends that: once the program
is fixed, the hot loop should execute a **pinned artifact** (JAX's
``lower().compile()`` AOT path; the CUDA-Graphs capture-and-replay
lesson) —

- :func:`compile` ``(fn, *abstract_args, comm=..., donate_argnums=...)``
  returns a :class:`PinnedProgram`: the fully lowered+compiled
  executable.  Its call path does no env-flag parsing, no cache-key
  hashing, and no program-cache lookups — the config stamp, every
  algo/fusion/analysis/resilience token, and the elastic epoch were
  captured ONCE at compile time (``invalidation.WorldStamp``), and a
  moved world raises :class:`~.invalidation.StaleProgramError` (MPX129)
  instead of silently serving old-world code;
- the **persistent tier** (``MPI4JAX_TPU_COMPILE_CACHE_DIR``,
  diskcache.py): pinned programs — and ``mpx.spmd`` program-cache
  misses, via :func:`through_disk_cache` — are keyed by (jaxpr
  fingerprint, mesh/topology, full dynamic cache token, toolchain
  versions) and serialized, so repeated cold starts and every rank of a
  multi-host job deserialize instead of re-lowering identical SPMD
  programs;
- :func:`compile_step` adapts a ``(state, step, comm)`` elastic step
  function: first call pins; a world change (new comm/epoch) raises
  ``StaleProgramError``, and ``mpx.elastic.run`` catches it and
  ``repin()``s transparently across shrink/grow boundaries.

Tracing a pin runs the IDENTICAL region body ``spmd`` traces
(``parallel/region.make_region_body``), so pinned HLO is byte-identical
to the jit path (pinned by tests/test_aot.py).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.profiling import (account as _account, span as _span,
                               tracing as _tracing)
from . import diskcache, keys, serialization
from .invalidation import StaleProgramError, WorldStamp

__all__ = ["PinnedProgram", "compile", "compile_step", "stats",
           "reset_stats", "through_disk_cache", "tracing_pinned"]


# ---------------------------------------------------------------------------
# counters (always on — the persistent tier of mpx.cache_stats(); mirrored
# into the telemetry meters when telemetry is enabled)
# ---------------------------------------------------------------------------


class _Stats:
    __slots__ = ("pins", "calls", "stale_raises", "disk_loads", "compiles",
                 "warmed")

    def __init__(self):
        self.reset()

    def reset(self):
        self.pins = 0
        self.calls = 0
        self.stale_raises = 0
        self.disk_loads = 0
        self.compiles = 0
        self.warmed = 0


_stats = _Stats()


def stats() -> dict:
    """AOT-layer counters: ``pins`` (programs pinned), ``calls`` (pinned
    executions), ``stale_raises`` (MPX129 refusals), ``disk_loads``
    (pins served by deserializing an artifact of the package's disk
    tier), ``compiles`` (pins whose executable XLA compiled in this
    process: one fetched from jax's persistent cache, or held by jax in
    memory, counts under ``cache_stats()["builds"]["by_origin"]`` only),
    ``warmed`` (programs pre-compiled by the cache-warming CLI —
    aot/warm.py)."""
    return {k: getattr(_stats, k) for k in _Stats.__slots__}


def reset_stats() -> None:
    _stats.reset()


def _meter(name: str, n: int = 1) -> None:
    from ..telemetry import core as _telemetry

    _telemetry.meter(name, n)


# ---------------------------------------------------------------------------
# pinned-trace marker (the MPX128 gate: a trace that is ALREADY being
# pinned must not be advised to pin itself)
# ---------------------------------------------------------------------------

_pinning_depth = 0


def tracing_pinned() -> bool:
    """True while a pin's trace/lower/compile is running (read by
    ``analysis.hook.config_snapshot`` so the MPX128 advisory never fires
    on a program that is being pinned right now)."""
    return _pinning_depth > 0


class _pinned_trace_scope:
    def __enter__(self):
        global _pinning_depth
        _pinning_depth += 1

    def __exit__(self, *exc):
        global _pinning_depth
        _pinning_depth -= 1
        return False


# ---------------------------------------------------------------------------
# key parts
# ---------------------------------------------------------------------------


def mesh_descriptor(mesh) -> Optional[tuple]:
    """Stable cross-process description of the physical partition a
    program was compiled for: axis names, mesh shape, the global device
    ids IN MESH ORDER, device kinds, platform, and process count.

    The device ids matter: the jaxpr text carries none, so two meshes
    over different device subsets (or the same devices permuted) would
    otherwise derive one key and serve an executable whose baked-in
    device assignment targets the wrong chips.  Global ids are
    identical on every process of a multi-host job, so the multi-host
    same-key contract still holds."""
    if mesh is None:
        return None
    devices = mesh.devices
    ids = tuple(int(getattr(d, "id", -1)) for d in devices.flat)
    kinds = tuple(sorted({
        getattr(d, "device_kind", "") for d in devices.flat
    }))
    platforms = tuple(sorted({
        getattr(d, "platform", "") for d in devices.flat
    }))
    return (tuple(mesh.axis_names), tuple(devices.shape), ids, kinds,
            platforms, jax.process_count())


def toolchain_versions() -> tuple:
    """(jax, jaxlib, libtpu, mpi4jax_tpu) — serialized executables are
    not portable across compilers, so all four are key parts."""
    import jaxlib

    from importlib.metadata import PackageNotFoundError, version

    def probe(name):
        try:
            return version(name)
        except PackageNotFoundError:
            return ""

    libtpu = probe("libtpu") or probe("libtpu-nightly")
    return (jax.__version__, getattr(jaxlib, "__version__", ""), libtpu,
            probe("mpi4jax_tpu"))


def _dynamic_token():
    from ..ops._base import dynamic_cache_token

    return dynamic_cache_token()


def _abstract(args: tuple) -> tuple:
    """Arguments -> ``ShapeDtypeStruct`` templates (arrays pass through
    by aval; templates are kept as given)."""
    leaves, treedef = jax.tree.flatten(args)
    return jax.tree.unflatten(treedef, [
        leaf if isinstance(leaf, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(jnp.shape(leaf), jnp.result_type(leaf))
        for leaf in leaves
    ])


# ---------------------------------------------------------------------------
# the pin core: trace -> persistent-cache consult -> compiled callable
# ---------------------------------------------------------------------------


def _consts_digest(closed_jaxpr) -> tuple:
    """Fingerprint the VALUES of a jaxpr's closed-over constants.

    ``str(jaxpr)`` prints constants by shape/dtype only — two programs
    differing in a baked-in weight array would render identically and
    collide on one disk key, serving the wrong executable.  Hash the
    bytes (jaxpr constants are arrays; one that is not is an error, not
    a guessed key)."""
    import numpy as np

    out = []
    for c in closed_jaxpr.consts:
        arr = np.asarray(c)
        out.append((str(arr.dtype), arr.shape,
                    keys.fingerprint(arr.tobytes())))
    return tuple(out)


class _null_scope:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


def _pin_executable(jitted, mesh, avals, label: str,
                    mark_pinned: bool = True):
    """Lower+compile ``jitted`` at ``avals`` through the persistent tier.

    Returns ``(call, key, from_disk)``: ``call`` is the loaded
    ``jax.stages.Compiled``; ``key`` is the persistent cache key (None
    when the tier is disabled); ``from_disk`` says whether the artifact
    was deserialized instead of compiled.

    ``mark_pinned=False`` (the spmd disk-consult path) keeps
    ``tracing_pinned()`` False during the trace: those programs still
    dispatch per call, so the MPX128 hot-loop advisory must keep firing
    for them — only a true ``mpx.compile`` pin is exempt.
    """
    # the build path's spans are kept: recorded with or without a profiler
    # session, with where the executable came from (utils/profiling.py).
    # Only an ``mpx.compile`` pin is a build of its own (``kind``); a
    # region's disk consult lies inside that region's ``mpx.build``
    kind = {"kind": "pin"} if mark_pinned else {}
    with (_pinned_trace_scope() if mark_pinned else _null_scope()), \
            _span("mpx.pin", keep=True, program=label, **kind):
        with _span("mpx.pin.trace", keep=True):
            traced = jitted.trace(*avals)
        key = None
        if diskcache.enabled():
            key = keys.derive_key(
                keys.fingerprint(str(traced.jaxpr)) + ":"
                + keys.fingerprint(
                    keys.canonical(_consts_digest(traced.jaxpr))),
                mesh_descriptor(mesh),
                _dynamic_token(),
                toolchain_versions(),
            )
            payload = diskcache.get(key)
            if payload is not None:
                _stats.disk_loads += 1
                with _span("mpx.pin.load", keep=True):
                    began = time.perf_counter()
                    loaded = serialization.loads(payload)
                    _account("fetch_s", time.perf_counter() - began,
                             "package_cache")
                    return loaded, key, True
        with _span("mpx.pin.lower", keep=True):
            lowered = traced.lower()
        with _span("mpx.pin.compile", keep=True) as built:
            compiled = lowered.compile()
        if built["attrs"].get("origin") == "compiled":
            _stats.compiles += 1
        if key is not None:
            diskcache.put(key, serialization.dumps(compiled))
        return compiled, key, False


def through_disk_cache(jitted, c, label: str = "fn"):
    """Route a jitted SPMD program through the persistent tier (the
    ``mpx.spmd`` program-cache miss hook, parallel/region.py).

    Returns a thin callable that, once per argument signature, traces
    the program, consults the on-disk cache, and thereafter calls the
    loaded/compiled executable directly.  Only installed when
    ``MPI4JAX_TPU_COMPILE_CACHE_DIR`` is set — unset, the jitted
    program is used as-is (keys and HLO byte-identical to a build
    without the AOT layer)."""
    mesh = c.mesh
    memo: dict = {}

    def cached_call(*args):
        leaves, treedef = jax.tree.flatten(args)
        sig = (treedef, tuple(
            (jnp.shape(leaf), str(jnp.result_type(leaf))) for leaf in leaves
        ))
        call = memo.get(sig)
        if call is None:
            call, _, _ = _pin_executable(jitted, mesh, _abstract(args),
                                         label, mark_pinned=False)
            memo[sig] = call
        with _span("mpx.launch"):
            return call(*args)

    return cached_call


# ---------------------------------------------------------------------------
# PinnedProgram: the public artifact
# ---------------------------------------------------------------------------


class PinnedProgram:
    """A fully lowered+compiled SPMD program with a zero-work call path.

    ``program(*dynamic_args)`` validates the captured world — one epoch
    int compare plus one raw-environment fingerprint compare; no flag
    parsing, no key hashing, no cache probe — and executes the pinned
    executable (``jax.stages.Compiled.__call__``, which dispatches
    through jax's C++ pjit path after its first call).  A moved world (config stamp or elastic epoch) raises
    :class:`StaleProgramError` (MPX129); ``repin()`` rebuilds against
    the current world.

    Static arguments were folded at pin time: call with the dynamic
    arguments only, shaped exactly like the abstract templates given to
    :func:`compile` (an AOT executable accepts exactly one signature).
    ``unroll`` records the megastep trip count (1 = single-step): a
    megastep program runs ``unroll`` state iterations per call and
    returns the final carry (docs/aot.md "Megastep execution").
    """

    __slots__ = ("_call", "_world", "_stats", "_respec", "fn_name", "key",
                 "from_disk", "donate_argnums", "unroll",
                 "_traceable", "_donate_call")

    def __init__(self, call, world: WorldStamp, respec, fn_name: str,
                 key, from_disk: bool, donate_argnums, unroll: int = 1,
                 traceable=None, donate_call=None):
        self._call = call
        self._world = world
        self._stats = _stats
        self._respec = respec
        self.fn_name = fn_name
        self.key = key
        self.from_disk = from_disk
        self.donate_argnums = donate_argnums
        self.unroll = unroll
        # the traceable jit twin of the pinned executable (same fn, same
        # donation semantics): the dataflow hazard verifier's re-trace
        # routes through it, because a Compiled cannot accept tracers
        self._traceable = traceable
        # donated positions in CALL-TIME coordinates (statics are folded
        # at pin time and not passed) — what record_donation indexes
        self._donate_call = tuple(donate_call) if donate_call is not None \
            else tuple(donate_argnums)

    def __call__(self, *args):
        if _tracing():
            # a profiler session runs: the same call under spans.
            # ``mpx.call`` less ``mpx.launch`` is what this class adds
            with _span("mpx.call", program=self.fn_name):
                launch = self._prepare(args)
                with _span("mpx.launch"):
                    return launch(*args)
        return self._prepare(args)(*args)

    def _prepare(self, args):
        """All the library does on a call before jax takes over: the
        world stamp, the call count, the hazard notes.  Returns what to
        launch."""
        world = self._world
        if not world.is_current():
            self._stats.stale_raises += 1
            _meter("aot.stale_raises")
            world.check(f"pinned program {self.fn_name!r}")
        self._stats.calls += 1
        # dataflow hazard bookkeeping (analysis/hazards.py MPX139/MPX140):
        # donation-free programs skip both branches on one attribute test
        # each, keeping the zero-work call path intact
        if self._donate_call:
            _note_donation(self, args)
        if self._traceable is not None and _analysis_recording():
            return self._traceable
        return self._call

    def is_stale(self) -> bool:
        """Non-raising probe: would the next call raise MPX129?"""
        return not self._world.is_current()

    def memory_bytes(self) -> Optional[dict]:
        """What the pinned executable needs on a device by XLA's own
        account (``memory_analysis()``), read on demand: ``temporaries``
        (scratch the program holds while it runs, which a device's
        ``memory_stats()`` leaves out), ``arguments``, ``results`` and
        ``code``, in bytes; ``None`` where the backend gives no account."""
        analysis = self._call.memory_analysis()
        if analysis is None:
            return None
        return {"temporaries": int(analysis.temp_size_in_bytes),
                "arguments": int(analysis.argument_size_in_bytes),
                "results": int(analysis.output_size_in_bytes),
                "code": int(analysis.generated_code_size_in_bytes)}

    def repin(self) -> "PinnedProgram":
        """Re-lower/re-compile (or re-load from the persistent tier)
        against the CURRENT world: the re-entry path after a
        ``StaleProgramError``."""
        return self._respec()

    def __repr__(self):
        src = "disk" if self.from_disk else "compiled"
        return (f"PinnedProgram({self.fn_name!r}, {src}, "
                f"epoch={self._world.epoch}"
                + (f", unroll={self.unroll}" if self.unroll > 1 else "")
                + (", STALE" if self.is_stale() else "") + ")")


def _analysis_recording() -> bool:
    """Is any analysis recorder capturing this call site?  Explicit
    ``mpx.analyze`` (global recorder stack) or an armed env-mode region
    context enclosing the call."""
    try:
        from ..analysis import hook
        from ..parallel.region import _region_stack
    except ImportError:  # pragma: no cover - isolated loaders
        return False
    if hook.recording():
        return True
    ctx = _region_stack[-1] if _region_stack else None
    return ctx is not None and \
        getattr(ctx, "analysis_recorder", None) is not None


def _note_donation(program: "PinnedProgram", args) -> None:
    """Hand this call's donated argument identities to the dataflow
    hazard verifier (analysis/hook.record_donation — fully self-gating:
    a no-op unless a recorder is active or the env mode is armed)."""
    try:
        from ..analysis import hook
        from ..parallel.region import _region_stack
    except ImportError:  # pragma: no cover - isolated loaders
        return
    ctx = _region_stack[-1] if _region_stack else None
    donated = [args[i] for i in program._donate_call if i < len(args)]
    hook.record_donation(donated, f"pinned call {program.fn_name!r}",
                         ctx=ctx)


def _normalize_statics(static_argnums, nargs: int) -> tuple:
    if static_argnums is None:
        raw = ()
    elif isinstance(static_argnums, int):
        raw = (static_argnums,)
    else:
        raw = tuple(static_argnums)
    statics = tuple(sorted({i if i >= 0 else i + nargs for i in raw}))
    for i in statics:
        if not 0 <= i < nargs:
            raise ValueError(
                f"static_argnums entry {i} out of range for {nargs} "
                "positional arguments"
            )
    return statics


def compile(fn, *abstract_args, comm=None, donate_argnums=(),
            static_argnums=None, in_specs=None, out_specs=None,
            wrap: Optional[bool] = None,
            unroll: Optional[int] = None) -> PinnedProgram:
    """Pin ``fn(*abstract_args)`` to a fully compiled executable.

    ``fn`` follows the same three conventions as ``mpx.analyze``:

    - an ``mpx.spmd``-decorated function: pinned as-is (its comm,
      specs, static_argnums, and unroll breadcrumbs are adopted; pass
      overrides to replace them);
    - a plain per-rank function: wrapped over ``comm`` (or the default
      comm) exactly like ``mpx.spmd`` would — same region body, same
      HLO;
    - ``wrap=False``: jitted exactly as given (eager-style functions
      taking global arrays and calling ops outside a region).

    ``abstract_args`` are example arrays or ``jax.ShapeDtypeStruct``
    templates — nothing is executed at pin time.  Arguments named by
    ``static_argnums`` must be concrete hashable values; they are folded
    into the program and NOT passed at call time.  ``donate_argnums``
    indexes the original argument positions; donated buffers are reused
    for outputs (the hot-loop double-buffer idiom).

    ``unroll=N`` (N > 1) pins a **megastep**: the body is rewritten into
    a device-resident ``lax.fori_loop`` over N iterations with the
    dynamic arguments as the carry, so each pinned call executes N steps
    for one host dispatch — the per-step host cost falls as 1/N
    (docs/aot.md "Megastep execution"; requires the region convention,
    not ``wrap=False``).  ``None`` resolves
    ``MPI4JAX_TPU_UNROLL_DEFAULT`` (1 = single-step, trace and HLO
    byte-identical to a pin without the megastep layer).

    With ``MPI4JAX_TPU_COMPILE_CACHE_DIR`` set, the lowered+compiled
    artifact is served from / written to the persistent cache
    (docs/aot.md); the call path is identical either way.
    """
    from ..parallel.megastep import validate_unroll
    from ..parallel.region import (
        make_region_body,
        region_axes_spec,
        resolve_comm,
    )

    spec = dict(comm=comm, donate_argnums=donate_argnums,
                static_argnums=static_argnums, in_specs=in_specs,
                out_specs=out_specs, wrap=wrap, unroll=unroll)

    inner = fn
    if wrap is None:
        wrap = True
    if wrap and getattr(fn, "_mpx_spmd", False):
        crumbs = fn._mpx_spmd_kwargs
        inner = fn._mpx_fn
        if comm is None:
            comm = crumbs.get("comm")
        if in_specs is None:
            in_specs = crumbs.get("in_specs")
        if out_specs is None:
            out_specs = crumbs.get("out_specs")
        if static_argnums is None:
            static_argnums = crumbs.get("static_argnums")
        if unroll is None:
            unroll = crumbs.get("unroll")
    # only an EXPLICIT unroll= errors on a shape that cannot carry the
    # loop (wrap=False, no dynamic args); the MPI4JAX_TPU_UNROLL_DEFAULT
    # fleet default degrades those to a single-step pin instead
    explicit_unroll = unroll is not None
    if explicit_unroll:
        n_unroll = validate_unroll(unroll)
    else:
        from ..utils.config import unroll_default

        n_unroll = unroll_default()
    # the wrapper's name first: a caller may rename an spmd-decorated
    # function after decorating it (one body, many programs)
    name = getattr(fn, "__name__", None) or getattr(inner, "__name__", "fn")

    donate = _normalize_statics(donate_argnums, len(abstract_args)) \
        if donate_argnums else ()
    statics = _normalize_statics(static_argnums, len(abstract_args))
    overlap_ = set(donate) & set(statics)
    if overlap_:
        raise ValueError(
            f"cannot donate static argument(s) {sorted(overlap_)}: statics "
            "are folded into the program and never buffered"
        )

    c = resolve_comm(comm)
    if wrap is False:
        if n_unroll > 1:
            if not explicit_unroll:
                n_unroll = 1
            else:
                raise ValueError(
                    "mpx.compile(unroll=N) needs the region calling "
                    "convention (a per-rank or spmd-decorated function): "
                    "an eager-style wrap=False function has no per-rank "
                    "carry to thread through the device-resident loop"
                )
        if c.mesh is None and comm is not None:
            raise RuntimeError(
                "mpx.compile(wrap=False) with an explicit comm needs it "
                "bound to a mesh (comm.bind(mesh))"
            )
        jitted = jax.jit(fn, static_argnums=statics or None,
                         donate_argnums=donate or None)
        trace_args = tuple(
            a if i in statics else _abstract((a,))[0]
            for i, a in enumerate(abstract_args)
        )
        # call-time coordinates: statics are folded and not passed, so a
        # donated original position shifts left past every static below
        # it (donate ∩ statics already rejected above)
        donate_call = tuple(i - sum(1 for s in statics if s < i)
                            for i in donate)
        # with statics the jit twin's signature differs from the pinned
        # call's — no traceable reroute there
        traceable = jitted if not statics else None
        mesh = c.mesh
    else:
        if c.mesh is None:
            raise RuntimeError(
                "mpx.compile requires a comm bound to a mesh "
                "(comm.bind(mesh)) or an available default mesh"
            )
        static_vals = tuple(abstract_args[i] for i in statics)
        try:
            hash(static_vals)
        except TypeError as e:
            raise TypeError(
                f"mpx.compile static argument values must be hashable "
                f"(like jax.jit static_argnums); got {static_vals!r}"
            ) from e
        dyn_args = tuple(a for i, a in enumerate(abstract_args)
                         if i not in statics)
        # donation indexes the ORIGINAL positions; the executable takes
        # only the dynamic args, so remap
        dyn_pos = {orig: j for j, orig in enumerate(
            i for i in range(len(abstract_args)) if i not in statics)}
        donate_dyn = tuple(dyn_pos[i] for i in donate)
        axes_spec = region_axes_spec(c)
        ispecs = in_specs if in_specs is not None else axes_spec
        ospecs = out_specs if out_specs is not None else axes_spec
        if n_unroll > 1 and not dyn_args:
            if not explicit_unroll:
                n_unroll = 1
            else:
                raise ValueError(
                    "mpx.compile(unroll=N) needs at least one dynamic "
                    "argument to carry through the device-resident loop"
                )
        body = make_region_body(
            inner, c, statics, static_vals, (), len(dyn_args),
            squeeze_in=in_specs is None, squeeze_out=out_specs is None,
            unroll=n_unroll, name=name,
        )
        sm = jax.shard_map(body, mesh=c.mesh, in_specs=ispecs,
                           out_specs=ospecs)
        jitted = jax.jit(sm, donate_argnums=donate_dyn or None)
        trace_args = _abstract(dyn_args)
        donate_call = donate_dyn
        traceable = jitted
        mesh = c.mesh

    # capture BEFORE the trace: a flag that moves mid-compile leaves a
    # stamp that (correctly, conservatively) refuses the first call
    world = WorldStamp.capture()
    call, key, from_disk = _pin_executable(jitted, mesh, trace_args, name)
    _stats.pins += 1
    _meter("aot.pins")

    def respec():
        return compile(fn, *abstract_args, **spec)

    return PinnedProgram(call, world, respec, name, key, from_disk, donate,
                         unroll=n_unroll,
                         traceable=traceable, donate_call=donate_call)


# ---------------------------------------------------------------------------
# the elastic adapter: pin-per-world step functions
# ---------------------------------------------------------------------------


class ElasticStep:
    """A ``(state, step, comm)`` step function that executes as a pinned
    program per world.

    The state contract matches the elastic examples: ``state`` is a
    REPLICATED pytree (identical on every rank — parameters after a
    gradient allreduce), carried WITHOUT a rank axis.  Each call tiles
    it to the global convention, runs the pinned program, and returns
    rank 0's row — so the state that crosses commit/restore boundaries
    is world-size-free and survives shrink/grow unchanged.  The step
    index rides as a tiny per-rank array, so stepping never retraces.

    The first call pins ``fn`` over the comm it is handed.  When the
    world moves — ``mpx.elastic.run`` hands a NEW comm after a
    shrink/grow/drain boundary, or the config stamp changes — the next
    call raises :class:`StaleProgramError` (MPX129) and ``repin()``
    drops the pin; ``mpx.elastic.run`` performs exactly that dance
    automatically, so an elastic loop keeps its pinned hot path across
    epochs without serving a single old-world execution.

    ``unroll=N`` pins a **megastep** step: each call executes N
    consecutive ``fn(state, step + i, comm)`` iterations device-resident
    (``lax.fori_loop``; the step index rides in the carry) and returns
    the state after step ``step + N``.  ``mpx.elastic.run`` reads the
    ``unroll`` attribute, aligns ``commit_every`` up to a multiple of N,
    and advances its step counter by N per call; a mid-megastep
    ``StaleProgramError`` retries the whole megastep from the same
    state — restart-idempotent by construction, since state only commits
    at megastep boundaries (docs/aot.md "Megastep execution").
    """

    def __init__(self, fn, donate_state: bool = False, unroll: int = 1):
        from ..parallel.megastep import validate_unroll

        self._fn = fn
        self._donate_state = donate_state
        self.unroll = validate_unroll(unroll)
        self._pinned: Optional[PinnedProgram] = None
        self._world_key = None

    def _step_array(self, comm, step: int):
        return jnp.full((comm.world_size(),), step, jnp.int32)

    @staticmethod
    def _tile(state, k: int):
        """Replicated pytree -> global convention (leading rank axis)."""
        return jax.tree.map(
            lambda v: jnp.tile(jnp.asarray(v)[None],
                               (k,) + (1,) * jnp.ndim(v)), state)

    def __call__(self, state, step: int, comm):
        pinned = self._pinned
        if pinned is not None and self._world_key != (
                comm.uid, getattr(comm, "epoch", 0)):
            from ..analysis.report import mpx_error

            _stats.stale_raises += 1
            _meter("aot.stale_raises")
            raise mpx_error(
                StaleProgramError, "MPX129",
                f"pinned elastic step {getattr(self._fn, '__name__', 'fn')!r} "
                f"was handed a different communicator (uid/epoch "
                f"{self._world_key} -> "
                f"{(comm.uid, getattr(comm, 'epoch', 0))}): the world "
                "moved — repin() and retry (mpx.elastic.run does this "
                "automatically)",
            )
        k = comm.world_size()
        g = self._tile(state, k)
        if pinned is None:
            n_unroll = self.unroll

            def per_rank(st, step_scalar):
                if n_unroll == 1:
                    return self._fn(st, step_scalar, comm)
                from ..parallel.megastep import megastep_loop

                # the megastep form: N device-resident iterations with
                # the state as the carry; the step index advances inside
                # the loop, so one pinned call covers steps
                # [step, step + N)
                def one(i, carry):
                    return self._fn(carry, step_scalar + i, comm)

                return megastep_loop(
                    one, st, n_unroll, comm,
                    label=getattr(self._fn, "__name__", "fn"))

            per_rank.__name__ = getattr(self._fn, "__name__", "fn")
            # unroll=1 here on purpose: the loop (when any) is built
            # above — a non-1 MPI4JAX_TPU_UNROLL_DEFAULT must not wrap a
            # second fori_loop around it
            self._pinned = compile(
                per_rank, g, self._step_array(comm, step), comm=comm,
                donate_argnums=(0,) if self._donate_state else (),
                unroll=1,
            )
            self._world_key = (comm.uid, getattr(comm, "epoch", 0))
            pinned = self._pinned
        out = pinned(g, self._step_array(comm, step))
        return jax.tree.map(lambda v: v[0], out)

    def repin(self) -> "ElasticStep":
        """Drop the pin; the next call re-pins against the comm (and
        state shapes) it is handed."""
        self._pinned = None
        self._world_key = None
        return self


def compile_step(fn, *, donate_state: bool = False,
                 unroll: int = 1) -> ElasticStep:
    """Adapt a per-rank ``fn(state, step, comm)`` for ``mpx.elastic.run``
    with a pinned hot path: see :class:`ElasticStep` (replicated-state
    contract).  ``donate_state`` donates the tiled state buffers into
    each step (they are rebuilt per call, so donation is safe) — the
    double-buffer idiom.  ``unroll=N`` makes each pinned call a megastep
    of N device-resident iterations; ``mpx.elastic.run`` aligns its
    commit cadence to the megastep boundary automatically (docs/aot.md
    "Megastep execution")."""
    return ElasticStep(fn, donate_state=donate_state, unroll=unroll)
