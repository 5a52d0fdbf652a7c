"""Resilience runtime glue: config resolution and the per-op dispatch plan.

``ops/_base.py _run_body`` — the single point all 12 ops flow through —
asks ``plan_for(opname)`` what to do around each collective.  The answer is
``None`` when every resilience feature is off (the default): the op body
runs untouched and the lowered HLO is byte-identical to an uninstrumented
build.  Otherwise a :class:`Plan` brackets the op:

- ``before``: fault-injection probe (delay/die/corrupt — faultinject.py),
  then the input numeric guard (numerics.py), then watchdog arm
  (watchdog.py), each threaded into the program with data dependencies so
  ordering survives XLA scheduling;
- ``after``: watchdog disarm tied to the op's first output, then the output
  numeric guard.

Configuration layers: programmatic overrides (``set_*`` below, for tests and
embedding frameworks) shadow the environment variables
(``MPI4JAX_TPU_WATCHDOG_TIMEOUT`` / ``_FAULT_SPEC`` / ``_CHECK_NUMERICS``,
utils/config.py).  ``cache_token()`` folds the effective configuration into
the compiled-program cache keys (ops/_base.py eager cache, parallel/region.py
spmd cache), so toggling a feature retraces instead of silently serving a
stale program.
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..utils import config
from .faultinject import (
    FaultClause,
    canonical_spec,
    parse_fault_spec,
    probe_host,
)

__all__ = [
    "Plan",
    "plan_for",
    "cache_token",
    "set_watchdog_timeout",
    "set_fault_spec",
    "set_check_numerics",
]

_UNSET = object()

_watchdog_override = _UNSET
_fault_override = _UNSET
_numerics_override = _UNSET


def set_watchdog_timeout(seconds) -> None:
    """Override ``MPI4JAX_TPU_WATCHDOG_TIMEOUT`` (``None``/0 disables;
    pass ``config.watchdog_timeout`` semantics).  ``reset_overrides()``
    returns control to the environment."""
    global _watchdog_override
    if not seconds:
        _watchdog_override = None
        config.bump_config_epoch()
        return
    val = float(seconds)
    # mirror the env path's validation (config.parse_env_float): a negative
    # timeout would declare the first collective hung on the monitor's
    # first scan and kill a healthy job; NaN would silently disable.
    # ``not (val > 0)`` catches both.
    if not (val > 0):
        raise ValueError(f"watchdog timeout must be > 0 seconds, got {seconds!r}")
    _watchdog_override = val
    config.bump_config_epoch()


def set_fault_spec(spec: Optional[str]) -> None:
    """Override ``MPI4JAX_TPU_FAULT_SPEC`` ('' or None disables).  The spec
    is validated immediately (ValueError on bad grammar)."""
    global _fault_override
    parse_fault_spec(spec or "")
    _fault_override = (spec or "").strip()
    config.bump_config_epoch()


def set_check_numerics(enabled) -> None:
    """Override ``MPI4JAX_TPU_CHECK_NUMERICS``."""
    global _numerics_override
    _numerics_override = bool(enabled)
    config.bump_config_epoch()


def reset_overrides() -> None:
    """Drop every programmatic override (environment variables rule again)."""
    global _watchdog_override, _fault_override, _numerics_override
    _watchdog_override = _fault_override = _numerics_override = _UNSET
    config.bump_config_epoch()


def effective_watchdog_timeout() -> Optional[float]:
    if _watchdog_override is not _UNSET:
        return _watchdog_override
    return config.watchdog_timeout()


def effective_fault_clauses() -> Tuple[FaultClause, ...]:
    raw = _fault_override if _fault_override is not _UNSET else config.fault_spec()
    return parse_fault_spec(raw)


def effective_check_numerics() -> bool:
    if _numerics_override is not _UNSET:
        return _numerics_override
    return config.check_numerics()


def cache_token() -> tuple:
    """Hashable fingerprint of the effective resilience configuration —
    belongs in every compiled-program cache key that caches op lowerings.

    The elastic token (resilience/elastic.py) rides here: the
    communication epoch plus the declared elastic knobs (grow, fail
    unit, drain grace, port span).  Advancing the epoch after a shrink
    or a grow changes this token, which changes both program-cache keys
    — every executable traced against the revoked world becomes
    unreachable and the next call re-traces at the new size.  A job that
    never churns, with every elastic knob at its default, carries the
    constant 0 and its keys match a build without the elastic layer
    engaged.
    """
    from .elastic import elastic_cache_token
    from .watchdog import _force_fallback

    return (
        effective_watchdog_timeout(),
        canonical_spec(effective_fault_clauses()),
        effective_check_numerics(),
        # the watchdog backend choice is baked into traced arm/disarm
        # callbacks, so flipping it must retrace too
        _force_fallback,
        elastic_cache_token(),
    )


class Plan:
    """What to weave around one op dispatch (trace-time object)."""

    __slots__ = ("clauses", "timeout", "numerics")

    def __init__(self, clauses, timeout, numerics):
        self.clauses = clauses      # ((bit, FaultClause), ...) matching this op
        self.timeout = timeout      # watchdog seconds or None
        self.numerics = numerics    # bool

    def before(self, mpi_name, call_id, comm, arrays, token):
        """Instrument the op's inputs; returns (arrays, token)."""
        import jax
        import jax.numpy as jnp
        from jax.experimental import io_callback

        from .. import native
        from ..ops.token import Token
        from . import watchdog as wd
        from .numerics import guard_values

        rank = comm.global_rank()

        # Array-less, token-less dispatches (a bare ``barrier()``) give the
        # ties below nothing to anchor to — the probe/arm callbacks would
        # float unordered relative to the collective, and an orphaned arm
        # could outlive its disarm and kill a healthy job.  Synthesize the
        # token: the op body consumes it, restoring the data dependency.
        if not arrays and token is None and (self.clauses or self.timeout is not None):
            token = Token(jnp.zeros((), jnp.uint32))

        if self.clauses:
            clauses = self.clauses

            def _probe(r, _name=mpi_name):
                import numpy as np

                return np.uint32(probe_host(clauses, _name, int(r)))

            mask = io_callback(
                _probe, jax.ShapeDtypeStruct((), jnp.uint32),
                jnp.asarray(rank, jnp.uint32), ordered=False,
            )
            # delay/die must precede the collective: tie every input (and
            # the token, which is the only handle for array-less ops like
            # barrier) to the probe's completion
            arrays = tuple(native._tie(a, mask) for a in arrays)
            if token is not None:
                token = Token(native._tie(token.value, mask))
            arrays = self._apply_corrupt(arrays, mask)

        if self.numerics:
            guard_values(mpi_name, call_id, rank, arrays, "input")

        if self.timeout is not None:
            armed = wd.arm_in_graph(mpi_name, call_id, comm, rank, self.timeout)
            arrays = tuple(wd.after_arm(a, armed) for a in arrays)
            if token is not None:
                token = Token(wd.after_arm(token.value, armed))

        return arrays, token

    def _apply_corrupt(self, arrays, mask):
        import jax.numpy as jnp

        out = list(arrays)
        for bit, clause in self.clauses:
            if clause.verb != "corrupt":
                continue
            fired = ((mask >> bit) & 1) == 1
            fill = jnp.nan if clause.mode == "nan" else jnp.inf
            out = [
                jnp.where(fired, jnp.full_like(a, fill), a)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact) else a
                for a in out
            ]
        return tuple(out)

    def after(self, mpi_name, call_id, comm, dep, results):
        """Instrument the op's outputs (``dep`` = first output's array)."""
        from ..ops.token import Token
        from . import watchdog as wd
        from .numerics import guard_values

        rank = comm.global_rank()
        if self.timeout is not None:
            wd.disarm_in_graph(mpi_name, call_id, comm, rank, dep)
        if self.numerics:
            values = [r.value if isinstance(r, Token) else r for r in results]
            guard_values(mpi_name, call_id, rank, values, "output")


# plan_for memo: Plans are stateless across dispatches (before/after close
# over nothing mutable), so one Plan per (config stamp, opname) serves
# every dispatch until the configuration changes — the per-traced-op
# watchdog-float/fault-spec/numerics re-parsing leaves the hot path.
_plan_memo: list = [None, {}]


def plan_for(opname: str) -> Optional[Plan]:
    """The resilience plan for one op dispatch, or ``None`` when every
    feature is off (the zero-cost default — no graph change at all)."""
    stamp = config.config_stamp()
    if _plan_memo[0] != stamp:
        # publish the stamp LAST (a concurrent reader must never pair the
        # new stamp with the previous memo dict)
        _plan_memo[1] = {}
        _plan_memo[0] = stamp
    memo = _plan_memo[1]
    if opname in memo:
        return memo[opname]
    timeout = effective_watchdog_timeout()
    numerics = effective_check_numerics()
    clauses = tuple(
        (bit, c)
        for bit, c in enumerate(effective_fault_clauses())
        if c.matches_op(opname)
    )
    plan = (None if timeout is None and not numerics and not clauses
            else Plan(clauses, timeout, numerics))
    memo[opname] = plan
    return plan
