"""Telemetry core: mode resolution, per-op counters, infrastructure meters.

The reference measured every collective because every collective WAS a
host call (``perf_counter`` brackets inside the libmpi bridge, ref
mpi_xla_bridge.pyx:47-60); our TPU-native lowering deliberately has no
host call per collective, so observability has to ride the points the
host *does* see:

- **dispatch** (``ops/_base.py``) — every op call flows through one
  Python function; counting there is pure host bookkeeping and costs
  nothing on the device.  That is the ``counters`` tier: per-(op,
  comm uid, algo, dtype) call counts and payload bytes, plus meters for
  the infrastructure around the ops (program-cache hits/misses/
  evictions, recompiles, watchdog arms/expiries, fault injections,
  numeric-guard trips, algorithm selections);
- **host callbacks** (``telemetry/bracket.py``) — the ``events`` tier
  adds begin/end ``io_callback`` brackets threaded around each
  collective with data dependencies (the same threading as the native
  ``op_begin``/``op_end`` trace hooks), feeding the per-rank journal.

Counting semantics (documented, not accidental): a dispatch inside a
traced program counts once per TRACE (the host only sees the trace); an
eager op counts once per CALL (dispatch runs per call, cache hit or
not).  Per-execution, per-rank truth lives in the ``events`` journal,
whose callbacks are compiled into the program.

Mode is ``MPI4JAX_TPU_TELEMETRY={off,counters,events}`` with a
programmatic override (``set_telemetry_mode``), folded into both
compiled-program cache keys via ``telemetry_cache_token()`` exactly like
the resilience and analyze flags.  Pure Python: importable under the
isolated test loader without JAX.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..utils import config
from . import health
from .hist import Histogram

__all__ = [
    "set_telemetry_mode",
    "effective_mode",
    "telemetry_cache_token",
    "meter",
    "snapshot",
    "reset",
]

_UNSET = object()
_mode_override = _UNSET


def set_telemetry_mode(mode: Optional[str]) -> None:
    """Programmatic override of ``MPI4JAX_TPU_TELEMETRY`` (``None``
    returns control to the environment), mirroring ``set_analyze_mode``
    and the resilience ``set_*`` overrides."""
    global _mode_override
    if mode is None:
        _mode_override = _UNSET
        config.bump_config_epoch()
        return
    if mode not in config.TELEMETRY_MODES:
        raise ValueError(
            f"telemetry mode must be one of {config.TELEMETRY_MODES}, "
            f"got {mode!r}"
        )
    _mode_override = mode
    config.bump_config_epoch()


def effective_mode() -> str:
    if _mode_override is not _UNSET:
        return _mode_override
    return config.telemetry_mode()


def events_on() -> bool:
    return effective_mode() == "events"


def telemetry_cache_token() -> tuple:
    """Folded into the compiled-program cache keys (ops/_base.py eager
    cache, parallel/region.py spmd cache): flipping the tier must
    retrace — the counters hook at trace time, and the events brackets
    change the traced program."""
    return (effective_mode(),)


# ---------------------------------------------------------------------------
# the counter registry
# ---------------------------------------------------------------------------


def op_key(op: str, comm_uid, algo: str, dtype: str) -> str:
    """The per-op counter key (also the JSON snapshot key)."""
    return f"{op}|{comm_uid}|{algo}|{dtype}"


class _Counters:
    """Process-wide counter state.  Locked: meters and latency records
    arrive from host-callback threads as well as the dispatch thread."""

    def __init__(self):
        self.lock = threading.Lock()
        self.ops: Dict[str, dict] = {}
        self.meters: Dict[str, int] = {}
        self.latency: Dict[str, Histogram] = {}

    def count_op(self, key: str, nbytes: int,
                 intra: Optional[int] = None,
                 inter: Optional[int] = None,
                 wire_inter: Optional[int] = None) -> None:
        with self.lock:
            row = self.ops.setdefault(
                key, {"calls": 0, "bytes": 0,
                      "intra_bytes": 0, "inter_bytes": 0,
                      "wire_inter_bytes": 0}
            )
            row["calls"] += 1
            row["bytes"] += int(nbytes)
            # link-class attribution (docs/topology.md): modeled per-rank
            # wire bytes by ICI (intra_host) vs DCN (inter_host), filled
            # by the algorithm layer; ops without a model (p2p, gather
            # family, native HLO) default to payload-on-intra
            row["intra_bytes"] += int(nbytes if intra is None else intra)
            inter_logical = int(0 if inter is None else inter)
            row["inter_bytes"] += inter_logical
            # DCN wire bytes after the codec (docs/compression.md): equal
            # to the logical inter bytes unless the hierarchy compressed
            # the inter-host leg — the logical/wire split is how the
            # snapshot shows what the codec actually saved
            row["wire_inter_bytes"] += (
                inter_logical if wire_inter is None else int(wire_inter)
            )

    def bump(self, name: str, n: int) -> None:
        with self.lock:
            self.meters[name] = self.meters.get(name, 0) + n

    def record_latency(self, key: str, seconds: float) -> None:
        with self.lock:
            h = self.latency.get(key)
            if h is None:
                h = self.latency[key] = Histogram()
            h.record(seconds)

    def reset(self) -> None:
        with self.lock:
            self.ops.clear()
            self.meters.clear()
            self.latency.clear()


_counters = _Counters()


def meter(name: str, n: int = 1) -> None:
    """Bump an infrastructure meter (no-op when telemetry is off).

    Meter names are dotted paths (``eager_cache.hits``,
    ``watchdog.expiries``, ``algo.allreduce.ring``, ...); the snapshot
    returns them verbatim.
    """
    if effective_mode() == "off":
        return
    _counters.bump(name, n)


def record_latency(key: str, seconds: float) -> None:
    """Feed one measured op latency into the per-op histogram (called by
    the journal when an events-tier end bracket completes) — and into
    the health detector's rolling window (telemetry/health.py)."""
    _counters.record_latency(key, seconds)
    health.feed_latency(key, seconds)


def count_host_op(key: str, nbytes: int) -> None:
    """Count one HOST-level phase execution into the per-op table — the
    serving runtime's prefill/decode brackets (serving/engine.py), which
    wrap a whole pinned dispatch rather than one collective.  Gated like
    :func:`meter` (no-op when telemetry is off)."""
    if effective_mode() == "off":
        return
    _counters.count_op(key, nbytes)


# ---------------------------------------------------------------------------
# dispatch-point op records
# ---------------------------------------------------------------------------


class OpRecord:
    """One in-flight dispatch's telemetry view (host-side, trace-time)."""

    __slots__ = ("op", "comm_uid", "comm_axes", "bytes", "dtype", "algo",
                 "counted", "intra_bytes", "inter_bytes", "wire_inter_bytes")

    def __init__(self, op, comm_uid, comm_axes, nbytes, dtype, counted):
        self.op = op
        self.comm_uid = comm_uid
        self.comm_axes = comm_axes
        self.bytes = nbytes
        self.dtype = dtype
        self.algo = "native"
        self.counted = counted
        # per-link-class modeled wire bytes (None until the algorithm
        # layer annotates them; count_op defaults payload-on-intra)
        self.intra_bytes = None
        self.inter_bytes = None
        # post-codec DCN bytes (None -> same as inter_bytes; only the
        # compressed hierarchy leg sets this, docs/compression.md)
        self.wire_inter_bytes = None

    def key(self) -> str:
        return op_key(self.op, self.comm_uid, self.algo, self.dtype)


# innermost-wins stack of open dispatches (annotate targets the top);
# single-threaded like the region stack it mirrors
_open_ops: List[OpRecord] = []

# active eager-capture cell: while set, closed records are captured on the
# cell instead of counted (the eager dispatch loop counts per CALL itself,
# and the traced program may be compiled once and reused many times)
_eager_cell: Optional["EagerCell"] = None


class EagerCell:
    """Per-eager-cache-entry stash of trace records, keyed by the call's
    argument signature (shapes + dtypes).

    A pure cache hit re-runs no Python trace, so the dispatch loop counts
    the call from the stash.  jit retraces internally per signature, and
    each retrace lands its records under ITS signature — so a
    shape-alternating workload counts every call with the bytes, dtype,
    and selected algorithm of the program that actually serves it, not
    whichever shape happened to trace last."""

    __slots__ = ("by_sig",)

    def __init__(self):
        self.by_sig: dict = {}

    def records_for(self, sig) -> List[OpRecord]:
        recs = self.by_sig.get(sig)
        if recs is not None:
            return recs
        # a hit implies the signature traced at some point; this fallback
        # only covers state loss (e.g. telemetry enabled mid-entry)
        return next(reversed(self.by_sig.values())) if self.by_sig else []


def call_signature(arrays) -> tuple:
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


class capture_eager:
    """Context manager for the eager dispatch path: records closed during
    the ``with`` land on ``cell`` under ``sig`` instead of the counters.
    A raising call does NOT refresh the stash — a partial trace must not
    poison the counts of later successful calls."""

    def __init__(self, cell: EagerCell, sig: tuple):
        self.cell = cell
        self.sig = sig
        self._pending: List[OpRecord] = []

    def __enter__(self):
        global _eager_cell
        self._saved = _eager_cell
        _eager_cell = self
        return self.cell

    def __exit__(self, exc_type, exc, tb):
        global _eager_cell
        _eager_cell = self._saved
        if self._pending and exc_type is None:
            self.cell.by_sig[self.sig] = self._pending
        return False


def open_op(opname: str, comm, arrays) -> Optional[OpRecord]:
    """Open a telemetry record for one dispatch (``None`` when telemetry
    is off — the zero-cost default)."""
    if effective_mode() == "off":
        return None
    health.ensure_boundary_hook()
    a0 = arrays[0] if arrays else None
    nbytes = 0
    dtype = ""
    if a0 is not None:
        nbytes = int(a0.size) * a0.dtype.itemsize
        dtype = str(a0.dtype)
    rec = OpRecord(opname, comm.uid, tuple(comm.axes), nbytes, dtype,
                   counted=_eager_cell is None)
    _open_ops.append(rec)
    return rec


def annotate(**fields) -> None:
    """Record trace-time facts only the op body knows — the selected
    algorithm, the view a gather-family op took of its block
    (``ops/_base.all_gather_blocks``), and the modeled per-link-class
    wire bytes
    (``link_bytes=(intra_host, inter_host)``, see
    ``ops/_hierarchy.annotate_selection``).  No-op when nothing is open
    (safe to call unconditionally from op bodies, mirroring
    ``analysis.hook.annotate``)."""
    if not _open_ops:
        return
    rec = _open_ops[-1]
    algo = fields.get("algo")
    if algo is not None:
        rec.algo = algo
        meter(f"algo.{rec.op}.{algo}")
    view = fields.get("view")
    if view is not None:
        meter(f"view.{rec.op}.{view}")
    link = fields.get("link_bytes")
    if link is not None:
        rec.intra_bytes, rec.inter_bytes = link
    wire = fields.get("wire_bytes")
    if wire is not None:
        # (intra, inter) after the DCN codec — the intra leg is never
        # compressed, so only the inter component is recorded
        rec.wire_inter_bytes = wire[1]


def close_op(rec: Optional[OpRecord]) -> None:
    """Commit a record: count it (traced dispatch), or stash it on the
    active eager cell for per-call counting by the dispatch loop."""
    if rec is None:
        return
    if _open_ops and _open_ops[-1] is rec:
        _open_ops.pop()
    if _eager_cell is not None:
        _eager_cell._pending.append(rec)
        return
    if rec.counted:
        _counters.count_op(rec.key(), rec.bytes,
                           rec.intra_bytes, rec.inter_bytes,
                           rec.wire_inter_bytes)
        health.record_dispatch(rec)


def abort_op(rec: Optional[OpRecord]) -> None:
    """Unwind a record whose op body raised (nothing is counted)."""
    if rec is not None and _open_ops and _open_ops[-1] is rec:
        _open_ops.pop()


def count_eager_call(cell: EagerCell, sig: tuple) -> None:
    """Count one eager CALL from the entry's stashed trace records for
    this call's signature (cache hits included — dispatch runs per call,
    the trace does not)."""
    if effective_mode() == "off":
        return
    for rec in cell.records_for(sig):
        _counters.count_op(rec.key(), rec.bytes,
                           rec.intra_bytes, rec.inter_bytes,
                           rec.wire_inter_bytes)
        health.record_dispatch(rec)


def current_open() -> Optional[OpRecord]:
    return _open_ops[-1] if _open_ops else None


# ---------------------------------------------------------------------------
# snapshot / reset
# ---------------------------------------------------------------------------


def snapshot(include_events: bool = False) -> dict:
    """JSON-ready view of everything collected so far on THIS process.

    ``include_events`` additionally embeds the events-tier journal
    records (used by ``report()`` for cross-rank skew and by ``dump()``).
    """
    from . import journal

    with _counters.lock:
        ops = {
            key: {
                "op": key.split("|")[0],
                "comm_uid": key.split("|")[1],
                "algo": key.split("|")[2],
                "dtype": key.split("|")[3],
                "calls": row["calls"],
                "bytes": row["bytes"],
                "intra_bytes": row.get("intra_bytes", 0),
                "inter_bytes": row.get("inter_bytes", 0),
                "wire_inter_bytes": row.get(
                    "wire_inter_bytes", row.get("inter_bytes", 0)),
            }
            for key, row in _counters.ops.items()
        }
        for key, h in _counters.latency.items():
            ops.setdefault(key, {
                "op": key.split("|")[0],
                "comm_uid": key.split("|")[1],
                "algo": key.split("|")[2],
                "dtype": key.split("|")[3],
                "calls": 0,
                "bytes": 0,
                "intra_bytes": 0,
                "inter_bytes": 0,
                "wire_inter_bytes": 0,
            })["latency"] = h.to_dict()
        meters = dict(_counters.meters)
    snap = {
        "version": 1,
        "mode": effective_mode(),
        "process": journal.process_index(),
        "ops": ops,
        "meters": meters,
    }
    # the elastic epoch audit trail (epoch, world size, cause) rides
    # every snapshot so report() can render a churn run's history;
    # guarded — the resilience package is optional under the isolated
    # test loaders, and a never-churned job contributes nothing
    try:
        from ..resilience import elastic as _elastic
    except ImportError:
        pass
    else:
        history = _elastic.epoch_history()
        if history:
            snap["epochs"] = history
    # the compile-cache tier (docs/aot.md): AOT pin/call counters + the
    # persistent disk-cache counters, so report() can render the
    # cold-start before/after evidence.  Guarded — the aot package needs
    # jax (absent under the isolated loaders), and a process that never
    # pinned nor enabled the cache dir contributes nothing.
    try:
        from ..aot import stats as _aot_stats
    except ImportError:
        pass
    else:
        cc = _aot_stats()
        if (any(cc["aot"].values()) or cc["disk_cache"]["enabled"]
                or any(v for k, v in cc["disk_cache"].items()
                       if isinstance(v, int))):
            snap["compile_cache"] = cc
    # the active tuning layer (docs/autotune.md): stamp + per-knob
    # tuned-vs-default values, so report() renders what this process is
    # actually running with.  Absent entirely when no layer is loaded —
    # the snapshot stays byte-identical to a build without autotune.
    from ..utils import config as _config

    tuning = _config.tuning_snapshot()
    if tuning:
        snap["tuning"] = tuning
    # dropped-record accounting (journal overflow + flight-ring
    # overwrites): present only when something was actually dropped, so
    # a healthy snapshot stays byte-identical to the pre-health shape
    dropped = {"journal": journal.dropped_records(),
               "flight_ring": health.ring_dropped()}
    if any(dropped.values()):
        snap["dropped"] = dropped
    if include_events:
        snap["events"] = journal.snapshot_events()
    return snap


def reset() -> None:
    """Forget every counter, meter, histogram, and journal record (test
    isolation; also the per-sweep reset ``benchmarks/micro.py`` uses)."""
    from . import journal

    _counters.reset()
    del _open_ops[:]
    journal.reset()
    health.reset()
