"""The serving engine: pinned per-bucket programs under the scheduler.

This is where the whole perf stack converges on one loop (ROADMAP item
1, docs/serving.md):

- each ``(bucket, phase)`` pair maps to ONE program — prefill and
  decode pinned separately through ``mpx.compile`` (zero per-call key
  work, PR 10), decode driven as a **megastep**
  (``unroll=MPI4JAX_TPU_SERVING_UNROLL``, PR 11) so one host dispatch
  generates N tokens per live lane;
- the scheduler (serving/scheduler.py) admits/evicts ONLY at megastep
  boundaries: batch composition changes between dispatches, never
  inside one, and the bucket table pads the live batch up so composition
  churn cannot force a retrace;
- KV state lives in a slot pool (serving/kvcache.py) sharded over the
  tensor-parallel comm; admission binds slot ids, eviction frees them —
  scatter updates, no reshapes;
- every shape-derived knob is consulted with the PADDED bucket payload
  (serving/buckets.bucket_payload_bytes), so two requests in one bucket
  hit one cache key by construction;
- elastic integration (PR 9): a ``resilience.elastic.BoundaryControl``
  is polled at every megastep boundary — a SIGTERM'd (preempted) rank
  drains at the boundary, survivors re-shard the committed master
  parameters at the new world size, re-pin the bucket table, and
  RE-ADMIT every in-flight sequence by re-prefilling it from its
  committed token history (prompt + generated so far, which IS the KV
  state's content — recompute-style recovery).  Zero failed requests.

The module imports jax lazily: :class:`ServingConfig` and
:func:`warm_manifest` are pure (the ``aot warm --emit-manifest`` path
and the isolated test loaders run them without jax).
"""

from __future__ import annotations

import itertools
import time
from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Tuple

from . import model
from .buckets import BucketTable, bucket_payload_bytes, declare_buckets
from .kvcache import SlotAllocator, kv_shape
from .metrics import summarize
from .scheduler import ContinuousScheduler, Request, StaticScheduler

__all__ = ["ServingConfig", "ServingEngine", "warm_manifest"]

PHASES = ("prefill", "decode")
# + the elastic-replay prefill (full-width prompt buffer): pinned on
# demand at a drain boundary, warmed by the manifest so a drain-ready
# fleet cold-starts those too
ALL_PHASES = ("prefill", "decode", "replay")

_engine_ids = itertools.count()


@dataclass(frozen=True)
class ServingConfig:
    """Static shape of one serving deployment (pure; hashable).

    ``heads`` and ``ffn`` must divide by every world size the deployment
    can shrink to (24 and 384 cover 1/2/3/4/6/8 — the default drill
    sizes); ``max_len`` bounds prompt + generated + megastep overshoot.
    ``clock`` is ``"wall"`` (real time) or ``"virtual"`` (one
    ``tick_s`` per megastep boundary — the deterministic clock the
    multi-process drill needs: every rank of a lockstep host loop must
    make identical admission decisions, which wall clocks cannot
    guarantee).
    """

    vocab: int = 64
    heads: int = 24
    head_dim: int = 4
    ffn: int = 384
    max_len: int = 48
    max_prompt: int = 16
    max_batch: int = 8
    buckets: Tuple[int, ...] = ()
    kv_slots: int = 0
    unroll: int = 4
    slo_p99_ms: float = 1000.0
    seed: int = 0
    clock: str = "wall"
    tick_s: float = 0.01

    @property
    def dim(self) -> int:
        return self.heads * self.head_dim

    @classmethod
    def from_env(cls, **overrides) -> "ServingConfig":
        """Defaults from the ``MPI4JAX_TPU_SERVING_*`` flag registry
        (utils/config.py), explicit keyword overrides winning."""
        from ..utils import config

        base = cls(
            max_batch=config.serving_max_batch(),
            kv_slots=config.serving_kv_slots(),
            unroll=config.serving_unroll(),
            slo_p99_ms=config.serving_slo_p99_ms(),
        )
        spec = config.serving_buckets()
        if spec:
            base = replace(base, buckets=BucketTable.from_spec(spec).buckets)
        return replace(base, **overrides) if overrides else base

    def table(self) -> BucketTable:
        if self.buckets:
            t = BucketTable(self.buckets)
            if t.max_batch != self.max_batch:
                raise ValueError(
                    f"bucket table {t.buckets} must top out at max_batch "
                    f"({self.max_batch})"
                )
            return t
        return BucketTable.from_spec("", self.max_batch)

    def slots(self) -> int:
        return self.kv_slots or 2 * self.max_batch

    def validate_world(self, k: int) -> None:
        if k < 1:
            raise ValueError(f"world size must be >= 1, got {k}")
        if self.heads % k or self.ffn % k:
            raise ValueError(
                f"serving config (heads={self.heads}, ffn={self.ffn}) "
                f"cannot shard over {k} ranks: both must divide by every "
                "world size the deployment runs at (docs/serving.md)"
            )
        if self.unroll < 1:
            raise ValueError(f"unroll must be >= 1, got {self.unroll}")
        if not 1 <= self.max_prompt <= self.max_len:
            raise ValueError(
                f"max_prompt ({self.max_prompt}) must be in "
                f"[1, max_len={self.max_len}]"
            )

    def budget_check(self, prompt_len: int, max_new: int) -> None:
        """A request must fit the prompt buffer AND the KV row: prompt +
        generated + one megastep's overshoot + the trailing token
        column."""
        if prompt_len > self.max_prompt:
            raise ValueError(
                f"prompt of {prompt_len} tokens exceeds max_prompt "
                f"({self.max_prompt}) — the admission prefill's padded "
                "width (docs/serving.md)"
            )
        need = prompt_len + max_new + self.unroll + 1
        if need > self.max_len:
            raise ValueError(
                f"request needs up to {need} KV positions (prompt "
                f"{prompt_len} + max_new {max_new} + unroll "
                f"{self.unroll} + 1) but max_len is {self.max_len}"
            )

    # -- program shapes (pure: shared by the pin path and the warm
    #    manifest, so warming hits the exact keys serving will ask for) --

    def _param_shapes(self, k: int) -> List[Tuple[Tuple[int, ...], str]]:
        hl, fl = self.heads // k, self.ffn // k
        d, dh = self.dim, self.head_dim
        return [
            ((k, self.vocab, d), "float32"),           # emb
            ((k, d, 3 * hl * dh), "float32"),          # wqkv
            ((k, hl * dh, d), "float32"),              # wo
            ((k, d, fl), "float32"),                   # w1
            ((k, fl, d), "float32"),                   # w2
        ]

    def prompt_width(self, phase: str) -> int:
        """The padded prompt width of a prefill-family program:
        ``prefill`` (admission) pads to the tight ``max_prompt``;
        ``replay`` (elastic re-admission of an in-flight sequence from
        its committed token history) pads to the full ``max_len`` —
        the history can be as long as the KV row."""
        return self.max_prompt if phase == "prefill" else self.max_len

    def program_args(self, phase: str, bucket: int,
                     k: int) -> List[Tuple[Tuple[int, ...], str]]:
        """Abstract (global) argument shapes of one (phase, bucket)
        program at world size ``k``."""
        if phase not in ALL_PHASES:
            raise ValueError(
                f"phase must be one of {ALL_PHASES}, got {phase!r}")
        hl = self.heads // k
        kv = (k,) + kv_shape(self.slots(), self.max_len, hl, self.head_dim)
        args = self._param_shapes(k) + [
            (kv, "float32"),                           # kk
            (kv, "float32"),                           # vv
            ((k, self.slots() + 1, self.max_len), "int32"),  # tok_table
        ]
        if phase in ("prefill", "replay"):
            width = self.prompt_width(phase)
            args += [
                ((k, bucket, width), "int32"),         # prompts
                ((k, bucket), "int32"),                # plens
                ((k, bucket), "int32"),                # slots
            ]
        else:
            args += [
                ((k, bucket), "int32"),                # last_tok
                ((k, bucket), "int32"),                # lens
                ((k, bucket), "int32"),                # slots
            ]
        return args

    def collective_payload_bytes(self, bucket: int) -> int:
        """Per-collective payload of a decode step at ``bucket`` — the
        PADDED bytes every payload-bucketed knob must be consulted with
        (buckets.bucket_payload_bytes; the MPX136/one-key rule)."""
        return bucket_payload_bytes(bucket, self.dim * 4)

    def workload_meta(self, k: int) -> Dict:
        return {
            "model": (f"tp-decoder d={self.dim} h={self.heads} "
                      f"ffn={self.ffn} L={self.max_len}"),
            "buckets": list(self.table().buckets),
            "kv_slots": self.slots(),
            "unroll": self.unroll,
            "tensor_parallel": k,
        }


def warm_manifest(cfg: ServingConfig, world: int) -> dict:
    """The ``python -m mpi4jax_tpu.aot warm`` manifest covering EVERY
    (bucket, phase) program of a deployment: one command pre-populates
    the persistent compile cache for a whole fleet cold start, and a
    subsequent serving run compiles nothing (``disk_cache.misses == 0``
    — asserted by the CI serving lane).  Pure (no jax)."""
    cfg.validate_world(world)
    programs = []
    for bucket in cfg.table().buckets:
        for phase in ALL_PHASES:
            fn = "decode_step" if phase == "decode" else "prefill_step"
            programs.append({
                "fn": f"mpi4jax_tpu.serving.model:{fn}",
                "label": f"serving.{phase}.b{bucket}",
                "args": [
                    {"shape": list(shape), "dtype": dtype}
                    for shape, dtype in cfg.program_args(phase, bucket,
                                                         world)
                ],
                # the prefill family pins explicitly at 1 so a
                # fleet-wide MPI4JAX_TPU_UNROLL_DEFAULT can never
                # megastep-ify a non-carry-shaped body; decode IS the
                # megastep
                "unroll": cfg.unroll if phase == "decode" else 1,
            })
    return {"programs": programs,
            "meta": {"kind": "serving", "world": world,
                     "buckets": list(cfg.table().buckets)}}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ServingEngine:
    """One tensor-parallel serving replica (see module docstring).

    ``pin="auto"`` drives programs through ``mpx.compile`` pinned
    executables on a single-controller world and through the ``mpx.spmd``
    program cache on multi-process worlds (same traced bodies, same
    per-bucket one-program rule; the jit path is the one the
    multi-controller input plumbing is proven on).  ``store`` (an
    ``mpx.ShardStore``) arms the elastic boundary: SIGTERM/preemption
    drains execute between megasteps.
    """

    def __init__(self, cfg: ServingConfig, comm=None, *, store=None,
                 pin: object = "auto"):
        from ..parallel.region import resolve_comm

        self.cfg = cfg
        self.comm = resolve_comm(comm)
        self.world = int(self.comm.world_size())
        cfg.validate_world(self.world)
        self.table = cfg.table()
        self.store = store
        # the store's comm IS the drain/shrink world: a store bound to a
        # different comm would announce boundaries on one world while
        # the engine serves another (note ShardStore.comm lazily binds
        # the default comm, so identity is checked by uid, not None)
        if store is not None and store.comm.uid != self.comm.uid:
            raise ValueError(
                "the elastic store must be built over the serving comm "
                f"(store comm uid {store.comm.uid} != serving comm uid "
                f"{self.comm.uid})"
            )
        self.master = model.init_master(cfg.vocab, cfg.dim, cfg.heads,
                                        cfg.head_dim, cfg.ffn, cfg.seed)
        if pin == "auto":
            import jax

            pin = jax.process_count() == 1
        self.pin = bool(pin)
        self.drained = False
        self._uid = next(_engine_ids)
        self._programs: Dict[Tuple[str, int], object] = {}
        self._alloc = SlotAllocator(cfg.slots())
        self._phase_seq = {p: 0 for p in ALL_PHASES}
        self._boundary = 0
        self._state = None   # (emb, wqkv, wo, w1, w2, kk, vv, tok)
        self._build_device_state()

    # -- device state ------------------------------------------------------

    def _build_device_state(self) -> None:
        import numpy as np

        k = self.world
        hl = self.cfg.heads // k
        params = model.shard_params(self.master, k)
        kv = np.zeros((k,) + kv_shape(self.cfg.slots(), self.cfg.max_len,
                                      hl, self.cfg.head_dim), np.float32)
        tok = np.zeros((k, self.cfg.slots() + 1, self.cfg.max_len),
                       np.int32)
        self._state = tuple(self._prep(a) for a in
                            params + (kv, kv.copy(), tok))

    def _prep(self, arr):
        """Host array -> program input.  Single-controller: committed
        to the serving mesh in the programs' own layout (row ``r`` on
        rank ``r``'s device), so the parameters and the KV pool are
        placed once at build time instead of being re-sharded off the
        first device by every dispatch.  Multi-process: the plain numpy
        array — every process passes the identical global value and jit
        commits it against the mesh (the elastic-drill convention)."""
        import jax

        if jax.process_count() == 1:
            from ..parallel.region import shard_global

            return shard_global(arr, self.comm)
        return arr

    def _lane(self, values, fill) -> "object":
        """Per-lane host array [bucket], padded with ``fill``, tiled to
        the global convention [k, bucket]."""
        import numpy as np

        bucket = self.table.bucket_for(len(values))
        row = np.full((bucket,), fill, np.int32)
        row[:len(values)] = np.asarray(values, np.int32)
        return self._prep(np.tile(row[None], (self.world, 1)))

    @staticmethod
    def _host(x):
        """One rank's row of a global array, on host."""
        import numpy as np

        return np.asarray(x[0])

    # -- programs ----------------------------------------------------------

    def _program(self, phase: str, bucket: int):
        key = (phase, bucket)
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        import jax
        import numpy as np

        fn = model.decode_step if phase == "decode" else model.prefill_step
        unroll = self.cfg.unroll if phase == "decode" else 1
        if self.pin:
            from ..aot.pinning import compile as aot_compile

            avals = tuple(
                jax.ShapeDtypeStruct(shape, np.dtype(dtype))
                for shape, dtype in self.cfg.program_args(phase, bucket,
                                                          self.world)
            )
            prog = aot_compile(fn, *avals, comm=self.comm, unroll=unroll)
        else:
            from ..parallel.region import spmd

            prog = spmd(comm=self.comm, unroll=unroll)(fn)
        self._programs[key] = prog
        self._meter(f"serving.programs.{phase}")
        return prog

    def warm(self) -> float:
        """Build every admission/decode program of the bucket table now
        (a pin compiles at build time), so no request pays a compile
        inside the serving loop; returns the seconds it took.  The
        elastic ``replay`` programs stay on demand: only a drain boundary
        needs them."""
        t0 = time.perf_counter()
        for bucket in self.table.buckets:
            for phase in PHASES:
                self._program(phase, bucket)
        return time.perf_counter() - t0

    # -- telemetry ---------------------------------------------------------

    def _meter(self, name: str, n: int = 1) -> None:
        from ..telemetry import core as tcore

        tcore.meter(name, n)

    @contextmanager
    def _phase(self, phase: str, bucket: int, nbytes: int):
        """Per-phase serving bracket: a host-side begin/end pair around
        one prefill/decode dispatch — an op-table row per (phase,
        bucket) with p50/p99 (and, in the events tier, a journal record
        whose deterministic call id matches across processes, feeding
        ``telemetry.report()``'s straggler attribution)."""
        from ..telemetry import core as tcore

        if tcore.effective_mode() == "off":
            yield
            return
        from ..telemetry import journal

        key = tcore.op_key(f"serving.{phase}", self.comm.uid,
                           f"b{bucket}", "")
        events = tcore.events_on()
        call_id = None
        rank = journal.process_index()
        if events:
            call_id = f"srv{self._uid}.{phase}.{self._phase_seq[phase]}"
            self._phase_seq[phase] += 1
            journal.begin(call_id, rank, {
                "op": f"serving.{phase}", "comm_uid": self.comm.uid,
                "bucket": bucket, "bytes": nbytes, "dtype": "",
                "unroll": self.cfg.unroll if phase == "decode" else 1,
            })
        t0 = time.perf_counter()
        try:
            yield
        finally:
            # close the bracket even when the dispatch raises: an
            # unmatched journal begin would corrupt the cross-process
            # pairing the straggler attribution matches on
            dt = time.perf_counter() - t0
            tcore.count_host_op(key, nbytes)
            if events:
                journal.end(call_id, rank, {"algo": f"b{bucket}"})
            else:
                tcore.record_latency(key, dt)

    # -- phases ------------------------------------------------------------

    def _prefill(self, seqs, phase: str = "prefill") -> None:
        import jax
        import numpy as np

        bucket = self.table.bucket_for(len(seqs))
        width = self.cfg.prompt_width(phase)
        prompts = []
        for s in seqs:
            row = list(s.tokens)
            if len(row) > width:
                raise RuntimeError(
                    f"{phase} history of {len(row)} tokens exceeds the "
                    f"padded prompt width {width}"
                )
            prompts.append(row + [0] * (width - len(row)))
        prompts += [[0] * width] * (bucket - len(seqs))
        prompts_g = self._prep(np.tile(
            np.asarray(prompts, np.int32)[None], (self.world, 1, 1)))
        plens_g = self._lane([len(s.tokens) for s in seqs], 1)
        slots_g = self._lane([s.slot for s in seqs], self._alloc.scratch)
        nbytes = bucket_payload_bytes(bucket, width * self.cfg.dim * 4)
        with self._phase(phase, bucket, nbytes):
            out = self._program(phase, bucket)(
                *self._state, prompts_g, plens_g, slots_g)
            jax.block_until_ready(out)
        kk, vv, tok, _first = out
        self._state = self._state[:5] + (kk, vv, tok)
        self._meter("serving.prefills")

    def _decode(self) -> None:
        import jax

        seqs = self._sched.running
        bucket = self.table.bucket_for(len(seqs))
        last_g = self._lane([s.tokens[-1] for s in seqs], 0)
        lens_g = self._lane([len(s.tokens) - 1 for s in seqs], 0)
        slots_g = self._lane([s.slot for s in seqs], self._alloc.scratch)
        with self._phase("decode", bucket,
                         self.cfg.collective_payload_bytes(bucket)):
            out = self._program("decode", bucket)(
                *self._state, last_g, lens_g, slots_g)
            jax.block_until_ready(out)
        self._state = out[:8]
        self._meter("serving.megasteps")

    def _collect_tokens(self, seqs, stride: int, now: float) -> int:
        """Read newly generated tokens off the token table (host mirror
        of one rank's row — the table is replicated content).  A lane's
        token columns run through ``len(tokens) - 1``; the dispatch just
        executed appended ``stride`` more (1 for prefill, ``unroll`` for
        a decode megastep)."""
        tok = self._host(self._state[7])
        produced = 0
        for s in seqs:
            have = len(s.tokens)
            row = tok[s.slot]
            fresh = row[have:min(self.cfg.max_len, have + stride)]
            if len(fresh):
                s.record(fresh, now)
                produced += len(fresh)
        return produced

    # -- elastic boundary --------------------------------------------------

    def _world_changed(self) -> None:
        """Survivor side of a drain/grow boundary: adopt the store's
        rebuilt comm, re-shard the committed master at the new world
        size, re-pin every bucket, and re-admit in-flight sequences by
        re-prefilling their committed token history."""
        self.comm = self.store.comm
        self.world = int(self.comm.world_size())
        self.cfg.validate_world(self.world)
        self._programs.clear()
        self._build_device_state()
        # pull every in-flight sequence out of the OLD slot pool, then
        # swap in a fresh pool (the KV tensors were rebuilt empty) and
        # re-point the live scheduler at it before re-seating
        moved = self._sched.requeue_running()
        self._alloc = SlotAllocator(self.cfg.slots())
        self._sched.alloc = self._alloc
        if moved:
            # <= max_batch sequences by the scheduler's residency cap,
            # so one full-width replay prefill re-seats them all: the
            # committed history (prompt + generated) becomes the
            # prompt, rebuilding the KV content on the survivors; the
            # one token it samples is the sequence's NEXT token and is
            # discarded here (the next decode megastep regenerates it
            # into the token table before the host ever reads it)
            self._sched.readmit(moved)
            self._meter("serving.readmissions", len(moved))
            self._prefill(moved, phase="replay")

    # -- the loop ----------------------------------------------------------

    def _now(self, t0: float) -> float:
        if self.cfg.clock == "virtual":
            return self._boundary * self.cfg.tick_s
        return time.monotonic() - t0

    def run(self, trace: List[Request], *, scheduler: str = "continuous",
            max_boundaries: Optional[int] = None) -> Dict:
        """Serve ``trace`` to completion; returns the metric block of
        serving/metrics.summarize plus engine bookkeeping.  A drained
        rank (elastic preemption) exits early with ``self.drained``
        set — its in-flight sequences continue on the survivors, so it
        reports zero failures by construction."""
        from ..parallel import megastep as _megastep
        from ..resilience.elastic import BoundaryControl

        if self.drained:
            raise RuntimeError(
                "this engine drained out of its world (elastic "
                "preemption); build a fresh ServingEngine over the "
                "current comm"
            )
        sched_cls = (ContinuousScheduler if scheduler == "continuous"
                     else StaticScheduler)
        self._alloc.reset()
        self._sched = sched_cls(self.table, self._alloc)
        self._boundary = 0
        for r in trace:
            self.cfg.budget_check(r.prompt_len, r.max_new_tokens)

        # the MPX136 gate is scoped to the serving loop: the engine's
        # own traces happen inside run(), and a bucket table declared
        # forever would flag unrelated later traces in the process
        from .buckets import clear_declared_buckets, declared_buckets

        prev_table = declared_buckets()
        declare_buckets(self.table)

        boundary = BoundaryControl(self.store) if self.store is not None \
            else None
        if boundary is not None and self.store.committed_step is None:
            # the committed state a survivor re-shards after a world
            # change; parameters are static in serving, so ONE commit
            # covers the whole run
            self.store.commit(0, {"params": self.master})

        t0 = time.monotonic()
        wall0 = time.perf_counter()
        try:
            if boundary is not None:
                boundary.__enter__()
            while not self._sched.idle(trace):
                now = self._now(t0)
                self._sched.offer(trace, now)
                new = self._sched.admit(now)
                if new:
                    self._meter("serving.requests_admitted", len(new))
                    self._prefill(new)
                    self._collect_tokens(new, 1, self._now(t0))
                if self._sched.running:
                    self._decode()
                    self._collect_tokens(self._sched.running,
                                         self.cfg.unroll, self._now(t0))
                elif self.cfg.clock == "wall":
                    nxt = self._sched.next_arrival_s(trace)
                    if nxt is not None:
                        time.sleep(min(0.05, max(0.0, nxt - now)))
                done = self._sched.finish_ready(self._now(t0))
                if done:
                    self._meter("serving.requests_completed", len(done))
                self._boundary += 1
                _megastep.run_boundary_hooks(self._boundary, engine=self)
                if boundary is not None:
                    outcome = boundary.poll(
                        self._boundary, {"params": self.master},
                        committed=True)
                    if outcome is not None:
                        kind = outcome[0]
                        if kind == "leave":
                            self.drained = True
                            break
                        self._world_changed()
                if max_boundaries is not None \
                        and self._boundary >= max_boundaries:
                    break
        finally:
            if boundary is not None:
                boundary.__exit__(None, None, None)
            if prev_table is not None:
                declare_buckets(prev_table)
            else:
                clear_declared_buckets()

        wall = time.perf_counter() - wall0
        if self.cfg.clock == "virtual":
            wall = self._boundary * self.cfg.tick_s
        finished = self._sched.finished
        failed = 0 if self.drained else (
            len(trace) - len(finished))
        self._meter("serving.tokens_generated",
                    sum(len(s.generated) for s in finished))
        if failed:
            self._meter("serving.requests_failed", failed)
        out = summarize(finished, wall_s=wall, chips=self.world,
                        slo_p99_ms=self.cfg.slo_p99_ms, failed=failed,
                        scheduler=scheduler)
        out["boundaries"] = self._boundary
        out["programs"] = sorted(f"{p}.b{b}" for p, b in self._programs)
        out["drained"] = self.drained
        out["world"] = self.world
        return out
