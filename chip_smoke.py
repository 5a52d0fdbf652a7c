"""Does the system still start on the chip?  The standing check.

    python chip_smoke.py              # on the chip (through the chip tool)
    python chip_smoke.py --rehearse   # same stages, tiny sizes, any platform

One process, every chip it finds, the entry points a user runs, full widths,
a few steps.  Three stages, all strict — a stage that raises, a check that
fails or a fallback taken makes the exit code non-zero:

- **A — the op surface**: all 13 ops through ``mpx.spmd`` and eagerly on the
  world comm, value-checked; with more than one device also the butterfly,
  ring, ``shift(1)``, alltoall and ``Comm.Split`` paths, with the compiled
  HLO searched for the collective ops and every input and output checked to
  be laid out over all devices.
- **B — the flagship**: ``examples/shallow_water.py`` at benchmark width
  (3600 x 1800) through ``solve_fused(fast="auto", pinned=True)``, the
  region the benchmark times: the Mosaic-compiled Pallas kernel (``wide2``,
  the wide-halo kernel on the carried widened frame, which ``auto`` gives
  every domain of this size: one device or the ``(2, 2)`` of four), the
  pinned artifact, the final state against the plain ``jnp`` step on the
  same devices.  On one device the closed basin (``periodic_x=False``)
  follows at the same size — the same kernel, its x bands zeros where the
  periodic domain's are slices of its own frame — and either domain runs
  once as that pinned region, once through the host loop ``solve()`` runs
  (``run_multisteps`` over ``make_stepper``'s two un-pinned programs, a
  call a multistep, the frame carried from call to call).
- **C — a server that answers a few requests**: ``mpx.serving.ServingEngine``
  with the ``bench`` preset of ``examples/serving/serve.py``, tensor-parallel
  over all devices, a dozen requests, continuous scheduler; every program
  pinned before the first request, none compiled inside the loop, and the
  prefill logits against an unsharded ``jnp`` forward of the same weights
  (``reference_prefill_logits`` below, which shares no code with the model).

Without ``--rehearse`` a platform other than ``tpu`` is a failure: the script
prints what jax found and exits 2.  It never sets ``JAX_PLATFORMS`` or
``XLA_FLAGS``.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failing run prints no such line.

Wall times and compile shares printed here are smoke observations on the
named device — information for the reader, never a metric.
"""

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

import jax

REPO = os.path.dirname(os.path.abspath(__file__))
for _p in (REPO, os.path.join(REPO, "examples"),
           os.path.join(REPO, "examples", "serving")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def check(cond, what):
    """A smoke check: raises (and so fails the stage) when ``cond`` is
    false.  Not ``assert`` — the checks must survive ``python -O``."""
    if not cond:
        raise AssertionError(what)


def close(got, want, what):
    """Stage A's value check: small exact numbers, so f32 rounding only."""
    got = np.asarray(got)
    check(got.shape == want.shape,
          f"{what}: shape {got.shape} != {want.shape}")
    np.testing.assert_allclose(got, want, rtol=1e-6, err_msg=what)


def spread_over(x, n, what):
    """``x`` must be laid out over all ``n`` devices, one row each — a run
    that quietly kept everything on the first chip fails here."""
    check(len(x.sharding.device_set) == n,
          f"{what}: on {len(x.sharding.device_set)} device(s), not {n}")
    shards = x.addressable_shards
    check(len(shards) == n, f"{what}: {len(shards)} shards, not {n}")
    check(all(s.data.shape[0] == 1 for s in shards),
          f"{what}: shard shapes {[s.data.shape for s in shards]}")


# ---------------------------------------------------------------------------
# compile accounting (jax's own monitoring events)
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",  # cache reads included
)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileMeter:
    """Seconds jax spent tracing, lowering and compiling (or reading its
    persistent cache), and how often that cache hit and missed."""

    def __init__(self):
        self.compile_s = 0.0
        self.events = {_CACHE_HIT: 0, _CACHE_MISS: 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def _event(self, event, **_):
        if event in self.events:
            self.events[event] += 1

    def snapshot(self):
        return (self.compile_s, self.events[_CACHE_HIT],
                self.events[_CACHE_MISS])


# ---------------------------------------------------------------------------
# stage A — the op surface
# ---------------------------------------------------------------------------


def stage_a(ctx):
    import mpi4jax_tpu as mpx
    from mpi4jax_tpu.ops import _algos
    from mpi4jax_tpu.utils import config

    comm, n = ctx["comm"], ctx["n"]
    ranks = np.arange(n)
    X = np.tile((ranks + 1.0)[:, None], (1, 4)).astype(np.float32)
    ROWS = np.tile((10.0 * ranks[:, None] + ranks[None, :])[..., None],
                   (1, 1, 4)).astype(np.float32)
    x, rows = mpx.shard_global((X, ROWS), comm)
    if n > 1:
        spread_over(x, n, "stage A input x")
        spread_over(rows, n, "stage A input rows")

    def all_ops(x, rows):
        token = mpx.create_token()
        a, token = mpx.allreduce(x, op=mpx.SUM, comm=comm, token=token)
        p, token = mpx.allreduce(x, op=mpx.PROD, comm=comm, token=token)
        b, token = mpx.bcast(x, 0, comm=comm, token=token)
        g, token = mpx.allgather(x, comm=comm, token=token)
        s, token = mpx.scan(x, mpx.SUM, comm=comm, token=token)
        sr, token = mpx.sendrecv(x, x, dest=mpx.shift(1), comm=comm,
                                 token=token)
        token = mpx.send(x, dest=mpx.shift(1), comm=comm, token=token)
        rc, token = mpx.recv(x, comm=comm, token=token)
        t, token = mpx.alltoall(rows, comm=comm, token=token)
        sc, token = mpx.scatter(rows, 0, comm=comm, token=token)
        gt, token = mpx.gather(x, 0, comm=comm, token=token)
        rd, token = mpx.reduce(x, mpx.MAX, 0, comm=comm, token=token)
        rs, token = mpx.reduce_scatter(rows, mpx.SUM, comm=comm,
                                       token=token)
        token = mpx.barrier(comm=comm, token=token)
        return a, p, b, g, s, sr, rc, t, sc, gt, rd, rs

    shifted = np.roll(X, 1, axis=0)          # rank r holds rank r-1's row
    rooted_max = X.copy()
    rooted_max[0] = X.max()
    want = {
        "allreduce": np.full_like(X, X[:, 0].sum()),
        "allreduce_prod": np.full_like(X, float(math.factorial(n))),
        "bcast": np.tile(X[:1], (n, 1)),
        "allgather": np.tile(X[None], (n, 1, 1)),
        "scan": np.cumsum(X, axis=0),
        "sendrecv": shifted,
        "recv": shifted,
        "alltoall": ROWS.transpose(1, 0, 2),
        "scatter": ROWS[0],
        "gather": np.tile(X[None], (n, 1, 1)),
        "reduce": rooted_max,
        "reduce_scatter": ROWS.sum(axis=0),
    }

    # in-region: one jitted shard_map program
    outs = mpx.spmd(all_ops, comm=comm)(x, rows)
    for name, got in zip(want, outs):
        close(got, want[name], f"spmd {name}")
        if n > 1:
            spread_over(got, n, f"spmd {name} output")

    # eagerly: every op compiles its own auto-wrapped program
    tok = None
    eager = {}
    eager["allreduce"], tok = mpx.allreduce(x, op=mpx.SUM, comm=comm)
    eager["allreduce_prod"], tok = mpx.allreduce(x, op=mpx.PROD, comm=comm,
                                                 token=tok)
    eager["bcast"], tok = mpx.bcast(x, 0, comm=comm, token=tok)
    eager["allgather"], tok = mpx.allgather(x, comm=comm, token=tok)
    eager["scan"], tok = mpx.scan(x, mpx.SUM, comm=comm, token=tok)
    eager["sendrecv"], tok = mpx.sendrecv(x, x, dest=mpx.shift(1),
                                          comm=comm, token=tok)
    tok = mpx.send(x, dest=mpx.shift(1), comm=comm, token=tok)
    eager["recv"], tok = mpx.recv(x, comm=comm, token=tok)
    eager["alltoall"], tok = mpx.alltoall(rows, comm=comm, token=tok)
    eager["scatter"], tok = mpx.scatter(rows, 0, comm=comm, token=tok)
    eager["gather"], tok = mpx.gather(x, 0, comm=comm, token=tok)
    eager["reduce"], tok = mpx.reduce(x, mpx.MAX, 0, comm=comm, token=tok)
    eager["reduce_scatter"], tok = mpx.reduce_scatter(rows, mpx.SUM,
                                                      comm=comm, token=tok)
    tok = mpx.barrier(comm=comm, token=tok)
    for name, got in eager.items():
        close(got, want[name], f"eager {name}")
        if n > 1:
            spread_over(got, n, f"eager {name} output")
    mpx.flush()

    # 12 value-checked results (allreduce twice) + send + barrier
    info = {"ops": 13, "hlo": "not searched on one device"}
    if n == 1:
        return info

    # more than one device: the lowerings that are dead code on one
    def hlo_of(fn, *args):
        # the optimized HLO of the program spmd would run
        return jax.jit(mpx.spmd(fn, comm=comm, jit=False)).lower(
            *args).compile().as_text()

    def sum_world(v):
        return mpx.allreduce(v, op=mpx.SUM, comm=comm)[0]

    def prod_world(v):
        return mpx.allreduce(v, op=mpx.PROD, comm=comm)[0]

    def ring_shift(v):
        return mpx.sendrecv(v, v, dest=mpx.shift(1), comm=comm)[0]

    def transpose(r):
        return mpx.alltoall(r, comm=comm)[0]

    for fn, arg, op in ((sum_world, x, "all-reduce"),
                        (prod_world, x, "collective-permute"),
                        (ring_shift, x, "collective-permute"),
                        (transpose, rows, "all-to-all")):
        check(op in hlo_of(fn, arg),
              f"compiled {fn.__name__} holds no {op}")

    # a SUM allreduce above the ring crossover on a one-group color split
    # (the world comm's SUM is the native all-reduce): the ring on groups
    # of RING_MIN_GROUP or more, the butterfly below
    everyone = comm.Split([0] * n)
    nelem = 2 * config.ring_crossover_bytes() // 4
    algo = _algos.resolve_algo("auto", nelem * 4, n, True)
    check(algo == ("ring" if n >= _algos.RING_MIN_GROUP else "butterfly"),
          f"{nelem * 4} B over {n} ranks resolved to {algo!r}")
    big = mpx.shard_global(
        np.tile((ranks + 1.0)[:, None], (1, nelem)).astype(np.float32),
        comm)

    def sum_group(v):
        return mpx.allreduce(v, op=mpx.SUM, comm=everyone)[0]

    check("collective-permute" in hlo_of(sum_group, big),
          f"compiled {algo} allreduce holds no collective-permute")
    got = mpx.spmd(sum_group, comm=comm)(big)
    spread_over(got, n, f"{algo} allreduce output")
    close(got, np.full((n, nelem), X[:, 0].sum(), np.float32),
          f"{algo} allreduce")

    # one real split: even and odd ranks
    halves = comm.Split([r % 2 for r in range(n)])
    got, _ = mpx.allreduce(x, op=mpx.SUM, comm=halves)
    by_parity = np.array([X[r % 2::2, 0].sum() for r in range(n)])
    close(got, np.tile(by_parity[:, None], (1, 4)), "split allreduce")
    spread_over(got, n, "split allreduce output")

    info["hlo"] = "all-reduce, collective-permute, all-to-all found"
    info["large_allreduce_algo"] = algo
    return info


# ---------------------------------------------------------------------------
# stage B — the flagship at benchmark width
# ---------------------------------------------------------------------------


def stage_b(ctx):
    """The periodic domain on every device count; on one device also the
    closed basin: ``auto`` sends both down the wide-halo path that every
    decomposed run takes, each as one pinned program, then through the
    host loop ``solve()`` runs (``run_multisteps``: a call a multistep) —
    the published benchmark's own driver on the periodic domain."""
    info = _flagship(ctx, periodic_x=True)
    if ctx["n"] == 1:
        info = {"periodic": info, "walled": _flagship(ctx, periodic_x=False),
                "walled_host_loop": _flagship(ctx, periodic_x=False,
                                              host_loop=True),
                "periodic_host_loop": _flagship(ctx, periodic_x=True,
                                                host_loop=True)}
    return info


def _flagship(ctx, periodic_x, host_loop=False):
    import mpi4jax_tpu as mpx
    import shallow_water as sw

    n = ctx["n"]
    nproc_y, nproc_x = sw.pick_process_grid(n)
    if ctx["rehearse"]:
        # the smallest interiors the wide-halo pair kernel takes
        cfg = sw.Config(nproc_y=nproc_y, nproc_x=nproc_x,
                        nx=16 * nproc_x, ny=16 * nproc_y,
                        periodic_x=periodic_x)
        multisteps, n_iters = 2, 2
    else:
        cfg = sw.Config(nproc_y=nproc_y, nproc_x=nproc_x, nx=3600, ny=1800,
                        periodic_x=periodic_x)
        multisteps, n_iters = 10, 2
    t1 = cfg.dt * (1 + multisteps * n_iters)
    n_want = 1 + multisteps * n_iters

    _, comm = sw.make_mesh_and_comm(cfg, devices=ctx["devices"])
    single, chunk, _ = sw.select_steps("auto", cfg)
    # every interior here fits the exchange depth (the rehearsal's are the
    # smallest that do), so it is the wide-halo pair on any device count
    want_kernel = sw.model_step2_wide
    check(chunk is want_kernel,
          f"'auto' chose {getattr(chunk, '__name__', chunk)} on {n} "
          f"device(s), not {want_kernel.__name__}")
    interpret = sw._resolve_interpret(comm)
    check(interpret == (ctx["platform"] != "tpu"),
          f"Pallas interpret mode is {interpret} on {ctx['platform']}")

    if host_loop:
        first_step, multistep = sw.make_stepper(cfg, comm, fast="auto")
        state = sw.initial_state(cfg, comm)
        n_steps = sw.run_plan(cfg, "auto", n_iters, multisteps)["steps"]
        # compile both programs, then the run, closed by one wait on the
        # last state
        jax.block_until_ready(multistep(first_step(state), multisteps))
        start = time.perf_counter()
        out = jax.block_until_ready(sw.run_multisteps(
            first_step, multistep, state, n_iters, multisteps))
        wall = time.perf_counter() - start
        check(n_steps == n_want, f"planned {n_steps} steps, not {n_want}")
    else:
        from mpi4jax_tpu.utils import profiling

        before, kept = mpx.cache_stats()["aot"], len(profiling.builds())
        wall, n_steps, out = sw.solve_fused(
            cfg, t1, num_multisteps=multisteps, devices=ctx["devices"],
            fast="auto", pinned=True, return_state=True)
        after = mpx.cache_stats()["aot"]
        check(n_steps == n_want, f"ran {n_steps} steps, not {n_want}")
        # the pinned artifact is what ran: one pin — compiled here, or
        # fetched from jax's persistent cache by a later process — called
        # for the warm-up and for the timed run
        delta = {k: after[k] - before[k]
                 for k in ("pins", "compiles", "calls")}
        origins = [r["attrs"].get("origin")
                   for r in profiling.builds()[kept:]
                   if r["attrs"].get("kind") == "pin"]
        check(origins in (["compiled"], ["jax_cache"])
              and delta == {"pins": 1, "calls": 2,
                            "compiles": int(origins == ["compiled"])},
              f"pinned program accounting {delta}, from {origins}")

    # the plain jnp step, same steps, same devices
    _, n_ref, ref = sw.solve_fused(
        cfg, t1, num_multisteps=multisteps, devices=ctx["devices"],
        fast=True, return_state=True)
    check(n_ref == n_steps, f"reference ran {n_ref} steps")
    # The fusion-order rounding band of tests/test_tpu_compiled.py
    # (5e-6 + 1e-6 max|x|, set there for 7 steps), grown in proportion to
    # the steps run: the kernel and the jnp step round in different orders
    # every step and the fields feed each other.  Measured at this width
    # on v5e (PR 21, PERF.md section 6): the binding field v differs by
    # 3.7e-6 after 7 steps (inside the 7-step band), 6.3e-6 after 14 and
    # 9.7e-6 after 21, 0.45-0.53e-6 a step - the same on one chip and on
    # four, where the two kernels agree with each other bit for bit.  A
    # wrong halo, mask or margin moves a field by a visible fraction of
    # itself, orders above this.
    growth = max(1.0, n_steps / 7)
    worst, bad = {}, []
    for name, got, want in zip(out._fields, out, ref):
        if n > 1:
            spread_over(got, n, f"stage B field {name}")
        got, want = np.asarray(got), np.asarray(want)
        check(np.isfinite(got).all(), f"field {name} is not finite")
        bound = growth * (5e-6 + 1e-6 * np.abs(want).max())
        err = np.abs(got - want).max()
        worst[name] = f"{err:.2e}/{bound:.2e}"
        if err > bound:
            bad.append(name)
    check(not bad, f"field(s) {bad} differ from the jnp step "
                   f"(max error/bound): {worst}")
    return {"grid": [nproc_y, nproc_x], "interior": [cfg.ny, cfg.nx],
            "periodic_x": periodic_x,
            "kernel": want_kernel.__name__, "interpret": interpret,
            "steps": n_steps, "timed_run_s": round(wall, 4),
            "max_err/bound": worst}


# ---------------------------------------------------------------------------
# stage C — a server that answers a few requests
# ---------------------------------------------------------------------------


def reference_prefill_logits(master, prompts, plens):
    """Prefill's last-position logits ``[B, V]`` from the UNSHARDED master
    weights in plain ``jnp``: no communicator, no KV pool, and nothing
    shared with ``mpi4jax_tpu/serving/model.py`` — the attention is written
    out here, head-major with ``jax.nn.softmax``, so a fault in the model's
    own attention core does not pass on both sides."""
    import jax.numpy as jnp

    emb, wqkv, wo, w1, w2 = (jnp.asarray(master[name])
                             for name in ("emb", "wqkv", "wo", "w1", "w2"))
    dim, _, heads, head_dim = wqkv.shape
    batch, pad_len = prompts.shape
    x = emb[prompts]                                         # [B, P, D]
    q, k, v = (jnp.einsum("bpd,dhe->bhpe", x, wqkv[:, i]) for i in range(3))
    scores = jnp.einsum("bhqe,bhke->bhqk", q, k) / math.sqrt(head_dim)
    pos = jnp.arange(pad_len)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhke->bqhe", jax.nn.softmax(scores, axis=-1), v)
    x = x + ctx.reshape(batch, pad_len, dim) @ wo.reshape(dim, dim)
    x = x + jnp.maximum(x @ w1, 0.0) @ w2
    return x[jnp.arange(batch), plens - 1] @ emb.T


def stage_c(ctx):
    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import serving
    from mpi4jax_tpu.serving import model
    from mpi4jax_tpu.serving.engine import PHASES
    import serve

    comm, k = ctx["comm"], ctx["n"]
    n_requests = 6 if ctx["rehearse"] else 12
    args = serve._parse_args([
        "--model", "tiny" if ctx["rehearse"] else "bench",
        "--requests", str(n_requests), "--scheduler", "continuous"])
    cfg = serve._config(args, serving)
    trace, _ = serve._trace(args, cfg, serving)

    engine = serving.ServingEngine(cfg, comm)
    check(engine.pin, "the engine did not choose pinned programs")
    if k > 1:
        for i, arr in enumerate(engine._state):
            spread_over(arr, k, f"serving state[{i}]")
    warm_s = engine.warm()
    n_programs = len(engine.table.buckets) * len(PHASES)
    warmed = mpx.cache_stats()["aot"]

    result = engine.run(trace, scheduler="continuous")
    served = mpx.cache_stats()["aot"]
    check(result["completed"] == n_requests and result["failed"] == 0,
          f"{result['completed']} completed, {result['failed']} failed "
          f"of {n_requests}")
    check(len(result["programs"]) == n_programs,
          f"programs {result['programs']}")
    # every (bucket, phase) program pinned once, before the first request
    check(served["pins"] == warmed["pins"]
          and served["compiles"] == warmed["compiles"],
          f"a program was built inside the serving loop: {warmed} -> "
          f"{served}")
    check(served["calls"] > warmed["calls"], "no pinned program was called")

    # prefill's last-position logits from the engine's own sharded
    # weights, tensor-parallel over all devices, against the unsharded jnp
    # forward of the master weights they were cut from
    rng = np.random.default_rng(cfg.seed)
    bucket = engine.table.buckets[-1]
    prompts = rng.integers(0, cfg.vocab, (bucket, cfg.max_prompt),
                           dtype=np.int32)
    plens = rng.integers(2, cfg.max_prompt + 1, (bucket,), dtype=np.int32)
    slots = np.arange(bucket, dtype=np.int32)
    lanes = mpx.shard_global(
        tuple(np.tile(a[None], (k,) + (1,) * a.ndim)
              for a in (prompts, plens, slots)), comm)
    logits = np.asarray(mpx.spmd(model.prefill_logits, comm=comm)(
        *engine._state, *lanes))
    ref = np.asarray(reference_prefill_logits(engine.master, prompts, plens))
    # Tolerance: 0.1% of the largest reference logit.  Both sides multiply
    # the same f32 values (on a TPU in the MXU's default bf16 passes) and
    # differ in the order of the contractions and in how the row-parallel
    # ones are split and summed; what that came to on the chip is in
    # PERF.md section 6.  A dropped allreduce leaves a rank with 1/k of a
    # partial sum and a wrong shard with other weights: either moves the
    # logits by their own magnitude, a thousand times the bound.
    bound = 1e-3 * np.abs(ref).max()
    errs = [float(np.abs(logits[r] - ref).max()) for r in range(k)]
    check(np.isfinite(logits).all(), "prefill logits are not finite")
    check(max(errs) <= bound,
          f"prefill logits differ from the unsharded forward: per-rank "
          f"max error {errs} > {bound:.3e}")
    return {"model": cfg.workload_meta(k)["model"], "tensor_parallel": k,
            "programs_pinned": n_programs, "warm_compile_s": round(warm_s, 2),
            "serving_wall_s": result["wall_s"],
            "completed": result["completed"], "failed": result["failed"],
            "tokens": result["tokens"],
            "logits_max_err/bound": f"{max(errs):.2e}/{bound:.2e}"}


STAGES = (("A", stage_a), ("B", stage_b), ("C", stage_c))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def main(argv=None, stages=STAGES):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--rehearse", action="store_true",
        help="run the same stages at tiny sizes on whatever platform jax "
             "finds (the unit test; debugging before spending chip time). "
             "Without it a platform other than tpu fails.")
    args = parser.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: jax {jax.__version__} platform={device['platform']} "
          f"device_kind={device['kind']} devices={device['count']}",
          flush=True)
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: FAILED - platform is {device['platform']!r}, "
              "not 'tpu' (no accelerator found)", flush=True)
        return 2

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    print(f"chip_smoke: compile cache at {cache_dir}", flush=True)
    meter = CompileMeter()
    mesh = mpx.make_world_mesh(devices=devices)
    ctx = {"devices": devices, "n": len(devices), "rehearse": args.rehearse,
           "platform": device["platform"],
           "comm": mpx.Comm(mesh.axis_names[0], mesh=mesh)}

    failed = []
    t_start = time.perf_counter()
    for name, stage in stages:
        c0, h0, m0 = meter.snapshot()
        t0 = time.perf_counter()
        try:
            info = stage(ctx)
        except Exception:  # noqa: BLE001 - reported, and fails the run
            traceback.print_exc()
            info = None
            failed.append(name)
        wall = time.perf_counter() - t0
        c1, h1, m1 = meter.snapshot()
        print(f"chip_smoke: stage {name} "
              f"{'ok' if info is not None else 'FAILED'} on "
              f"{device['count']}x {device['kind']}: wall {wall:.1f} s, "
              f"compile {c1 - c0:.1f} s ({(c1 - c0) / wall:.0%} of wall), "
              f"cache hits {h1 - h0} misses {m1 - m0}"
              + (f" | {json.dumps(info)}" if info else ""), flush=True)
    total = time.perf_counter() - t_start
    compile_s, hits, misses = meter.snapshot()
    print(f"chip_smoke: total {total:.1f} s, compile {compile_s:.1f} s "
          f"({compile_s / total:.0%} of wall), cache hits {hits} misses "
          f"{misses} (smoke observation on {device['count']}x "
          f"{device['kind']}, not a metric)", flush=True)
    if failed:
        print(f"chip_smoke: FAILED - stage(s) {', '.join(failed)}",
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
