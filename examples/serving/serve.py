"""Continuous-batching serving: the deployment, benchmark, and drain drill.

The serving runtime end to end (docs/serving.md): a tensor-parallel
transformer decode loop served by the iteration-level batching scheduler
— per-(bucket, phase) programs pinned through ``mpx.compile``, decode as
a device-resident megastep, admission/eviction at megastep boundaries,
KV slots scatter-managed so churn never retraces.  Three modes:

- **benchmark** (default): serve one synthetic Poisson trace with the
  CONTINUOUS scheduler and again with the STATIC batch baseline, and
  write both numbers — tokens/s/chip at the p99 latency bound — to
  ``--out`` (the ``BENCH_serving.json`` schema)::

      python examples/serving/serve.py --scheduler both --json \\
          --out BENCH_serving.json

- **simulate** (``--simulate``): the same trace through the same
  scheduler on the cost-model replay (serving/sim.py) — no devices
  touched; the capture path for containers without an accelerator;

- **drain drill** (``--launch N``): a CPU-world drill — N worker
  processes, each forced onto one virtual CPU device
  (``JAX_PLATFORMS=cpu``), so it never needs an accelerator and never
  competes for one (a chip belongs to one process; the launching parent
  stays off jax).  The workers serve one trace
  in lockstep (virtual clock); at ``--drain-boundary`` the drained rank
  posts its preemption notice (the same ``request_drain`` path a
  SIGTERM or the ``preempt`` fault verb feeds), the world executes the
  planned shrink at the next megastep boundary, survivors re-shard the
  committed parameters, RE-ADMIT every in-flight sequence from its
  committed token history, and finish the trace with ZERO failed
  requests — exactly one ``drain`` incident per journal
  (the PR 9 drill routed through the serving loop)::

      MPI4JAX_TPU_TELEMETRY=events MPI4JAX_TPU_TELEMETRY_DIR=/tmp/srv \\
          python examples/serving/serve.py --launch 3 --drain-rank 2
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

DONE_TAG = "SERVING_DONE"
DRAINED_TAG = "SERVING_DRAINED"

# model presets: "tiny" traces/compiles in seconds on the CI CPU mesh —
# and matches the ServingConfig dataclass defaults EXACTLY, so programs
# warmed from `aot warm --emit-manifest` (which reads those defaults)
# hit the same cache keys a tiny serve run asks for; "bench" is the
# serving-number workload (realistic weight traffic)
PRESETS = {
    "tiny": dict(heads=24, head_dim=4, ffn=384, max_len=48, max_prompt=16),
    "bench": dict(heads=24, head_dim=64, ffn=6144, max_len=160,
                  max_prompt=16),
}


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", choices=sorted(PRESETS), default="tiny")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--rate", type=float, default=50.0,
                   help="Poisson arrival rate (requests/s)")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--long-frac", type=float, default=0.25,
                   help="fraction of requests drawing the heavy-tail "
                        "generation budget")
    p.add_argument("--unroll", type=int, default=0,
                   help="decode megastep trip count (0 = the "
                        "MPI4JAX_TPU_SERVING_UNROLL default)")
    p.add_argument("--max-batch", type=int, default=0,
                   help="0 = the MPI4JAX_TPU_SERVING_MAX_BATCH default")
    p.add_argument("--slo-ms", type=float, default=0.0,
                   help="p99 latency bound (0 = the "
                        "MPI4JAX_TPU_SERVING_SLO_P99_MS default)")
    p.add_argument("--scheduler", choices=("continuous", "static", "both"),
                   default="both")
    p.add_argument("--simulate", action="store_true",
                   help="cost-model replay instead of real devices")
    p.add_argument("--virtual-clock", action="store_true",
                   help="advance arrivals one tick per megastep boundary "
                        "(deterministic across ranks; implied by --launch)")
    p.add_argument("--json", action="store_true",
                   help="print ONLY the JSON payload")
    p.add_argument("--out", default="",
                   help="write the BENCH_serving.json payload here")
    # drain drill plumbing
    p.add_argument("--launch", type=int, default=0, metavar="N",
                   help="launch an N-process drill world (a CPU-world "
                        "drill: every worker is forced onto one virtual "
                        "CPU device)")
    p.add_argument("--drain-rank", type=int, default=-1,
                   help="drill: rank that receives the preemption notice "
                        "(-1 = last)")
    p.add_argument("--drain-boundary", type=int, default=4,
                   help="drill: megastep boundary at which the notice "
                        "lands")
    p.add_argument("--process-id", type=int, default=-1,
                   help=argparse.SUPPRESS)
    p.add_argument("--num-processes", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--port-base", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--drill-timeout", type=float, default=540.0,
                   help=argparse.SUPPRESS)
    return p.parse_args(argv)


def _config(args, mpx_serving):
    overrides = dict(PRESETS[args.model], seed=args.seed)
    if args.unroll:
        overrides["unroll"] = args.unroll
    if args.max_batch:
        overrides["max_batch"] = args.max_batch
    if args.slo_ms:
        overrides["slo_p99_ms"] = args.slo_ms
    if args.virtual_clock or args.launch or args.process_id >= 0:
        overrides["clock"] = "virtual"
    return mpx_serving.ServingConfig.from_env(**overrides)


def _trace(args, cfg, mpx_serving):
    # budgets scale with the model's KV row so every preset saturates
    # its lanes: short answers for most requests, a heavy tail of long
    # ones — the regime where static batching idles lanes
    short_hi = max(4, (cfg.max_len - cfg.max_prompt) // 8)
    long_hi = cfg.max_len - cfg.max_prompt - cfg.unroll - 1
    trace = mpx_serving.poisson_trace(
        args.requests, args.rate, seed=args.seed,
        prompt_len=(2, min(6, cfg.max_prompt)),
        max_new=(4, short_hi),
        long_frac=args.long_frac,
        long_new=(max(short_hi + 1, 3 * long_hi // 4), long_hi),
        vocab=cfg.vocab,
    )
    meta = {
        "requests": args.requests, "rate_rps": args.rate,
        "seed": args.seed, "long_frac": args.long_frac,
        "span_s": round(trace[-1].arrival_s, 4),
        "tokens_budgeted": sum(r.max_new_tokens for r in trace),
    }
    return trace, meta


def _emit(args, payload):
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    print(json.dumps(payload) if args.json
          else json.dumps(payload, indent=2))


def run_simulate(args):
    from mpi4jax_tpu import serving
    from mpi4jax_tpu.serving import sim

    cfg = _config(args, serving)
    trace, meta = _trace(args, cfg, serving)
    import jax

    k = jax.device_count()
    cfg.validate_world(k)
    payload, _, _ = sim.replay_bench(cfg, trace, k=k, trace_meta=meta)
    _emit(args, payload)


def run_benchmark(args):
    import jax

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import serving
    from mpi4jax_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    cfg = _config(args, serving)
    trace, meta = _trace(args, cfg, serving)
    comm = mpx.get_default_comm()
    k = comm.world_size()
    device = jax.devices()[0]

    results = {}
    warm_s = {}
    schedulers = (("continuous", "static") if args.scheduler == "both"
                  else (args.scheduler,))
    for sched in schedulers:
        engine = serving.ServingEngine(cfg, comm)
        # compile every (bucket, phase) program before the first request:
        # a latency percentile must not contain a compile
        warm_s[sched] = round(engine.warm(), 3)
        results[sched] = engine.run(trace, scheduler=sched)
        if not args.json:
            r = results[sched]
            print(f"{sched:>10}: {r['tokens_per_s_per_chip']} tok/s/chip, "
                  f"p99 {r['p99_ms']} ms (slo {r['slo_p99_ms']} ms, "
                  f"met={r['slo_met']}), {r['completed']} completed / "
                  f"{r['failed']} failed over {r['boundaries']} "
                  "boundaries", file=sys.stderr)

    cont = results.get("continuous") or results[args.scheduler]
    payload = serving.bench_payload(
        workload=cfg.workload_meta(k), trace_meta=meta, chips=k,
        continuous=cont, static=results.get("static"),
        environment=(f"measured: {k}-device {device.platform} mesh, "
                     f"{device.device_kind} (examples/serving/serve.py)"),
    )
    from mpi4jax_tpu.aot import stats as aot_stats

    payload["compile_cache"] = aot_stats()
    payload["warm_compile_s"] = warm_s
    _emit(args, payload)


# ---------------------------------------------------------------------------
# the drain drill: --launch parent + worker halves
# ---------------------------------------------------------------------------


def run_worker(args):
    import jax

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import serving
    from mpi4jax_tpu.parallel import megastep

    mpx.init_distributed(
        coordinator_address=f"localhost:{args.port_base}",
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    assert jax.device_count() == args.num_processes

    cfg = _config(args, serving)
    trace, _ = _trace(args, cfg, serving)
    mesh = mpx.make_world_mesh()
    comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)
    store = mpx.ShardStore(comm, bootstrap={
        "host": "localhost",
        "port_base": args.port_base,
        "process_id": args.process_id,
        "num_processes": args.num_processes,
        "agree_port_base": args.port_base + 100,
    })
    engine = serving.ServingEngine(cfg, comm, store=store)

    drain_rank = (args.drain_rank if args.drain_rank >= 0
                  else args.num_processes - 1)

    posted = []

    def preemption_notice(step, **info):
        # the preemption notice lands ONCE, at the first boundary past
        # --drain-boundary with sequences IN FLIGHT (deterministic and
        # identical on every rank: the scheduler state is replicated),
        # so the drill always exercises the re-admission path.  Same
        # request_drain path a SIGTERM (BoundaryControl installs the
        # handler) or the `preempt` fault verb feeds.
        eng = info.get("engine")
        if (not posted and step >= args.drain_boundary
                and args.process_id == drain_rank
                and eng is not None and eng._sched.running):
            posted.append(step)
            mpx.request_drain()

    unregister = megastep.register_boundary_hook("drill-preempt",
                                                 preemption_notice)
    try:
        result = engine.run(trace, scheduler="continuous")
    finally:
        unregister()

    tag = DRAINED_TAG if engine.drained else DONE_TAG
    print(f"{tag} world={result['world']} completed={result['completed']} "
          f"failed={result['failed']} "
          f"readmissions={result['preempt_readmissions']}", flush=True)
    assert result["failed"] == 0, result
    if not engine.drained:
        assert result["completed"] == len(trace), result
        assert result["world"] == args.num_processes - 1, result
        assert result["preempt_readmissions"] > 0, (
            "the drain boundary should have re-admitted in-flight "
            f"sequences: {result}")


def run_launcher(args):
    """Spawn the drill world; success = every worker exits 0, exactly
    one prints the drained tag, and every survivor reports the full
    trace completed with zero failures."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port_base = s.getsockname()[1]

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    n = args.launch

    def spawn(i):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--process-id", str(i), "--num-processes", str(n),
               "--port-base", str(port_base),
               "--model", args.model,
               "--requests", str(args.requests),
               "--rate", str(args.rate), "--seed", str(args.seed),
               "--long-frac", str(args.long_frac),
               "--drain-rank", str(args.drain_rank),
               "--drain-boundary", str(args.drain_boundary)]
        if args.unroll:
            cmd += ["--unroll", str(args.unroll)]
        if args.max_batch:
            cmd += ["--max-batch", str(args.max_batch)]
        return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    workers = [spawn(i) for i in range(n)]
    deadline = time.monotonic() + args.drill_timeout
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in workers):
            break
        time.sleep(0.5)
    else:
        for p in workers:
            p.kill()
        print("drill timeout", file=sys.stderr)
        return 1

    drained = done = failures = 0
    for i, p in enumerate(workers):
        out = p.stdout.read()
        sys.stderr.write(f"--- worker {i} (rc={p.returncode}) ---\n{out}\n")
        if p.returncode != 0:
            failures += 1
        if DRAINED_TAG in out:
            drained += 1
        if DONE_TAG in out:
            done += 1
    ok = failures == 0 and drained == 1 and done == n - 1
    print(f"drill: {done} survivor(s) done, {drained} drained, "
          f"{failures} failure(s) -> {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


def main():
    args = _parse_args()
    if args.launch:
        sys.exit(run_launcher(args))
    if args.process_id >= 0:
        run_worker(args)
    elif args.simulate:
        run_simulate(args)
    else:
        run_benchmark(args)


if __name__ == "__main__":
    main()
