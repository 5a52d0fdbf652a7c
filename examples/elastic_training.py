"""Elastic data-parallel training: survive rank loss and keep going.

The acceptance drill for the elastic-recovery layer
(docs/resilience.md "Elastic recovery" / "Grow and graceful drain"): a
DP-SGD loop wrapped in ``mpx.elastic.run`` with a ``ShardStore``
in-memory checkpoint.  When a rank dies (or hangs) mid-run, the
survivors agree on the failed set, revoke the communication epoch,
shrink the mesh/comm to "all minus failed", restore the last committed
state from the surviving shard replicas, and finish the step budget on
``k - f`` ranks.

Two modes:

- **single process** (default): all local devices form the world; a
  simulated :class:`RankFailure` fires at ``--fail-step`` and the mesh
  shrinks in place —

      python examples/elastic_training.py

- **multi-process drill** (``--launch N``): a CPU-world drill — N worker
  processes over ``jax.distributed``, each forced onto one virtual CPU
  device (``JAX_PLATFORMS=cpu``), so it never needs an accelerator and
  never competes for one (a chip belongs to one process; the launching
  parent stays off jax); kill one with the fault
  injector and the survivors re-bootstrap a smaller world —

      MPI4JAX_TPU_FAULT_SPEC='die:rank=3:op=allreduce:after=5' \\
        python examples/elastic_training.py --launch 4 --steps 12

  The parent exits 0 iff a surviving majority completed the full step
  budget.  Swap ``die`` for ``hang`` to drill the watchdog-expiry
  detection path (the loop claims the expiry handler while it runs).

Elastic extensions (this file is also their CI drill):

- ``--grow``: after the fault injector kills a rank, the launcher
  spawns a REPLACEMENT process (``mpx.elastic.join_and_run``) that
  contacts the shrunken world's coordinator, is admitted at a commit
  boundary, receives the committed state through the cold-join restore,
  and helps finish the budget at the original world size — the 4→3→4
  loop.  Requires ``MPI4JAX_TPU_ELASTIC_GROW=1`` in the environment.
- ``--grid RxC``: run on a Cartesian (R, C) mesh.  Combined with a
  ``preempt`` fault clause and ``MPI4JAX_TPU_ELASTIC_FAIL_UNIT=row``,
  this is the graceful-preemption drill: the preempted rank's whole
  grid row drains out at a step boundary (one forced commit, one
  ``drain`` incident, zero watchdog expiries) and the remaining rows
  finish the budget —

      MPI4JAX_TPU_ELASTIC_FAIL_UNIT=row \\
      MPI4JAX_TPU_FAULT_SPEC='preempt:rank=3:after=4' \\
        python examples/elastic_training.py --launch 4 --grid 2x2 \\
          --steps 12 --expect-world 2
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

DONE_TAG = "ELASTIC_DONE"
DRAINED_TAG = "ELASTIC_DRAINED"


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--steps", type=int, default=12,
                   help="total training steps to complete (the budget)")
    p.add_argument("--commit-every", type=int, default=1,
                   help="commit the state to the ShardStore every N steps")
    p.add_argument("--fail-step", type=int, default=5,
                   help="single-process mode: step at which the simulated "
                        "failure fires (<0 disables)")
    p.add_argument("--fail-rank", type=int, default=-1,
                   help="single-process mode: rank to fail (-1 = last)")
    p.add_argument("--out", default="",
                   help="write the per-step loss trace as JSON here")
    # multi-process drill plumbing
    p.add_argument("--launch", type=int, default=0, metavar="N",
                   help="launch an N-process world and run the drill (a "
                        "CPU-world drill: every worker is forced onto "
                        "one virtual CPU device)")
    p.add_argument("--grow", action="store_true",
                   help="--launch parent: spawn a replacement worker "
                        "(join_and_run) for each rank the fault injector "
                        "kills — the shrink-then-grow drill (needs "
                        "MPI4JAX_TPU_ELASTIC_GROW=1)")
    p.add_argument("--grid", default="",
                   help="Cartesian mesh shape 'RxC' (default: 1-D world)")
    p.add_argument("--expect-world", type=int, default=0,
                   help="--launch parent: expected FINAL world size "
                        "(default: launch size minus fault subjects)")
    p.add_argument("--process-id", type=int, default=-1,
                   help=argparse.SUPPRESS)  # worker-internal
    p.add_argument("--num-processes", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--port-base", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--join", action="store_true",
                   help=argparse.SUPPRESS)  # replacement-worker-internal
    p.add_argument("--watchdog", type=float, default=30.0,
                   help="multi-process drill: watchdog timeout in seconds "
                        "(the hang-drill detection bound)")
    p.add_argument("--drill-timeout", type=float, default=540.0,
                   help="--launch parent: seconds before the drill fails")
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# the model + elastic step (shared by both modes)
# ---------------------------------------------------------------------------


def _init_params(dim=16, hidden=32, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w1": rng.normal(0, dim ** -0.5, (dim, hidden)).astype(np.float32),
        "b1": np.zeros((hidden,), np.float32),
        "w2": rng.normal(0, hidden ** -0.5, (hidden, 1)).astype(np.float32),
        "b2": np.zeros((1,), np.float32),
    }


def _data_for(k, per_rank=32, dim=16, seed=1):
    """Synthetic regression data with a leading rank axis, derived from
    the CURRENT world size — after a shrink the survivors re-derive it
    at k-f (every process computes the same arrays: same seed)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(k, per_rank, dim)).astype(np.float32)
    w = rng.normal(size=(dim, 1)).astype(np.float32)
    y = np.tanh(x @ w).astype(np.float32)
    return x, y


def _make_elastic_step(mpx, lr=0.05, store=None):
    """``step_fn(state, step, comm)`` for ``mpx.elastic.run``: builds (and
    caches) one SPMD program per comm — after a shrink the new comm gets a
    fresh program traced at the new size (the epoch in the cache key
    guarantees the old one is unreachable anyway).

    The gradient exchange is ``mpx.compress.ef_allreduce`` with the
    error-feedback residual COMMITTED as part of the state (one row per
    rank): with ``MPI4JAX_TPU_COMPRESS=off`` it is the plain allreduce
    and the residual stays zero; under bf16/fp8 a restore replays the
    residual from the last commit, a shrink moves surviving rows to
    their new ranks (``store.last_rank_map`` -> ``ef_reshard``), and a
    cold joiner's row starts ZERO — never a dead rank's stale error
    (docs/compression.md "Error feedback under elasticity")."""
    import jax
    import jax.numpy as jnp

    programs = {}

    def train_step_for(comm):
        key = (comm.uid, comm.epoch)
        if key not in programs:
            size = comm.Get_size()

            @mpx.spmd(comm=comm)
            def train_step(params, residual, x, y):
                def loss_fn(p, x, y):
                    h = jax.nn.relu(x @ p["w1"] + p["b1"])
                    pred = h @ p["w2"] + p["b2"]
                    return jnp.mean((pred - y) ** 2)

                loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
                red, residual, token = mpx.compress.ef_allreduce(
                    grads, residual, op=mpx.SUM, comm=comm)
                loss = mpx.allreduce(loss, op=mpx.SUM, comm=comm,
                                     token=token)[0] / size
                new = jax.tree.map(lambda p, g: p - lr * (g / size),
                                   params, red)
                return mpx.varying((new, residual, loss))

            programs[key] = train_step
        return programs[key]

    def replicate(tree, k):
        return jax.tree.map(
            lambda v: jnp.tile(jnp.asarray(v)[None], (k,) + (1,) * v.ndim),
            tree)

    def residual_for(state, params_g, k):
        res = state.get("ef_residual")
        if res is None:
            return mpx.compress.ef_zeros_like(params_g)
        old_k = int(np.shape(jax.tree.leaves(res)[0])[0])
        if old_k == k:
            return res
        # a restore across a boundary: the committed residual's rows
        # belong to the OLD world — move survivors, zero joiners
        rmap = store.last_rank_map if store is not None else None
        if rmap is None:
            rmap = {r: r for r in range(min(old_k, k))}
        return mpx.compress.ef_reshard(res, rmap, k)

    losses = []

    def step_fn(state, step, comm):
        k = comm.Get_size()
        x, y = _data_for(k)
        params_g = replicate(state["params"], k)
        res = residual_for(state, params_g, k)
        params_g, res, loss = train_step_for(comm)(params_g, res, x, y)
        loss = float(np.asarray(loss)[0])
        losses.append({"step": step, "world": k, "loss": loss,
                       "epoch": comm.epoch})
        print(f"step {step:3d}  world {k}  epoch {comm.epoch}  "
              f"loss {loss:.6f}", flush=True)
        # params stay single-copy (replicated invariant: every rank's row
        # is identical, row 0 is the canonical copy the ShardStore
        # shards); the residual is genuinely per-rank, so its full
        # (k, ...) stack is the committed artifact
        return {"params": jax.tree.map(lambda v: np.asarray(v[0]), params_g),
                "ef_residual": jax.tree.map(np.asarray, res)}

    return step_fn, losses


# ---------------------------------------------------------------------------
# single-process mode: simulated failure, in-place mesh shrink
# ---------------------------------------------------------------------------


def run_single(args):
    import mpi4jax_tpu as mpx

    mesh = mpx.make_world_mesh()
    comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)
    k = comm.Get_size()
    fail_rank = args.fail_rank if args.fail_rank >= 0 else k - 1
    fail_at = args.fail_step if 0 <= args.fail_step < args.steps else None
    if fail_at is not None and k < 2:
        print("single device: nothing to shrink, running clean")
        fail_at = None

    store = mpx.ShardStore(comm)
    base_step, losses = _make_elastic_step(mpx, store=store)

    def step_fn(state, step, comm):
        state = base_step(state, step, comm)
        if fail_at is not None and step == fail_at and comm.epoch == 0:
            # simulate rank loss AFTER the step's work (a real death
            # surfaces as an error/expiry inside the next collective; the
            # recovery path from here on is identical)
            raise mpx.RankFailure({fail_rank},
                                  f"simulated loss of rank {fail_rank}")
        return state

    state = {"params": _init_params()}
    state = mpx.elastic.run(step_fn, state, store, steps=args.steps,
                            commit_every=args.commit_every)

    final_world = store.comm.Get_size()
    expect_world = k - 1 if fail_at is not None else k
    assert final_world == expect_world, (final_world, expect_world)
    assert len([r for r in losses if r["step"] == args.steps - 1]) == 1
    if fail_at is not None:
        from mpi4jax_tpu.resilience import elastic as el

        assert el.current_epoch() == 1, el.current_epoch()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"losses": losses, "final_world": final_world}, f,
                      indent=2)
    print(f"{DONE_TAG} steps={args.steps} world={final_world}", flush=True)
    return state


# ---------------------------------------------------------------------------
# multi-process drill: --launch parent + worker halves
# ---------------------------------------------------------------------------


def _parse_grid(spec):
    if not spec:
        return None
    r, _, c = spec.lower().partition("x")
    return int(r), int(c)


def _make_mesh_comm(mpx, grid):
    if grid is None:
        mesh = mpx.make_world_mesh()
    else:
        mesh = mpx.make_world_mesh(grid, ("y", "x"))
    comm = mpx.Comm(tuple(mesh.axis_names), mesh=mesh)
    return mesh, comm


def _finish_worker(args, store, losses, pid):
    final_world = int(store.comm.Get_size())
    if args.out:
        with open(f"{args.out}.p{pid}", "w") as f:
            json.dump({"losses": losses, "final_world": final_world,
                       "drained": bool(store.drained)}, f, indent=2)
    if store.drained:
        # shrunk out by a planned drain (the preempted rank, or a
        # row-mate on a Cartesian drain): a graceful exit, not a
        # completion — the survivors own the rest of the budget
        print(f"{DRAINED_TAG} world={final_world}", flush=True)
    else:
        print(f"{DONE_TAG} steps={args.steps} world={final_world}",
              flush=True)


def run_worker(args):
    import jax

    import mpi4jax_tpu as mpx

    mpx.init_distributed(
        coordinator_address=f"localhost:{args.port_base}",
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    assert jax.device_count() == args.num_processes

    if args.watchdog > 0:
        mpx.set_watchdog_timeout(args.watchdog)

    _, comm = _make_mesh_comm(mpx, _parse_grid(args.grid))
    store = mpx.ShardStore(comm, bootstrap={
        "host": "localhost",
        "port_base": args.port_base,
        "process_id": args.process_id,
        "num_processes": args.num_processes,
        "agree_port_base": args.port_base + 100,
    })
    step_fn, losses = _make_elastic_step(mpx, store=store)

    state = {"params": _init_params()}
    state = mpx.elastic.run(step_fn, state, store, steps=args.steps,
                            commit_every=args.commit_every)
    _finish_worker(args, store, losses, args.process_id)


def run_joiner(args):
    """A replacement worker: contact the running (shrunken) world's
    coordinator, get admitted at a commit boundary, receive the
    committed state through the cold-join restore, and help finish the
    budget (docs/resilience.md "Grow and graceful drain")."""
    import mpi4jax_tpu as mpx

    if args.watchdog > 0:
        mpx.set_watchdog_timeout(args.watchdog)

    store = mpx.ShardStore(None, bootstrap={
        "host": "localhost",
        "port_base": args.port_base,
        "agree_port_base": args.port_base + 100,
    })
    step_fn, losses = _make_elastic_step(mpx, store=store)
    mpx.elastic.join_and_run(step_fn, store, steps=args.steps,
                             commit_every=args.commit_every,
                             join_timeout=args.drill_timeout)
    _finish_worker(args, store, losses,
                   f"j{store.bootstrap['process_id']}")


def run_launcher(args):
    """Spawn the N-process world, reap survivors, judge the drill.

    Success = the expected number of workers (``--expect-world``, or a
    strict MAJORITY by default) exit 0 with the completion tag and the
    full step budget, and every OTHER exit-0 worker was gracefully
    drained (the ``preempt`` drill's leavers print the drained tag).
    Workers killed by the fault injector (``die`` exits 13) or hung
    forever (``hang``, killed here once the survivors finish) are the
    drill's subjects, not failures of it.  With ``--grow``, each killed
    worker is replaced by a joiner (``join_and_run``) that must ALSO
    complete — the shrink-then-grow loop.
    """
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port_base = s.getsockname()[1]

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    if args.grow:
        env["MPI4JAX_TPU_ELASTIC_GROW"] = "1"
    n = args.launch

    def common_flags():
        cmd = ["--steps", str(args.steps),
               "--commit-every", str(args.commit_every),
               "--port-base", str(port_base),
               "--watchdog", str(args.watchdog),
               "--drill-timeout", str(args.drill_timeout)]
        if args.grid:
            cmd += ["--grid", args.grid]
        if args.out:
            cmd += ["--out", args.out]
        return cmd

    def spawn(extra, name):
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)] + common_flags()
            + extra,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        proc._drill_name = name
        return proc

    workers = [
        spawn(["--process-id", str(i), "--num-processes", str(n)], f"r{i}")
        for i in range(n)
    ]
    # a joiner cannot start with its replacement target still alive (the
    # fault has not fired yet): spawned on first observed subject death
    spawned = 0
    target = args.expect_world if args.expect_world > 0 else n // 2 + 1

    deadline = time.monotonic() + args.drill_timeout
    while time.monotonic() < deadline:
        subjects = [p for p in workers
                    if p.poll() is not None and p.returncode != 0]
        if args.grow and len(subjects) > spawned:
            for _ in range(len(subjects) - spawned):
                workers.append(spawn(["--join"], f"j{spawned}"))
                spawned += 1
        live = [p for p in workers if p.poll() is None]
        done_ok = [p for p in workers
                   if p.poll() is not None and p.returncode == 0]
        if not live:
            break
        if len(done_ok) >= target:
            # the expected completions are in; whoever is still running
            # is the drill's hung subject — give stragglers a grace
            # period, then put them down
            grace = time.monotonic() + 20.0
            while any(p.poll() is None for p in workers) \
                    and time.monotonic() < grace:
                time.sleep(0.2)
            for p in workers:
                if p.poll() is None:
                    p.kill()
            break
        time.sleep(0.5)
    outputs = {}
    for p in workers:
        name = p._drill_name
        try:
            out, _ = p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outputs[name] = (p.returncode, out or "")
        sys.stdout.write(f"--- worker {name} (exit {p.returncode}) ---\n")
        sys.stdout.write(outputs[name][1])
    winners = [nm for nm, (rc, _) in outputs.items() if rc == 0]
    completed = [nm for nm in winners
                 if f"{DONE_TAG} steps={args.steps}" in outputs[nm][1]]
    drained = [nm for nm in winners if DRAINED_TAG in outputs[nm][1]]
    print(f"drill: {len(completed)} worker(s) completed the "
          f"{args.steps}-step budget ({completed}), {len(drained)} "
          f"drained gracefully ({drained})", flush=True)
    ok = len(completed) >= target
    # every exit-0 worker must be accounted for: a completion or a
    # graceful drain — an exit-0 worker with neither tag went wrong
    ok = ok and sorted(winners) == sorted(set(completed) | set(drained))
    if args.expect_world > 0:
        ok = ok and len(completed) == args.expect_world
    if ok:
        print("DRILL_OK", flush=True)
        return 0
    print("DRILL_FAILED", flush=True)
    return 1


def main(argv=None):
    args = _parse_args(argv)
    if args.launch > 0:
        return run_launcher(args)
    if args.join:
        run_joiner(args)
        return 0
    if args.process_id >= 0:
        run_worker(args)
        return 0
    run_single(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
