"""True multi-process execution tests.

The reference's whole suite runs under ``mpirun -np 2 pytest`` with an
explicit warning that single-process runs "cannot test a large part" of the
library (ref docs/developers.rst:15-27).  The TPU-native analog launches
N OS processes that rendezvous through ``mpi4jax_tpu.init_distributed``
(``jax.distributed.initialize`` under the hood — the ``mpirun`` replacement,
SURVEY.md §2.7) on localhost, each owning a slice of a virtual-CPU device
"pod", and runs collectives + the shallow-water model over the
process-spanning mesh.

This is the only place ``init_distributed`` executes for real: the rest of
the suite is single-process/8-virtual-devices.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

# true multi-controller runs take ~15+ min: slow tier (pyproject addopts)
pytestmark = pytest.mark.slow

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Everything the workers run.  Process-spanning assertions check only this
# process's addressable shards (a host fetch of the full global array is not
# legal in multi-controller JAX).
WORKER = textwrap.dedent(
    """
    import os, sys
    proc_id = int(sys.argv[1])
    nprocs = int(sys.argv[2])
    port = sys.argv[3]
    local_devices = int(sys.argv[4])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={local_devices}"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    repo = sys.argv[5]
    sys.path.insert(0, repo)
    sys.path.insert(0, os.path.join(repo, "examples"))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import mpi4jax_tpu as mpx

    # the mpirun replacement: explicit coordinator on CPU clusters
    mpx.init_distributed(
        coordinator_address=f"localhost:{port}",
        num_processes=nprocs,
        process_id=proc_id,
    )
    # idempotent (second call is a no-op, not an error)
    mpx.init_distributed()
    assert jax.process_count() == nprocs, jax.process_count()
    size = nprocs * local_devices
    assert jax.device_count() == size, jax.device_count()

    import numpy as np
    import jax.numpy as jnp

    # --- 1. allreduce over the process-spanning world mesh ---------------
    @mpx.spmd
    def f(x):
        res, _ = mpx.allreduce(x, op=mpx.SUM)
        return res

    x = jnp.zeros((size, 3)) + jnp.arange(float(size))[:, None]
    out = f(x)
    want = size * (size - 1) / 2
    for s in out.addressable_shards:
        assert np.all(np.asarray(s.data) == want), (proc_id, s.index)

    # --- 2. sendrecv ring across the process boundary --------------------
    @mpx.spmd
    def ring(x):
        res, _ = mpx.sendrecv(x, x, dest=mpx.shift(1))
        return res

    r = ring(jnp.arange(float(size)))
    for s in r.addressable_shards:
        rank = s.index[0].start
        got = np.asarray(s.data)[0]
        assert got == (rank - 1) % size, (rank, got)

    # --- 3. shallow-water multistep over a process-spanning 2-D mesh ------
    from shallow_water import (
        Config, initial_state, make_mesh_and_comm, make_stepper,
    )

    nproc_y = 2 if size % 2 == 0 else 1
    cfg = Config(
        nproc_y=nproc_y, nproc_x=size // nproc_y,
        nx=4 * (size // nproc_y), ny=8 * nproc_y,
    )
    mesh, comm = make_mesh_and_comm(cfg)
    # fast="auto" selects the shipped multi-rank mode
    # (model_step_pallas_halo; on this CPU worker its interpret fallback —
    # same math, no Pallas machinery) with the sendrecv halo exchanges
    # crossing real process boundaries
    first, multi = make_stepper(cfg, comm, fast="auto")
    state = multi(first(initial_state(cfg)), 3)
    for s in state.h.addressable_shards:
        block = np.asarray(s.data)
        assert np.isfinite(block).all(), (proc_id, s.index)
        assert 50 < block.mean() < 150  # height near resting depth

    # --- 3b. unequal color split across real process boundaries -----------
    if size >= 3:
        uneq = mpx.get_default_comm().Split([0, 0] + [1] * (size - 2))
        xs = jnp.arange(float(size))[:, None]
        sc, _ = mpx.scan(xs, mpx.SUM, comm=uneq)
        rg, _ = mpx.sendrecv(xs, xs, dest=mpx.shift(1), comm=uneq)
        for arr, expect in ((sc, "scan"), (rg, "ring")):
            for s in arr.addressable_shards:
                r = s.index[0].start
                got = float(np.asarray(s.data)[0, 0])
                g = next(grp for grp in uneq.groups if r in grp)
                i = g.index(r)
                want = (float(sum(g[: i + 1])) if expect == "scan"
                        else float(g[(i - 1) % len(g)]))
                assert got == want, (proc_id, expect, r, got, want)

    # --- 4. wide-halo carried frame across real process boundaries --------
    # 16-cell local interiors: "auto" ships the communication-avoiding
    # wide path, whose margin-band sendrecvs here cross processes
    cfg_w = Config(
        nproc_y=nproc_y, nproc_x=size // nproc_y,
        nx=16 * (size // nproc_y), ny=16 * nproc_y,
    )
    _, comm_w = make_mesh_and_comm(cfg_w)
    from shallow_water import model_step_wide, select_steps
    assert select_steps("auto", cfg_w)[0] is model_step_wide
    first_w, multi_w = make_stepper(cfg_w, comm_w, fast="auto")
    state_w = multi_w(first_w(initial_state(cfg_w)), 3)
    for s in state_w.h.addressable_shards:
        block = np.asarray(s.data)
        assert np.isfinite(block).all(), (proc_id, s.index)
        assert 50 < block.mean() < 150

    print(f"MULTIPROC_OK {proc_id}", flush=True)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch(nprocs: int, local_devices: int, timeout: int = 420):
    """Launch ``nprocs`` worker processes and wait for all of them."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    port = str(_free_port())
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", WORKER,
                str(i), str(nprocs), port, str(local_devices), REPO_ROOT,
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        # one worker crashed → the others hang in the collective until the
        # timeout.  Kill everyone and collect whatever each wrote, so the
        # crashed worker's traceback reaches the assertion message.
        for p in procs:
            if p.poll() is None:
                p.kill()
        while len(outs) < len(procs):
            out, _ = procs[len(outs)].communicate()
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


@pytest.mark.parametrize(
    "nprocs,local_devices", [(2, 4), (4, 2)],
    ids=["2procs-x4dev", "4procs-x2dev"],
)
def test_multiprocess_collectives_and_shallow_water(nprocs, local_devices):
    procs, outs = _launch(nprocs, local_devices)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-4000:]}"
        assert f"MULTIPROC_OK {i}" in out
