"""Comm structure, eager mode, dtypes, validation, config, capability probes.

Ports ref tests/test_validation.py, test_decorators.py (env parsing),
test_has_cuda.py / test_has_sycl.py (probes), and the comm-handling parts of
test_common.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as mpx
from mpi4jax_tpu.utils.config import parse_env_bool
from helpers import ranks_arange, world


def test_comm_size_rank():
    comm, size = world()
    assert comm.Get_size() == jax.device_count()

    @mpx.spmd
    def f(x):
        r = mpx.get_default_comm().Get_rank()
        return x * 0 + r

    out = np.asarray(f(ranks_arange((1,))))[:, 0]
    assert np.allclose(out, np.arange(size))


def test_comm_clone_distinct_uid():
    comm, _ = world()
    clone = comm.Clone()
    assert clone.uid != comm.uid
    assert clone.axes == comm.axes
    assert clone.Get_size() == comm.Get_size()


def test_comm_2d_mesh_sub():
    mesh = mpx.make_world_mesh((4, 2), ("y", "x"))
    comm = mpx.Comm(("y", "x"), mesh=mesh)
    assert comm.Get_size() == 8

    @mpx.spmd(comm=comm)
    def f(xl):
        row = mpx.get_default_comm().sub("x")
        col = mpx.get_default_comm().sub("y")
        rs, _ = mpx.allreduce(xl, op=mpx.SUM, comm=row)
        cs, _ = mpx.allreduce(xl, op=mpx.SUM, comm=col)
        return rs, cs

    x = jnp.arange(8.0)[:, None]
    rs, cs = f(x)
    rs, cs = np.asarray(rs)[:, 0], np.asarray(cs)[:, 0]
    # row-major (y,x): linear rank r has y=r//2, x=r%2
    assert np.allclose(rs, [1, 1, 5, 5, 9, 9, 13, 13])  # sums over x
    assert np.allclose(cs, [12, 16, 12, 16, 12, 16, 12, 16])  # sums over y


def test_shard_global_places_one_row_per_rank():
    """Host arrays go straight to the layout region programs take —
    ``global[r]`` on rank r's device, also on a 2-axis comm — and a
    program fed the placed array returns what the unplaced one does."""
    mesh = mpx.make_world_mesh((4, 2), ("y", "x"))
    comm = mpx.Comm(("y", "x"), mesh=mesh)
    host = {"a": np.arange(16.0, dtype=np.float32).reshape(8, 2),
            "b": np.ones((8, 3, 2), np.float32)}
    placed = mpx.shard_global(host, comm)
    devices = list(mesh.devices.flat)  # row-major = rank order
    for name, arr in placed.items():
        assert len(arr.sharding.device_set) == 8
        assert np.array_equal(np.asarray(arr), host[name])
        for shard in arr.addressable_shards:
            r = devices.index(shard.device)
            assert np.array_equal(np.asarray(shard.data), host[name][r:r + 1])

    @mpx.spmd(comm=comm)
    def f(xl):
        return mpx.allreduce(xl, op=mpx.SUM)[0]

    assert np.array_equal(np.asarray(f(placed["a"])),
                          np.asarray(f(jnp.asarray(host["a"]))))


def test_comm_multi_axis_allreduce():
    mesh = mpx.make_world_mesh((4, 2), ("y", "x"))
    comm = mpx.Comm(("y", "x"), mesh=mesh)

    @mpx.spmd(comm=comm)
    def f(xl):
        s, _ = mpx.allreduce(xl, op=mpx.SUM)
        return s

    out = np.asarray(f(jnp.arange(8.0)[:, None]))
    assert np.allclose(out, 28.0)


def test_comm_rank_row_major():
    mesh = mpx.make_world_mesh((4, 2), ("y", "x"))
    comm = mpx.Comm(("y", "x"), mesh=mesh)

    @mpx.spmd(comm=comm)
    def f(xl):
        return xl * 0 + comm.Get_rank()

    out = np.asarray(f(jnp.zeros((8, 1))))[:, 0]
    assert np.allclose(out, np.arange(8))


def test_p2p_on_multi_axis_comm():
    """p2p over a multi-axis comm rides the linearized row-major rank
    order: shift(1) on a (4, 2) comm is one ring over all 8 devices
    (before round 5 this raised 'requires a single-axis communicator')."""
    mesh = mpx.make_world_mesh((4, 2), ("y", "x"))
    comm = mpx.Comm(("y", "x"), mesh=mesh)

    @mpx.spmd(comm=comm)
    def f(xl):
        y, _ = mpx.sendrecv(xl, xl, dest=mpx.shift(1))
        return y

    out = np.asarray(f(jnp.arange(8.0)[:, None])).ravel()
    np.testing.assert_array_equal(out, np.roll(np.arange(8.0), 1))


def test_unbound_comm_error():
    comm = mpx.Comm("nonexistent_axis")
    with pytest.raises(RuntimeError, match="not bound"):
        comm.Get_size()


def test_eager_wrong_leading_axis():
    with pytest.raises(ValueError, match="leading rank axis"):
        mpx.allreduce(jnp.zeros((3, 2)))


def test_eager_token_roundtrip():
    x = ranks_arange((2,))
    res, token = mpx.allreduce(x)
    res2, token2 = mpx.allreduce(x, token=token)
    assert np.allclose(np.asarray(res), np.asarray(res2))


def test_unsupported_dtype():
    # f64 works on CPU; check the rejection path with a genuinely
    # unsupported width via a numpy structured view is overkill — use
    # float128 if the platform has it
    if not hasattr(np, "float128"):
        pytest.skip("platform lacks float128")
    x = np.zeros((8, 2), dtype=np.float128)
    with pytest.raises((TypeError, ValueError)):
        @mpx.spmd
        def f(xl):
            return mpx.allreduce(xl)[0]

        f(x)


def test_parse_env_bool(monkeypatch):
    # ref tests/test_decorators.py truthy-env parsing.  Reads go through
    # the declared-flag registry (utils/config.py FLAGS), so the probe
    # flag is declared for the duration of the test.
    from mpi4jax_tpu.utils import config as _config

    monkeypatch.setitem(
        _config.FLAGS, "MPI4JAX_TPU_TESTFLAG",
        _config.Flag("MPI4JAX_TPU_TESTFLAG", "bool", False, "test probe"),
    )
    for v in ("1", "true", "ON", "yes"):
        monkeypatch.setenv("MPI4JAX_TPU_TESTFLAG", v)
        assert parse_env_bool("MPI4JAX_TPU_TESTFLAG") is True
    for v in ("0", "false", "OFF", "no", ""):
        monkeypatch.setenv("MPI4JAX_TPU_TESTFLAG", v)
        assert parse_env_bool("MPI4JAX_TPU_TESTFLAG") is False
    monkeypatch.setenv("MPI4JAX_TPU_TESTFLAG", "maybe")
    with pytest.raises(ValueError, match="could not be parsed"):
        parse_env_bool("MPI4JAX_TPU_TESTFLAG")
    monkeypatch.delenv("MPI4JAX_TPU_TESTFLAG")
    assert parse_env_bool("MPI4JAX_TPU_TESTFLAG", True) is True


def test_undeclared_flag_read_raises(monkeypatch):
    # the registry is the single read point: undeclared MPI4JAX_TPU_*
    # reads fail loudly (and are a lint failure — tests/test_lint.py)
    with pytest.raises(RuntimeError, match="not declared"):
        parse_env_bool("MPI4JAX_TPU_NOT_A_FLAG")


def test_capability_probes():
    # ref tests/test_has_cuda.py / test_has_sycl.py
    assert mpx.has_cuda_support() in (True, False)
    assert mpx.has_tpu_support() in (True, False)
    assert mpx.has_sycl_support() is False
    # CPU test backend: no cuda/tpu
    assert not mpx.has_cuda_support()


def test_public_api_surface():
    # the reference's 12 ops + probes (ref mpi4jax/__init__.py:26-41) must
    # all be importable from the top level
    for name in [
        "allgather", "allreduce", "alltoall", "barrier", "bcast", "gather",
        "recv", "reduce", "scan", "scatter", "send", "sendrecv",
        "has_cuda_support", "has_sycl_support", "has_tpu_support",
    ]:
        assert hasattr(mpx, name), name


def test_debug_logging_format(capfd):
    # ref tests/collective_ops/test_common.py:118-144 — debug log format
    # r{rank} | {8 hex} | MPI_X asserted on captured output
    import re

    from mpi4jax_tpu.utils import debug

    debug.set_logging(True)
    try:
        @mpx.spmd
        def f(x):
            res, _ = mpx.allreduce(x, op=mpx.SUM)
            return res

        out = f(ranks_arange((1,)))
        out.block_until_ready()
        jax.effects_barrier()
    finally:
        debug.set_logging(False)
    captured = capfd.readouterr()
    text = captured.out + captured.err
    assert re.search(r"r\d+ \| [0-9a-f]{8} \| MPI_Allreduce", text), text[:500]


def test_logging_toggle_busts_spmd_program_cache(capfd):
    # Regression (ADVICE r1): the spmd program cache must key on the
    # dynamically-read observability flags — enabling logging *after* a
    # wrapped function's first call must re-trace, not silently serve the
    # stale silent program.
    import re

    from mpi4jax_tpu.utils import debug

    @mpx.spmd
    def f(x):
        res, _ = mpx.allreduce(x, op=mpx.SUM)
        return res

    out = f(ranks_arange((1,)))
    out.block_until_ready()
    jax.effects_barrier()
    capfd.readouterr()  # discard pre-toggle output

    debug.set_logging(True)
    try:
        out = f(ranks_arange((1,)))
        out.block_until_ready()
        jax.effects_barrier()
    finally:
        debug.set_logging(False)
    text = capfd.readouterr()
    text = text.out + text.err
    assert re.search(r"r\d+ \| [0-9a-f]{8} \| MPI_Allreduce", text), text[:500]


def test_wallclock_fallback_without_native_lib(monkeypatch):
    # Regression (ADVICE r1): the pure-Python wallclock fallback declared a
    # float64 pure_callback result, which raises under the default
    # x64-disabled config. It must work and match the FFI path's dtype.
    from mpi4jax_tpu import native

    monkeypatch.setattr(native, "runtime_tracing_supported", lambda: False)
    t0 = jax.jit(native.wallclock)()
    t1 = jax.jit(native.wallclock)()
    expect = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    assert t0.dtype == expect
    assert float(t1) >= float(t0)

    # two reads inside ONE jit must not be deduped into a single host call
    def elapsed():
        a = native.wallclock()
        b = native.wallclock(dep=a)
        return a, b

    a, b = jax.jit(elapsed)()
    assert float(b) >= float(a)


def test_axis_bound_probe():
    # Pins the two behaviors in_parallel_region relies on (a JAX upgrade
    # that changes either must fail HERE, not silently reroute every
    # in-region op through the eager path):
    # 1. the private axis-env probe agrees with reality in and out of
    #    shard_map;
    # 2. the fallback contract — lax.axis_size raises NameError (not some
    #    other exception) for an unbound axis.
    from jax import lax

    from mpi4jax_tpu.utils.jax_compat import axis_bound

    comm, _ = world()
    axis = comm.axes[0]

    assert not axis_bound(axis)
    assert not mpx.parallel.region.in_parallel_region(comm)

    with pytest.raises(NameError, match="unbound axis"):
        lax.axis_size("definitely-not-an-axis")

    seen = {}

    @mpx.spmd
    def f(x):
        seen["inside"] = axis_bound(axis)
        seen["region"] = mpx.parallel.region.in_parallel_region(comm)
        return x

    f(ranks_arange((1,)))
    assert seen["inside"] is True
    assert seen["region"] is True
