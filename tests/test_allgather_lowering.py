"""The gather family hands a 1-D block of a multiple of 128 elements to the
AllGather HLO through its lane-shaped view ``(N // 128, 128)``
(``ops/_base.all_gather_blocks``), every other block as it is.

Pinned here: the view is pure movement (``allgather``, ``gather`` and the
colour-split ``allgather`` return bit for bit what the plain
``lax.all_gather(x, axes, axis=0, tiled=False)`` returns; ``jax.grad`` and
``jax.vmap`` go through it as through the plain form), the choice is
recorded where the op records itself, and — compiled by the TPU's compiler
for a described (not attached) v5e 2x2 — the benchmark's 1 GiB link holds
one ``all-gather`` and no ``dynamic-update-slice``, while a block the view
does not take compiles to the plain form's program.
"""

import os
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mpi4jax_tpu as mpx
from mpi4jax_tpu.ops._base import gather_view
from helpers import world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COLORS_EO = [r % 2 for r in range(8)]  # evens / odds: uniform, non-Cartesian

BLOCKS = [(128,), (1024,), (130,), (1,), (4, 6)]
VIEWS = {(128,): "lanes", (1024,): "lanes", (130,): "block", (1,): "block",
         (4, 6): "block"}


def _global(shape, dtype, size):
    """global[r]: values no two ranks or positions share (exact in
    bfloat16 too: small integers)."""
    n = int(np.prod(shape))
    vals = (np.arange(size * n) % 251).reshape(size, *shape) - 125
    return jnp.asarray(vals, dtype)


def _plain(comm, split=None):
    """The plain line, in the same kind of region; on a colour split the
    rows of this rank's group, in group order."""
    @partial(mpx.spmd, comm=comm)
    def f(x):
        full = lax.all_gather(x, comm.axes, axis=0, tiled=False)
        if split is None:
            return full
        color = jnp.asarray(COLORS_EO)[comm.Get_rank()]
        return jnp.take(full, jnp.asarray(split.groups)[color], axis=0)
    return f


def _through(form, comm):
    if form == "allgather":
        @mpx.spmd
        def f(x):
            return mpx.allgather(x)[0]
        return f, _plain(comm)
    if form == "gather":
        @mpx.spmd
        def f(x):
            return mpx.gather(x, 3)[0]
        return f, _plain(comm)
    split = comm.Split(COLORS_EO)

    @mpx.spmd
    def f(x):
        return mpx.allgather(x, comm=split)[0]
    return f, _plain(comm, split)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", BLOCKS, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("form", ["allgather", "gather", "split_allgather"])
def test_bit_for_bit_the_plain_line(form, shape, dtype):
    comm, size = world()
    got_fn, want_fn = _through(form, comm)
    x = _global(shape, dtype, size)
    got, want = got_fn(x), want_fn(x)
    k = size // 2 if form == "split_allgather" else size
    assert got.shape == (size, k, *shape) and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got).view(np.uint8),
                          np.asarray(want).view(np.uint8))


@pytest.mark.parametrize("shape", BLOCKS, ids=lambda s: "x".join(map(str, s)))
def test_eager_allgather_keeps_the_shape_contract(shape):
    _, size = world()
    x = _global(shape, jnp.float32, size)
    res, _ = mpx.allgather(x)
    assert res.shape == (size, size, *shape)
    for r in (0, size - 1):
        assert np.array_equal(np.asarray(res[r]), np.asarray(x))


@pytest.mark.parametrize("n", [128, 130], ids=["lanes", "block"])
def test_grad_equals_the_plain_forms(n):
    comm, size = world()
    w = jnp.asarray(np.linspace(0.5, 1.5, size * n, dtype=np.float32)
                    .reshape(size, n))

    def loss(gathered):
        return jnp.sum(gathered * w * jnp.cos(gathered))

    @mpx.spmd
    def through(x):
        return jax.grad(lambda v: loss(mpx.allgather(v)[0]))(x)

    @mpx.spmd
    def plain(x):
        return jax.grad(lambda v: loss(
            lax.all_gather(v, comm.axes, axis=0, tiled=False)))(x)

    x = _global((n,), jnp.float32, size) / 64.0
    assert np.array_equal(np.asarray(through(x)), np.asarray(plain(x)))


@pytest.mark.parametrize("n", [128, 130], ids=["lanes", "block"])
def test_vmap_equals_the_plain_forms(n):
    comm, size = world()

    @mpx.spmd
    def through(x):
        return jax.vmap(lambda v: mpx.allgather(v)[0])(x)

    @mpx.spmd
    def plain(x):
        return jax.vmap(lambda v: lax.all_gather(
            v, comm.axes, axis=0, tiled=False))(x)

    x = _global((3, n), jnp.float32, size)
    got = through(x)
    assert got.shape == (size, 3, size, n)
    assert np.array_equal(np.asarray(got), np.asarray(plain(x)))


def test_token_threads_through_the_view():
    _, size = world()

    @mpx.spmd
    def f(x):
        g, tok = mpx.allgather(x)
        h, tok = mpx.allgather(g[1] + 1.0, token=tok)
        return h

    x = _global((256,), jnp.float32, size)
    want = np.broadcast_to(np.asarray(x)[1] + 1.0, (size, size, 256))
    assert np.array_equal(np.asarray(f(x)), want)


@pytest.mark.parametrize("shape", BLOCKS, ids=lambda s: "x".join(map(str, s)))
def test_view_is_chosen_from_the_block_alone(shape):
    assert gather_view(jax.ShapeDtypeStruct(shape, jnp.float32)) \
        == VIEWS[shape]
    assert gather_view(jax.ShapeDtypeStruct(shape, jnp.bfloat16)) \
        == VIEWS[shape]


def test_an_empty_block_is_left_alone():
    assert gather_view(jax.ShapeDtypeStruct((0,), jnp.float32)) == "block"


def test_view_recorded_on_the_analysis_events():
    comm, size = world()
    split = comm.Split(COLORS_EO)

    def f(x):
        a, _ = mpx.allgather(x)
        b, _ = mpx.gather(x[:130], 0)
        c, _ = mpx.allgather(x, comm=split)
        d, _ = mpx.allreduce(x, op=mpx.SUM)
        return a, b, c, d

    report = mpx.analyze(f, jnp.zeros((size, 256), jnp.float32))
    assert [(e.op, e.view) for e in report.events] == [
        ("allgather", "lanes"), ("gather", "block"), ("allgather", "lanes"),
        ("allreduce", None)]


def test_view_metered_by_telemetry():
    _, size = world()
    mpx.telemetry.reset()
    mpx.set_telemetry_mode("counters")
    try:
        mpx.allgather(jnp.zeros((size, 256)))
        mpx.allgather(jnp.zeros((size, 2, 128)))
        mpx.gather(jnp.zeros((size, 130)), 0)
        meters = mpx.telemetry.snapshot()["meters"]
    finally:
        mpx.set_telemetry_mode(None)
        mpx.telemetry.reset()
        mpx.clear_caches()
    assert meters["view.allgather.lanes"] == 1
    assert meters["view.allgather.block"] == 1
    assert meters["view.gather.block"] == 1
    assert "view.gather.lanes" not in meters


def test_view_named_in_the_debug_log(capfd):
    from mpi4jax_tpu.utils import debug

    _, size = world()
    debug.set_logging(True)
    try:
        @mpx.spmd
        def f(x):
            a, _ = mpx.allgather(x)
            b, _ = mpx.gather(x[:130], 0)
            return a, b

        jax.block_until_ready(f(jnp.zeros((size, 256), jnp.float32)))
        jax.effects_barrier()
    finally:
        debug.set_logging(False)
    captured = capfd.readouterr()
    text = captured.out + captured.err
    assert re.search(r"MPI_Allgather: sending 256 items \(lanes view\)", text)
    assert re.search(r"MPI_Gather: sending 130 items to root 0 "
                     r"\(block view\)", text)


# ---------------------------------------------------------------------------
# compiled for a described v5e 2x2 (no chip): what the view buys
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def world_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = mpx.make_world_mesh(devices=topo.devices)
    comm = mpx.Comm(mesh.axis_names, mesh=mesh)
    return comm, NamedSharding(mesh, PartitionSpec(mesh.axis_names))


def _sweep_link(op):
    """The loop body the benchmark's collective cell chains, and its
    traffic's programs of that op by name."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from chipbench import harness
    from chipbench.reference import collectives as coll_ref

    bench = os.path.join(REPO, "chipbench")
    driver = harness.load_module(os.path.join(bench, "drivers",
                                              "collectives.py"))
    traffic = harness.load_json(os.path.join(bench, "traffic",
                                             "sweep_world.json"))
    coef = jnp.asarray(coll_ref.coefficients(4), jnp.float32)
    specs = {p["name"]: p for p in traffic["programs"] if p["op"] == op}
    return driver._link(op, 4, coll_ref.GROWTH, coef), coef, specs


def _compiled_text(comm, sharding, body, n, links, dtype=jnp.float32):
    """The chained program at block length ``n``, as the TPU's compiler
    leaves it, less what names source lines."""
    @partial(mpx.spmd, comm=comm)
    def chained(x):
        return lax.fori_loop(0, links, body, x)

    x = jax.ShapeDtypeStruct((4, n), dtype, sharding=sharding)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = mpx.compile(chained, x)._call
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", compiled.as_text())
    lines = [ln for ln in text.splitlines()
             if not re.match(r"^(FileNames|FunctionNames|FileLocations|"
                             r"StackFrames|\d+ )", ln)]
    return "\n".join(lines), compiled.memory_analysis()


def _count(text, opcode):
    return len(re.findall(rf" {re.escape(opcode)}\(", text))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_large_link_is_one_all_gather_and_no_rebuild(world_2x2, dtype):
    """``large_allgather_1GiB``: the gather lands in its result, the
    link's multiply and sum read it there, and the loop holds no
    ``dynamic-update-slice``, ``dynamic-slice`` or ``reshape``.  In
    bfloat16 (256 MiB blocks again: twice the elements) the loop is the
    same; that program's exit, not its links, relays the ``(1, N)`` result
    once (``T(2,128)(2,1)``), which the loop's line below leaves out."""
    comm, sharding = world_2x2
    body, coef, specs = _sweep_link("allgather")
    spec = specs["large_allgather_1GiB"]
    n = spec["block_elems"] * 4 // jnp.dtype(dtype).itemsize
    if dtype == jnp.bfloat16:
        coef16 = coef.astype(dtype)

        def body(_, v):
            g, _tok = mpx.allgather(v)
            return mpx.varying(jnp.sum(g * coef16[:, None], axis=0))
    text, mem = _compiled_text(comm, sharding, body, n, spec["chain"], dtype)
    assert _count(text, "all-gather") == 1
    loop = text[text.index("all-gather("):]
    loop = loop[:loop.index("\n}")]
    for opcode in ("dynamic-update-slice", "dynamic-slice", "reshape",
                   "copy", "transpose"):
        assert _count(loop, opcode) == 0, opcode
    assert "multiply_reduce_fusion" in loop
    if dtype == jnp.float32:
        assert _count(text, "dynamic-update-slice") == 0
        # the gathered GiB and nothing beside it (the plain line: 2.5 x)
        assert mem.temp_size_in_bytes < 1.01 * 4 * 4 * n


@pytest.mark.parametrize("name", ["small_allgather_4B", "block_130"])
def test_a_block_the_view_does_not_take_compiles_to_the_plain_program(
        world_2x2, name):
    comm, sharding = world_2x2
    body, coef, specs = _sweep_link("allgather")
    n, links = ((specs[name]["block_elems"], specs[name]["chain"])
                if name in specs else (130, 8))

    def plain(_, v):
        g = lax.all_gather(v, comm.axes, axis=0, tiled=False)
        return mpx.varying(jnp.sum(g * coef[:, None], axis=0))

    got, _ = _compiled_text(comm, sharding, body, n, links)
    want, _ = _compiled_text(comm, sharding, plain, n, links)
    assert got == want


def test_small_4KiB_link_takes_the_view(world_2x2):
    comm, sharding = world_2x2
    body, _, specs = _sweep_link("allgather")
    spec = specs["small_allgather_4KiB"]
    text, _ = _compiled_text(comm, sharding, body, spec["block_elems"],
                             spec["chain"])
    assert _count(text, "all-gather") == 1
    assert _count(text, "reshape") == 0
    assert _count(text, "dynamic-update-slice") == 0
