"""Opt-in chip lane: the Mosaic-COMPILED Pallas kernels and the TPU
lowering of the op surface.

The normal suite runs on the forced 8-device virtual CPU mesh, where
every Pallas path takes its interpret/jnp form — identical arithmetic,
but the compiled kernels themselves (Mosaic lowering, VMEM blocking,
SMEM scalar operands, in-kernel rolls) are never built.  This file is
the chip-side complement, the analog of the reference suite's second
execution mode (``mpirun -np N pytest``, ref docs/developers.rst:15-27 —
same tests, realer substrate).  It runs on a machine with a TPU, through
the chip tool, on one chip or on four:

    MPI4JAX_TPU_TEST_PLATFORM=ambient python -m pytest \
        tests/test_tpu_compiled.py -q

With the env var set, conftest.py keeps the process's own backend (the
TPU) instead of forcing CPU; without it — i.e. in the normal suite —
every test here skips.  Run it against this file only: the rest of the
suite assumes 8 devices.

One process holds the chip: no test here starts a second process that
needs it, and none writes a record file.

Which devices each test means is said in the test.  The kernel tests
build a ONE-device mesh on the first chip (they check a kernel against
the jnp step, not how work spreads);
``test_butterfly_rounds_on_multi_device_chip`` uses every chip the host
has.  ``chip_smoke.py`` is the check that work is spread over all chips.

Each test compares a compiled kernel path against the fast jnp step on
the SAME chip, so the assertion bounds are the fusion-order rounding
bands established by the interpret-mode equality tests, not looser
device tolerances.  Grids are kept small (a few kernel blocks) so the
whole lane is a handful of compiles.
"""

import os
import sys

import numpy as np
import pytest

import jax

_AMBIENT = os.environ.get("MPI4JAX_TPU_TEST_PLATFORM") == "ambient"
if _AMBIENT and jax.default_backend() != "tpu":
    # the operator explicitly asked for the chip lane: a silent all-skip
    # green run would hide that jax found no TPU — fail loudly instead
    raise RuntimeError(
        "MPI4JAX_TPU_TEST_PLATFORM=ambient is set but the backend is "
        f"'{jax.default_backend()}', not 'tpu' — jax found no TPU in this "
        "process; run the lane on a machine with a chip"
    )

pytestmark = pytest.mark.skipif(
    not _AMBIENT,
    reason="real-TPU lane (MPI4JAX_TPU_TEST_PLATFORM=ambient)",
)

_REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path[:0] = [_REPO, os.path.join(_REPO, "examples")]  # bench, examples

_RUNS = {}  # (cfg, fast, steps) -> State; Config is frozen/hashable


def _run(cfg, fast, steps):
    """Stepper runs on a ONE-device mesh (the first chip), cached: the
    fast-step baseline for the periodic config is shared by two tests,
    and each make_stepper costs a fresh XLA compile on chip."""
    key = (cfg, fast, steps)
    if key not in _RUNS:
        from shallow_water import (
            initial_state, make_mesh_and_comm, make_stepper,
        )

        _, comm = make_mesh_and_comm(cfg, devices=jax.devices()[:1])
        first, multi = make_stepper(cfg, comm, fast=fast)
        _RUNS[key] = multi(first(initial_state(cfg, comm)), steps)
    return _RUNS[key]


def _assert_fields_close(a, b, what):
    for name, x, y in zip(a._fields, a, b):
        x, y = np.asarray(x), np.asarray(y)
        bound = 5e-6 + 1e-6 * np.abs(x).max()
        assert np.abs(x - y).max() <= bound, (
            f"{what}: field {name} diverged on chip: "
            f"{np.abs(x - y).max():.3e} > {bound:.3e}"
        )


def test_whole_step_pair_kernel_compiled():
    """The fused whole-step pair kernel, Mosaic-compiled (multi-block
    grid: ny_local = 2 x _PBLK), by name: the benchmark's path until PR 38
    gave one periodic chip the wide-halo kernel wherever it fits."""
    from shallow_water import Config, model_step_wide, select_steps

    cfg = Config(nproc_y=1, nproc_x=1, nx=512, ny=254)
    assert select_steps("auto", cfg)[0] is model_step_wide
    _assert_fields_close(
        _run(cfg, "pallas2", 7), _run(cfg, True, 7), "pallas2"
    )


def test_wide_halo_kernel_compiled():
    """The multi-rank path's kernels (wide masks, SMEM offsets, carried
    frame with margin refresh), compiled on a one-device mesh — walls
    config, which 'auto' routes to the wide path.  (The same kernel with
    real halo permutes between chips is chip_smoke.py stage B on a
    four-chip host.)"""
    from shallow_water import Config, model_step_wide, select_steps

    cfg = Config(nproc_y=1, nproc_x=1, nx=512, ny=254, periodic_x=False)
    assert select_steps("auto", cfg)[0] is model_step_wide
    _assert_fields_close(_run(cfg, "auto", 7), _run(cfg, True, 7), "wide")


def test_wide_halo_kernel_compiled_periodic():
    """Wide path on a periodic config, what ``auto`` gives it (the
    benchmark's path since PR 38): the wrap self-exchanges are elided to
    identity routings; the compiled kernel must agree with the specialist
    whole-step kernel's physics."""
    from shallow_water import Config

    cfg = Config(nproc_y=1, nproc_x=1, nx=512, ny=254)
    _assert_fields_close(
        _run(cfg, "wide2", 7), _run(cfg, True, 7), "wide-periodic"
    )


def test_flash_attention_kernel_compiled():
    """The flash block kernel (masked, unmasked, and causal tile-skipping
    forms) vs the jnp reference path, on chip."""
    import jax.numpy as jnp

    from mpi4jax_tpu.kernels.flash_attention import flash_block_partials

    b, t, h, d = 2, 1024, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.float32) for kk in ks)
    scale = 1.0 / np.sqrt(d)

    tril = jnp.tril(jnp.ones((t, t), bool))
    for kernel_kwargs, jnp_kwargs in (
        (dict(mask=None), dict(mask=None)),
        (dict(mask=tril), dict(mask=tril)),
        (dict(mask=None, causal=True), dict(mask=tril)),
    ):
        o1, m1, l1 = flash_block_partials(
            q, k, v, scale=scale, **kernel_kwargs
        )
        o2, m2, l2 = flash_block_partials(
            q, k, v, scale=scale, force_jnp=True, **jnp_kwargs
        )
        # f32 dots ride the MXU's bf16-multiply default on chip, and the
        # kernel and einsum accumulate in different orders, so scores —
        # and everything downstream — agree to matmul (bf16-epsilon)
        # precision, not CPU 1-ulp: observed ~1e-3 relative on m
        np.testing.assert_allclose(
            np.asarray(m1), np.asarray(m2), rtol=2e-2, atol=2e-2
        )
        np.testing.assert_allclose(
            np.asarray(l1), np.asarray(l2), rtol=5e-2, atol=5e-2
        )
        # compare NORMALIZED attention (o / l): unnormalized partials have
        # per-row magnitudes spanning orders of magnitude, so elementwise
        # relative error is meaningless there (observed ~3e3 relative on
        # near-zero partials that are ~0.3% of their row's scale)
        def norm(o, l):
            return np.asarray(o) / np.moveaxis(
                np.maximum(np.asarray(l), 1e-6), 1, 2
            )[..., None]

        np.testing.assert_allclose(
            norm(o1, l1), norm(o2, l2), atol=2e-2
        )


def test_all_twelve_ops_on_chip():
    """The full op surface, compiled and EXECUTED on the chip, on a
    ONE-device mesh (the first chip, whatever the host has) — in-region
    (one jitted shard_map program) and eagerly (every op through the
    auto-wrapped dispatch path).  Single-device collectives degenerate to
    self-communication (the reference's 1-process mode, ref
    docs/developers.rst:15-27) but still exercise the TPU lowering +
    runtime of every op, which the CPU-mesh suite never compiles.  The
    world-comm form over every chip, value-checked per rank, is
    chip_smoke.py stage A."""
    import jax.numpy as jnp

    import mpi4jax_tpu as mpx

    mesh = mpx.make_world_mesh(devices=jax.devices()[:1])
    comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)

    @mpx.spmd(comm=comm)
    def f(x, rows):
        token = mpx.create_token()
        a, token = mpx.allreduce(x, op=mpx.SUM, comm=comm, token=token)
        p, token = mpx.allreduce(x, op=mpx.PROD, comm=comm, token=token)
        b, token = mpx.bcast(x, 0, comm=comm, token=token)
        g, token = mpx.allgather(x, comm=comm, token=token)
        s, token = mpx.scan(x, mpx.SUM, comm=comm, token=token)
        r, token = mpx.sendrecv(x, x, dest=mpx.shift(1), comm=comm,
                                token=token)
        token = mpx.send(x, dest=[(0, 0)], comm=comm, token=token)
        rcv, token = mpx.recv(x, comm=comm, token=token)
        t, token = mpx.alltoall(rows, comm=comm, token=token)
        sc, token = mpx.scatter(rows, 0, comm=comm, token=token)
        gt, token = mpx.gather(x, 0, comm=comm, token=token)
        rd, token = mpx.reduce(x, mpx.MAX, 0, comm=comm, token=token)
        token = mpx.barrier(comm=comm, token=token)
        return a, p, b, g.sum(0), s, r, rcv, t, sc, gt.sum(0), rd

    x = jnp.full((1, 4), 3.0)
    rows = jnp.arange(4.0).reshape(1, 1, 4)
    outs = f(x, rows)
    for name, v in zip("a p b g s r rcv t sc gt rd".split(), outs):
        v = np.asarray(v)
        assert np.isfinite(v).all(), name
        ref = np.asarray(rows) if name in ("t",) else (
            np.asarray(rows)[0] if name == "sc" else np.asarray(x))
        np.testing.assert_allclose(v.ravel(), ref.ravel(), err_msg=name)

    # eager path — ALL ops: global arrays with leading rank axis, each op
    # compiling its own auto-wrapped shard_map program on the chip
    xg, rg = x[None], rows[None]
    e_ar, tok = mpx.allreduce(xg, op=mpx.SUM, comm=comm)
    e_bc, tok = mpx.bcast(xg, 0, comm=comm, token=tok)
    e_ag, tok = mpx.allgather(xg, comm=comm, token=tok)
    e_sc, tok = mpx.scan(xg, mpx.SUM, comm=comm, token=tok)
    e_sr, tok = mpx.sendrecv(xg, xg, dest=mpx.shift(1), comm=comm,
                             token=tok)
    tok = mpx.send(xg, dest=[(0, 0)], comm=comm, token=tok)
    e_rc, tok = mpx.recv(xg, comm=comm, token=tok)
    e_t, tok = mpx.alltoall(rg, comm=comm, token=tok)
    e_st, tok = mpx.scatter(rg, 0, comm=comm, token=tok)
    e_gt, tok = mpx.gather(xg, 0, comm=comm, token=tok)
    e_rd, tok = mpx.reduce(xg, mpx.MAX, 0, comm=comm, token=tok)
    tok = mpx.barrier(comm=comm, token=tok)
    for name, v, ref in (
        ("allreduce", e_ar, xg), ("bcast", e_bc, xg),
        ("allgather", e_ag, xg), ("scan", e_sc, xg),
        ("sendrecv", e_sr, xg), ("recv", e_rc, xg),
        ("alltoall", e_t, rg), ("scatter", e_st, rows),
        ("gather", e_gt, xg), ("reduce", e_rd, xg),
    ):
        np.testing.assert_allclose(
            np.asarray(v).ravel(), np.asarray(ref).ravel(),
            err_msg=f"eager {name}",
        )


# COVERAGE GAP (by construction): on the 1-device mesh above, every group
# lowering's CollectivePermute machinery is dead code — kmax == 1 returns
# the input before any butterfly/doubling round is traced, so the chip lane
# compiles none of the ppermute rounds.  The rounds themselves are pinned
# at the lowered-HLO level on the 8-device CPU mesh
# (tests/test_collectives.py::test_butterfly_emits_ppermute_rounds_aot);
# the test below closes the on-chip half on a host with more than one chip
# (the four-chip host of the chip tool).


def test_butterfly_rounds_on_multi_device_chip():
    """The butterfly/doubling ppermute rounds compiled and EXECUTED on the
    WORLD mesh over every chip of the host — the coverage a one-device
    mesh cannot provide, so this test runs on the four-chip host and skips
    on one chip.  PROD allreduce takes the fold+broadcast butterfly; the
    split bcast takes the doubling broadcast."""
    import jax.numpy as jnp

    import mpi4jax_tpu as mpx

    n = jax.device_count()
    if n < 2:
        pytest.skip("needs a host with several chips (ppermute rounds are "
                    "dead code on 1 device)")

    mesh = mpx.make_world_mesh()
    comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)
    split = comm.Split([0] * n)  # one group of everyone: kmax = n

    @mpx.spmd(comm=comm)
    def butterfly(x):
        res, _ = mpx.allreduce(x, op=mpx.PROD, comm=comm)
        return res

    @mpx.spmd(comm=split)
    def doubling(x):
        res, _ = mpx.bcast(x, 1, comm=split)
        return res

    vals = mpx.shard_global(
        np.arange(1.0, n + 1, dtype=np.float32)[:, None]
        * np.ones((n, 4), np.float32), comm)
    out = butterfly(vals)
    assert len(out.sharding.device_set) == n, out.sharding
    p = np.asarray(out)
    np.testing.assert_allclose(
        p, np.prod(np.arange(1.0, n + 1)) * np.ones((n, 4)), rtol=1e-5
    )
    b = np.asarray(doubling(vals))
    np.testing.assert_allclose(b, 2.0 * np.ones((n, 4)))


def test_profile_ops_on_chip(tmp_path):
    """The per-op latency story on the TPU backend: profile_ops must
    capture a device trace of a collective-bearing program on the chip,
    on a ONE-device mesh (the CPU suite pins the same protocol; this is
    the platform the MPI4JAX_TPU_TRACE host brackets cannot cover)."""
    import glob

    import jax.numpy as jnp

    import mpi4jax_tpu as mpx

    mesh = mpx.make_world_mesh(devices=jax.devices()[:1])
    comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)

    @mpx.spmd(comm=comm)
    def step(x):
        y, _ = mpx.allreduce(x, op=mpx.SUM, comm=comm)
        return y

    x = jnp.ones((1, 512, 512))
    step(x)  # compile first
    logdir = str(tmp_path / "trace")
    with mpx.profile_ops(logdir):
        step(x)
    assert glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True), logdir


def test_flash_attention_backward_compiled():
    """jax.grad through the Pallas flash kernels — forward AND the
    blockwise backward kernels — Mosaic-compiled.  This was the round-4
    gap: grad through ``flash_block_partials`` raised ``Linearization
    failed`` on the chip, so the "differentiable" claim held only on the
    CPU/jnp fallback.  Gradient equality is against the jnp path's grads
    computed on the SAME chip (shared MXU bf16-multiply default)."""
    import jax.numpy as jnp

    from mpi4jax_tpu.kernels.flash_attention import flash_block_partials

    b, t, h, d = 2, 1024, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.float32) for kk in ks)
    scale = 1.0 / np.sqrt(d)

    def loss(q, k, v, causal, **kwargs):
        o, _, l = flash_block_partials(
            q, k, v, None, scale=scale, causal=causal, **kwargs
        )
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = o / jnp.moveaxis(l_safe, 1, 2)[..., None]
        return (out**2).sum()

    for causal in (False, True):
        g_k = jax.jit(jax.grad(
            lambda *a: loss(*a, causal), (0, 1, 2)
        ))(q, k, v)
        g_j = jax.jit(jax.grad(
            lambda *a: loss(*a, causal, force_jnp=True), (0, 1, 2)
        ))(q, k, v)
        for a, e, nm in zip(g_k, g_j, "qkv"):
            a, e = np.asarray(a), np.asarray(e)
            assert np.isfinite(a).all(), f"d{nm} (causal={causal}) not finite"
            # grads of a squared loss amplify the matmul (bf16-epsilon)
            # band; bound element error against the cotangent's scale
            # (observed ~6e-3 of max-grad on the causal dq at T=1024 —
            # interpret mode pins the same comparison at 1e-3 RELATIVE,
            # so this band is chip matmul precision, not kernel logic)
            bound = 1e-2 * np.abs(e).max() + 1e-3
            assert np.abs(a - e).max() <= bound, (
                f"d{nm} (causal={causal}) diverged on chip: "
                f"{np.abs(a - e).max():.3e} > {bound:.3e}"
            )


def test_flash_backward_small_shapes_all_inputs_compiled():
    """Grads wrt q AND k/v at small T and small head dims, Mosaic-compiled.

    Regression: the dk/dv kernel used to dynamic-slice m/g_l on the LANE
    dim at qj*bq offsets, which Mosaic can only prove 128-aligned when bq
    (= min(Tq, 512)) is a multiple of 128 — so any transformer-block
    training step with a T_local that wasn't failed to compile on TPU,
    and nothing caught it because every earlier chip test took grads wrt
    q only (the dk/dv kernel was dead code there).  m/g_l now enter that
    kernel transposed (query positions on the sublane dim, 8-aligned)."""
    import jax.numpy as jnp

    from mpi4jax_tpu.kernels.flash_attention import flash_block_partials

    for (t, d) in ((32, 8), (200, 32), (1024, 128)):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q, k, v = (
            jax.random.normal(kk, (1, t, 2, d), jnp.float32) for kk in ks
        )
        for causal in (False, True):
            g = jax.jit(jax.grad(
                lambda q, k, v: flash_block_partials(
                    q, k, v, None, scale=0.2, causal=causal
                )[0].sum(),
                (0, 1, 2),
            ))(q, k, v)
            for a, nm in zip(g, "qkv"):
                assert np.isfinite(np.asarray(a)).all(), (t, d, causal, nm)


def test_ring_and_ulysses_grad_compiled():
    """ring/ulysses grads compile and run on a ONE-device mesh on chip.

    Scope: size=1 means the ring has no sendrecv rotation and the Ulysses
    all-to-alls are no-ops — what this exercises is the custom-VJP kernel
    path (Pallas fwd + causal bwd kernels) *inside shard_map under grad*
    on the chip, value-checked against reference attention grads on the
    same chip.  The multi-rank collective-transpose half of the grad path
    is pinned by the CPU-mesh suite (tests/test_long_context.py) and the
    driver's dryrun; on chips it has not run."""
    import jax.numpy as jnp

    import mpi4jax_tpu as mpx
    from long_context_attention import (
        reference_attention, ring_attention, ulysses_attention,
    )

    mesh = mpx.make_world_mesh(devices=jax.devices()[:1])
    comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)
    b, t, h, d = 1, 512, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (
        jax.random.normal(kk, (1, b, t, h, d), jnp.float32) for kk in ks
    )
    g_ref = jax.jit(jax.grad(
        lambda q: (reference_attention(q, k[0], v[0], causal=True) ** 2).sum()
    ))(q[0])

    for scheme in (ring_attention, ulysses_attention):

        @mpx.spmd(comm=comm)
        def f(q, k, v, scheme=scheme):
            out = scheme(q, k, v, comm=comm, causal=True)
            return mpx.varying(jnp.sum(out**2))

        g = np.asarray(jax.grad(lambda q: jnp.sum(f(q, k, v)))(q))[0]
        assert np.isfinite(g).all(), scheme.__name__
        e = np.asarray(g_ref)
        bound = 1e-2 * np.abs(e).max() + 1e-3
        assert np.abs(g - e).max() <= bound, (
            f"{scheme.__name__} dq diverged on chip: "
            f"{np.abs(g - e).max():.3e} > {bound:.3e}"
        )
