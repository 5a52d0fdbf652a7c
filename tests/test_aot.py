"""Traced half of the AOT pinning + persistent compile cache suite
(docs/aot.md): everything that needs real traces on the 8-device
virtual CPU mesh.

- pinned == jit bit-identity for the token, notoken, and eager
  (wrap=False) paths — a pin is the SAME program, only the call path
  changes;
- buffer donation through ``donate_argnums``;
- HLO and program-cache-key byte-identity with the cache dir unset (the
  AOT layer must be invisible until asked for);
- the persistent tier: in-process re-pin served from disk, a
  second-process cold start served from disk (subprocess drill, slow),
  and the spmd program-cache consult on miss;
- staleness: config-stamp and elastic-epoch changes raise
  ``StaleProgramError`` (MPX129) — through direct calls, ``mpx.analyze``
  and the ambient error mode — and ``repin()``/``mpx.elastic.run``
  re-enter the new world (the shrink drill keeps its pinned hot path);
- MPX128 (unpinned hot loop) positive/negative through ``mpx.analyze``
  and env=error, including the being-pinned gate.

The pure half (keys, disk cache, stale state machine, MPX128 checker on
hand-built graphs) runs under any JAX in tests/test_aot_pure.py via the
isolated loader.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx
from mpi4jax_tpu.ops._base import dynamic_cache_token
from mpi4jax_tpu.resilience import elastic as el

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_aot_state():
    """Every test starts at epoch 0 with cold caches, no telemetry/
    analyze override, and no cache dir unless it sets one."""
    el._reset_epoch_for_tests()
    mpx.set_default_mesh(None)
    mpx.clear_caches()
    yield
    mpx.set_telemetry_mode(None)
    mpx.set_analyze_mode(None)
    el._reset_epoch_for_tests()
    mpx.set_default_mesh(None)
    mpx.clear_caches()
    from mpi4jax_tpu.parallel import region as _region

    _region._default_comm = None


def _world_comm():
    mesh = mpx.make_world_mesh()
    return mpx.Comm(mesh.axis_names[0], mesh=mesh)


def _reduce_step(v):
    s, _ = mpx.allreduce(v, op=mpx.SUM)
    return mpx.varying(s * 0.5)


# ---------------------------------------------------------------------------
# pinned == jit bit-identity
# ---------------------------------------------------------------------------


def test_pinned_matches_spmd_token_path():
    comm = _world_comm()
    k = comm.Get_size()

    def step(v):
        tok = mpx.create_token()
        s, tok = mpx.allreduce(v, op=mpx.SUM, token=tok)
        b, tok = mpx.bcast(mpx.varying(s), 0, token=tok)
        return mpx.varying(b + v)

    x = jnp.arange(k * 6, dtype=jnp.float32).reshape(k, 6)
    want = np.asarray(mpx.spmd(step, comm=comm)(x))
    pinned = mpx.compile(step, x, comm=comm)
    got = np.asarray(pinned(x))
    np.testing.assert_array_equal(want, got)
    assert mpx.cache_stats()["aot"]["pins"] == 1
    assert mpx.cache_stats()["aot"]["calls"] == 1


def test_pinned_matches_spmd_notoken_path(monkeypatch):
    monkeypatch.setenv("MPI4JAX_TPU_PREFER_NOTOKEN", "1")
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.full((k, 4), 2.0, jnp.float32)
    want = np.asarray(mpx.spmd(_reduce_step, comm=comm)(x))
    pinned = mpx.compile(_reduce_step, x, comm=comm)
    np.testing.assert_array_equal(want, np.asarray(pinned(x)))


def test_pinned_matches_eager_wrap_false():
    comm = _world_comm()
    k = comm.Get_size()

    def eager_fn(v):
        # global arrays, ops outside any region (the eager convention)
        s, _ = mpx.allreduce(v, op=mpx.SUM, comm=comm)
        return s + 1.0

    x = jnp.arange(k * 3, dtype=jnp.float32).reshape(k, 3)
    want = np.asarray(eager_fn(x))
    pinned = mpx.compile(eager_fn, x, comm=comm, wrap=False)
    np.testing.assert_array_equal(want, np.asarray(pinned(x)))


def test_pinned_spmd_decorated_with_static_argnums():
    comm = _world_comm()
    k = comm.Get_size()

    @mpx.spmd(comm=comm, static_argnums=(1,))
    def step(v, n):
        out = v
        for _ in range(n):
            out = mpx.varying(mpx.allreduce(out, op=mpx.SUM)[0] / k)
        return out

    x = jnp.full((k, 4), 3.0, jnp.float32)
    want = np.asarray(step(x, 2))
    # breadcrumbs adopted: comm, static_argnums — the static folds at
    # pin time and the pinned call takes only the dynamic args
    pinned = mpx.compile(step, x, 2)
    np.testing.assert_array_equal(want, np.asarray(pinned(x)))


def test_donation_is_plumbed():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 8), jnp.float32)
    pinned = mpx.compile(_reduce_step, x, comm=comm, donate_argnums=(0,))
    assert pinned.donate_argnums == (0,)
    out = np.asarray(pinned(jnp.ones((k, 8), jnp.float32)))
    np.testing.assert_array_equal(out, np.full((k, 8), k * 0.5, np.float32))
    # donating a static is a contract error
    with pytest.raises(ValueError, match="donate static"):
        mpx.compile(lambda v, n: v * n, x, 2, comm=comm,
                    static_argnums=(1,), donate_argnums=(1,))


# ---------------------------------------------------------------------------
# invisibility with the cache dir unset
# ---------------------------------------------------------------------------


def test_hlo_and_cache_keys_unchanged_by_aot(monkeypatch, tmp_path):
    """The PR-9 identity: pinning activity and the cache-dir flag must
    not move the dynamic cache token (both program-cache keys) nor the
    lowered HLO of the existing paths."""
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)

    # lower the SAME body construction both paths share
    from jax.sharding import PartitionSpec as P

    from mpi4jax_tpu.parallel.region import make_region_body

    def lower_text():
        body = make_region_body(_reduce_step, comm, (), (), (), 1,
                                squeeze_in=True, squeeze_out=True)
        sm = jax.jit(jax.shard_map(
            body, mesh=comm.mesh, in_specs=P(comm.axes[0]),
            out_specs=P(comm.axes[0])))
        return sm.lower(x).as_text()

    tok0 = dynamic_cache_token()
    base = lower_text()

    pinned = mpx.compile(_reduce_step, x, comm=comm)
    pinned(x)
    assert lower_text() == base

    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    # the env stamp moved (new raw fingerprint) so the token object is
    # rebuilt — but its VALUE must be identical: the cache-dir flag is
    # not a trace-shaping knob and must not enter program-cache keys
    assert dynamic_cache_token() == tok0
    assert lower_text() == base


# ---------------------------------------------------------------------------
# the persistent tier
# ---------------------------------------------------------------------------

def test_repin_served_from_disk(monkeypatch, tmp_path):
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.full((k, 16), 1.5, jnp.float32)

    first = mpx.compile(_reduce_step, x, comm=comm)
    assert not first.from_disk
    want = np.asarray(first(x))
    stats = mpx.cache_stats()
    assert stats["disk_cache"]["writes"] == 1
    assert stats["aot"]["compiles"] == 1

    mpx.clear_caches()  # zero the counters; artifacts stay on disk
    second = mpx.compile(_reduce_step, x, comm=comm)
    assert second.from_disk, "identical program did not load from disk"
    np.testing.assert_array_equal(want, np.asarray(second(x)))
    stats = mpx.cache_stats()
    assert stats["disk_cache"]["hits"] == 1
    assert stats["disk_cache"]["misses"] == 0, "re-lowered on a warm cache"
    assert stats["aot"]["compiles"] == 0
    assert stats["aot"]["disk_loads"] == 1


def test_spmd_program_cache_consults_disk_on_miss(monkeypatch, tmp_path):
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.full((k, 8), 2.0, jnp.float32)

    want = np.asarray(mpx.spmd(_reduce_step, comm=comm)(x))
    assert mpx.cache_stats()["disk_cache"]["writes"] >= 1
    mpx.clear_caches()

    # a FRESH decoration = a fresh program cache = a cold start in
    # miniature: the miss must deserialize, not re-lower
    got = np.asarray(mpx.spmd(_reduce_step, comm=comm)(x))
    np.testing.assert_array_equal(want, got)
    stats = mpx.cache_stats()["disk_cache"]
    assert stats["hits"] >= 1
    assert stats["misses"] == 0


@pytest.mark.slow
def test_cold_start_second_process_served_from_disk(tmp_path):
    """The multi-host cold-start contract in miniature: a SECOND process
    pinning the identical program must deserialize (hits > 0, zero
    misses — zero re-lowers)."""
    script = textwrap.dedent("""
        import json
        import jax.numpy as jnp
        import mpi4jax_tpu as mpx

        comm = mpx.get_default_comm()
        k = comm.Get_size()

        def f(v):
            return mpx.varying(mpx.allreduce(v, op=mpx.SUM)[0] * 0.5)

        x = jnp.full((k, 16), 1.5, jnp.float32)
        pinned = mpx.compile(f, x, comm=comm)
        out = pinned(x)
        assert float(out[0, 0]) == k * 1.5 * 0.5
        print(json.dumps({"from_disk": pinned.from_disk,
                          **{k2: v for k2, v in
                             mpx.cache_stats()["disk_cache"].items()
                             if k2 != "dir"}}))
    """)
    path = tmp_path / "cold_start.py"
    path.write_text(script)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8",
        MPI4JAX_TPU_COMPILE_CACHE_DIR=str(tmp_path / "cache"),
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )

    def run():
        out = subprocess.run(
            [sys.executable, str(path)], env=env, capture_output=True,
            text=True, timeout=240, check=True,
        )
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert not cold["from_disk"] and cold["writes"] >= 1, cold
    warm = run()
    assert warm["from_disk"], warm
    assert warm["hits"] >= 1 and warm["misses"] == 0, warm


# ---------------------------------------------------------------------------
# staleness: MPX129 + re-pin
# ---------------------------------------------------------------------------


def test_epoch_advance_raises_stale_and_repin_recovers():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    pinned = mpx.compile(_reduce_step, x, comm=comm)
    pinned(x)
    assert not pinned.is_stale()

    el.advance_epoch(world=k, cause="revoke", detail="test")
    assert pinned.is_stale()
    with pytest.raises(mpx.StaleProgramError) as ei:
        pinned(x)
    assert getattr(ei.value, "mpx_code", None) == "MPX129"
    assert "epoch" in str(ei.value)
    assert mpx.cache_stats()["aot"]["stale_raises"] == 1

    fresh = pinned.repin()
    out = np.asarray(fresh(x))
    np.testing.assert_array_equal(out, np.full((k, 4), k * 0.5, np.float32))


def test_config_change_raises_stale_and_repin_recovers():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    pinned = mpx.compile(_reduce_step, x, comm=comm)
    pinned(x)
    mpx.set_telemetry_mode("counters")
    try:
        with pytest.raises(mpx.StaleProgramError, match="MPX129"):
            pinned(x)
        fresh = pinned.repin()
        fresh(x)
    finally:
        mpx.set_telemetry_mode(None)
    # back at the original stamp, the ORIGINAL pin is current again
    # (same stamp == same trace); the re-pin of the counters world is
    # now the stale one
    assert not pinned.is_stale()
    assert fresh.is_stale()


def test_mpx129_through_analyze_and_env_error():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    pinned = mpx.compile(_reduce_step, x, comm=comm)

    # negative: a current pin executes clean under the ambient error mode
    mpx.set_analyze_mode("error")
    pinned(x)

    el.advance_epoch(world=k, cause="revoke", detail="test")

    # positive, env=error path: the direct call refuses with the tagged
    # error regardless of mode
    with pytest.raises(mpx.StaleProgramError, match="MPX129"):
        pinned(x)
    mpx.set_analyze_mode(None)

    # positive, analyze path: the tagged raise becomes a finding
    def caller(v):
        return pinned(v)

    report = mpx.analyze(caller, x, wrap=False)
    assert any(f.code == "MPX129" for f in report.findings), report.render()


# ---------------------------------------------------------------------------
# MPX128: the unpinned-hot-loop advisory, traced
# ---------------------------------------------------------------------------


def _hot_loop_fn(n):
    # callable reduction: never fuses (so MPX111 stays quiet and the
    # advisory under test is exactly MPX128), still counts as one
    # repeated (op, comm, statics) signature
    def fn(v):
        out = v
        for _ in range(n):
            out = mpx.varying(mpx.allreduce(out, op=jnp.maximum)[0])
        return out

    return fn


def test_mpx128_through_analyze_positive_and_negative():
    from mpi4jax_tpu.analysis.checkers import AOT_ADVISORY_MIN_REPEATS as N

    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    report = mpx.analyze(_hot_loop_fn(N), x, comm=comm)
    assert any(f.code == "MPX128" for f in report.findings), report.render()
    report = mpx.analyze(_hot_loop_fn(N - 1), x, comm=comm)
    assert not any(f.code == "MPX128" for f in report.findings)


def test_mpx128_env_error_fires_and_pinning_is_exempt():
    from mpi4jax_tpu.analysis.checkers import AOT_ADVISORY_MIN_REPEATS as N

    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    mpx.set_analyze_mode("error")
    try:
        with pytest.raises(mpx.AnalysisError, match="MPX128"):
            mpx.spmd(_hot_loop_fn(N), comm=comm)(x)
        mpx.clear_caches()
        # the SAME hot loop under the pinner is exempt (it is being
        # pinned — the advisory's advice is already taken)
        pinned = mpx.compile(_hot_loop_fn(N), x, comm=comm)
        pinned(x)
    finally:
        mpx.set_analyze_mode(None)


# ---------------------------------------------------------------------------
# the elastic re-pin drill
# ---------------------------------------------------------------------------


@pytest.mark.faults
def test_elastic_run_repins_across_shrink():
    """The acceptance drill: an elastic loop whose step is a PINNED
    program survives a shrink — the old pin refuses the new world with
    MPX129, ``mpx.elastic.run`` re-pins transparently, and the run
    finishes the full budget on 7 ranks with a second pin on record."""
    steps, fail_at = 8, 4
    comm = _world_comm()
    store = mpx.ShardStore(comm)
    worlds = []

    def base(state, step_scalar, comm):
        # per-rank step: grad-style allreduce + update (replicated state)
        g, _ = mpx.allreduce(state["p"] * 0.01, op=mpx.SUM, comm=comm)
        return {"p": mpx.varying(state["p"] - g / comm.uniform_size())}

    class Drill:
        """The user-side wrapper pattern: bookkeeping + fault injection
        around the pinned step, exposing repin() for elastic.run."""

        def __init__(self):
            self.inner = mpx.aot.compile_step(base)

        def __call__(self, state, step, comm):
            worlds.append((step, comm.Get_size()))
            if step == fail_at and comm.epoch == 0:
                raise mpx.RankFailure({3}, "simulated")
            return self.inner(state, step, comm)

        def repin(self):
            self.inner.repin()
            return self

    p0 = np.full((3, 2), 1.0, np.float32)
    final = mpx.elastic.run(Drill(), {"p": p0}, store, steps=steps)

    assert el.current_epoch() == 1
    assert store.comm.Get_size() == 7
    # the budget completed on the shrunken world
    assert sorted({s for s, w in worlds if w == 7}) == list(
        range(fail_at, steps))
    stats = mpx.cache_stats()["aot"]
    assert stats["pins"] >= 2, stats          # pre- and post-shrink pins
    assert stats["stale_raises"] >= 1, stats  # the refusal that re-pinned
    assert np.asarray(final["p"]).shape == (3, 2)


def test_compile_step_pins_once_and_raises_on_new_comm():
    comm = _world_comm()

    def base(state, step_scalar, comm):
        s, _ = mpx.allreduce(state["v"], op=mpx.SUM, comm=comm)
        return {"v": mpx.varying(s / comm.uniform_size())}

    step = mpx.aot.compile_step(base)
    s0 = {"v": np.ones((4,), np.float32)}
    s1 = step(s0, 0, comm)
    pins_after_first = mpx.cache_stats()["aot"]["pins"]
    s2 = step(s1, 1, comm)
    assert mpx.cache_stats()["aot"]["pins"] == pins_after_first  # no re-pin
    np.testing.assert_allclose(np.asarray(s2["v"]), np.ones((4,)), rtol=1e-6)

    other = _world_comm()  # a different comm identity = a moved world
    with pytest.raises(mpx.StaleProgramError, match="MPX129"):
        step(s2, 2, other)
    step.repin()
    s3 = step(s2, 2, other)
    np.testing.assert_allclose(np.asarray(s3["v"]), np.ones((4,)), rtol=1e-6)


def test_telemetry_meters_and_report_section():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    mpx.set_telemetry_mode("counters")
    try:
        pinned = mpx.compile(_reduce_step, x, comm=comm)
        pinned(x)
        pinned(x)
        snap = mpx.telemetry.snapshot()
        assert snap["meters"].get("aot.pins") == 1
        assert snap["meters"].get("aot.calls") == 2
        assert "compile_cache" in snap
        assert snap["compile_cache"]["aot"]["calls"] == 2
        text = mpx.telemetry.report(comm=comm, file=open(os.devnull, "w"))
        assert "compile cache:" in text
        assert "2 pinned call(s)" in text
    finally:
        mpx.set_telemetry_mode(None)
