"""Traced half of the megastep execution suite (docs/aot.md "Megastep
execution"): everything that needs real traces on the 8-device virtual
CPU mesh.

- megastep == N eager steps bit-identity: ``mpx.compile(fn, unroll=N)``
  and ``mpx.spmd(..., unroll=N)`` against N sequential single-step
  executions, through the token, notoken, and eager comparison paths,
  with fusion and start/wait spans inside the loop body;
- HLO byte-identity at ``unroll=1`` (the megastep layer must be
  invisible until asked for);
- MPX130 (span straddles the loop boundary) positive/negative through
  ``mpx.analyze`` and the ambient error mode;
- the elastic 8 -> 7 shrink drill with a megastep step function:
  commit/retry at megastep granularity, resuming from the last commit;
- the whole-megastep watchdog bracket (deadline scaled by N) and the
  events-tier megastep bracket + synthesized per-step estimate;
- the cache-warming CLI end to end against a manifest.

The pure half (MPX130 checker matrix, manifest parsing, alignment
helpers) is tests/test_megastep_pure.py, via the isolated loader.
"""

import json
import textwrap
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx
from mpi4jax_tpu.resilience import elastic as el
from mpi4jax_tpu.resilience import runtime as resilience_runtime

UNROLL = 4


@pytest.fixture(autouse=True)
def _clean_state():
    el._reset_epoch_for_tests()
    mpx.set_default_mesh(None)
    mpx.clear_caches()
    yield
    mpx.set_telemetry_mode(None)
    mpx.set_analyze_mode(None)
    mpx.set_fusion_mode(None)
    resilience_runtime.reset_overrides()
    el._reset_epoch_for_tests()
    mpx.set_default_mesh(None)
    mpx.clear_caches()
    from mpi4jax_tpu.parallel import region as _region

    _region._default_comm = None


def _world_comm():
    mesh = mpx.make_world_mesh()
    return mpx.Comm(mesh.axis_names[0], mesh=mesh)


def _step_token(v):
    tok = mpx.create_token()
    s, tok = mpx.allreduce(v, op=mpx.SUM, token=tok)
    b, tok = mpx.bcast(mpx.varying(s), 0, token=tok)
    return mpx.varying(b * 0.25 + v * 0.5)


def _step_plain(v):
    s, _ = mpx.allreduce(v, op=mpx.SUM)
    return mpx.varying(s * 0.25 + v * 0.5)


def _n_eager_steps(fn_single, x, n, comm):
    out = x
    prog = mpx.spmd(fn_single, comm=comm)
    for _ in range(n):
        out = prog(out)
    return np.asarray(out)


# ---------------------------------------------------------------------------
# megastep == N eager steps bit-identity
# ---------------------------------------------------------------------------


def test_megastep_pinned_matches_n_steps_token_path():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.arange(k * 6, dtype=jnp.float32).reshape(k, 6) * 0.01
    want = _n_eager_steps(_step_token, x, UNROLL, comm)
    pinned = mpx.compile(_step_token, x, comm=comm, unroll=UNROLL)
    assert pinned.unroll == UNROLL
    got = np.asarray(pinned(x))
    np.testing.assert_array_equal(want, got)


def test_megastep_pinned_matches_n_steps_notoken_path(monkeypatch):
    monkeypatch.setenv("MPI4JAX_TPU_PREFER_NOTOKEN", "1")
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.full((k, 4), 2.0, jnp.float32)
    want = _n_eager_steps(_step_plain, x, UNROLL, comm)
    pinned = mpx.compile(_step_plain, x, comm=comm, unroll=UNROLL)
    np.testing.assert_array_equal(want, np.asarray(pinned(x)))


def test_megastep_spmd_matches_n_steps():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.arange(k * 4, dtype=jnp.float32).reshape(k, 4) * 0.1
    want = _n_eager_steps(_step_plain, x, UNROLL, comm)
    mega = mpx.spmd(_step_plain, comm=comm, unroll=UNROLL)
    np.testing.assert_array_equal(want, np.asarray(mega(x)))


def test_megastep_matches_eager_applications():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.full((k, 3), 1.5, jnp.float32)
    # eager reference: N global-array applications outside any region
    out = x
    for _ in range(UNROLL):
        s, _ = mpx.allreduce(out, op=mpx.SUM, comm=comm)
        out = np.asarray(s) * 0.25 + np.asarray(out) * 0.5
    pinned = mpx.compile(_step_plain, x, comm=comm, unroll=UNROLL)
    np.testing.assert_allclose(np.asarray(pinned(x)), out, rtol=1e-6)


def test_megastep_with_fusion_inside_body():
    mpx.set_fusion_mode("auto")
    comm = _world_comm()
    k = comm.Get_size()

    def step(pair):
        a, b = pair
        # the fusion idiom inside the loop body: issue both, then
        # consume — buckets must stay per-iteration
        ra = mpx.allreduce(a, op=mpx.SUM)[0]
        rb = mpx.allreduce(b, op=mpx.SUM)[0]
        return (mpx.varying(ra * (1.0 / k)), mpx.varying(rb * (1.0 / k)))

    a = jnp.arange(k * 4, dtype=jnp.float32).reshape(k, 4)
    b = jnp.full((k, 4), 3.0, jnp.float32)
    want = _n_eager_steps(step, (a, b), UNROLL, comm)
    pinned = mpx.compile(step, (a, b), comm=comm, unroll=UNROLL)
    got = np.asarray(pinned((a, b)))
    np.testing.assert_array_equal(want, got)


def test_megastep_with_start_wait_inside_body():
    comm = _world_comm()
    k = comm.Get_size()

    def step(v):
        h, _tok = mpx.allreduce_start(v, op=mpx.SUM)
        w = jnp.tanh(v)  # independent compute in the gap
        s, _tok = mpx.allreduce_wait(h)
        return mpx.varying(s * (1.0 / k) + w * 0.0)

    x = jnp.arange(k * 8, dtype=jnp.float32).reshape(k, 8) * 0.05
    want = _n_eager_steps(step, x, UNROLL, comm)
    pinned = mpx.compile(step, x, comm=comm, unroll=UNROLL)
    np.testing.assert_array_equal(want, np.asarray(pinned(x)))


def test_megastep_multi_arg_carry_and_statics():
    comm = _world_comm()
    k = comm.Get_size()

    @mpx.spmd(comm=comm, static_argnums=(1,), unroll=UNROLL)
    def mega(v, gain, w):
        s, _ = mpx.allreduce(v, op=mpx.SUM)
        return (mpx.varying(s * gain), mpx.varying(w + 1.0))

    @mpx.spmd(comm=comm, static_argnums=(1,))
    def single(v, gain, w):
        s, _ = mpx.allreduce(v, op=mpx.SUM)
        return (mpx.varying(s * gain), mpx.varying(w + 1.0))

    v = jnp.full((k, 4), 0.5, jnp.float32)
    w = jnp.zeros((k, 2), jnp.float32)
    cv, cw = v, w
    for _ in range(UNROLL):
        cv, cw = single(cv, 0.125, cw)
    gv, gw = mega(v, 0.125, w)
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(gv))
    np.testing.assert_array_equal(np.asarray(cw), np.asarray(gw))


# ---------------------------------------------------------------------------
# invisibility at unroll=1
# ---------------------------------------------------------------------------


def test_unroll_one_hlo_byte_identical():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)

    from jax.sharding import PartitionSpec as P

    from mpi4jax_tpu.parallel.region import make_region_body

    def lower_text(**kw):
        body = make_region_body(_step_plain, comm, (), (), (), 1,
                                squeeze_in=True, squeeze_out=True, **kw)
        sm = jax.jit(jax.shard_map(
            body, mesh=comm.mesh, in_specs=P(comm.axes[0]),
            out_specs=P(comm.axes[0])))
        return sm.lower(x).as_text()

    assert lower_text(unroll=1) == lower_text()


def test_unroll_validation_and_kwarg_rejection():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    with pytest.raises(ValueError, match=">= 1"):
        mpx.spmd(_step_plain, comm=comm, unroll=0)(x)
    with pytest.raises(TypeError, match="positional"):
        mpx.spmd(lambda *, v: v, comm=comm, unroll=2)(v=x)
    with pytest.raises(ValueError, match="wrap=False|region"):
        mpx.compile(lambda v: v, x, comm=comm, wrap=False, unroll=2)


def test_megastep_carry_contract_error():
    comm = _world_comm()
    k = comm.Get_size()

    def shape_changer(v):
        s, _ = mpx.allreduce(v, op=mpx.SUM)
        return mpx.varying(s[..., :2])  # narrows the carry

    x = jnp.ones((k, 4), jnp.float32)
    with pytest.raises(ValueError, match="megastep carry contract"):
        mpx.spmd(shape_changer, comm=comm, unroll=2)(x)


def test_unroll_default_env_flag(monkeypatch):
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.full((k, 4), 1.0, jnp.float32)
    want = _n_eager_steps(_step_plain, x, 2, comm)
    monkeypatch.setenv("MPI4JAX_TPU_UNROLL_DEFAULT", "2")
    got = mpx.spmd(_step_plain, comm=comm)(x)  # default picks N=2
    np.testing.assert_array_equal(want, np.asarray(got))


def test_unroll_default_degrades_for_non_unrollable_shapes(monkeypatch):
    # a fleet-wide default must not break programs that cannot carry a
    # megastep loop — only an EXPLICIT unroll= errors on them
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    monkeypatch.setenv("MPI4JAX_TPU_UNROLL_DEFAULT", "4")
    pinned = mpx.compile(lambda v: v + 1.0, x, comm=comm, wrap=False)
    assert pinned.unroll == 1
    np.testing.assert_array_equal(np.asarray(pinned(x)),
                                  np.asarray(x) + 1.0)


# ---------------------------------------------------------------------------
# MPX130 through analyze and env=error
# ---------------------------------------------------------------------------


def _straddling_step(v):
    # a start whose wait never appears in the iteration: the span
    # straddles the loop boundary by construction
    h, _tok = mpx.allreduce_start(v, op=mpx.SUM)
    return mpx.varying(v * 1.0)


def _paired_step(v):
    h, _tok = mpx.allreduce_start(v, op=mpx.SUM)
    s, _tok = mpx.allreduce_wait(h)
    return mpx.varying(s * 0.125)


def test_mpx130_through_analyze_positive_and_negative():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)

    bad = mpx.spmd(_straddling_step, comm=comm, unroll=UNROLL)
    report = mpx.analyze(bad, x)
    assert any(f.code == "MPX130" for f in report.findings), report.render()

    good = mpx.spmd(_paired_step, comm=comm, unroll=UNROLL)
    report = mpx.analyze(good, x)
    assert not any(f.code == "MPX130" for f in report.findings), \
        report.render()
    # the same span outside a megastep is MPX112 territory, never MPX130
    report = mpx.analyze(_straddling_step, x, comm=comm)
    assert not any(f.code == "MPX130" for f in report.findings)


def test_mpx130_env_error_fires_at_trace():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)
    mpx.set_analyze_mode("error")
    try:
        with pytest.raises(mpx.AnalysisError, match="MPX130"):
            mpx.spmd(_straddling_step, comm=comm, unroll=UNROLL)(x)
        # negative: the paired span traces clean under the error mode
        out = mpx.spmd(_paired_step, comm=comm, unroll=UNROLL)(x)
        assert np.asarray(out).shape == (k, 4)
    finally:
        mpx.set_analyze_mode(None)


# ---------------------------------------------------------------------------
# watchdog: whole-megastep bracket, deadline scaled by N
# ---------------------------------------------------------------------------


def test_watchdog_brackets_megastep_with_scaled_deadline(monkeypatch):
    from mpi4jax_tpu import native
    from mpi4jax_tpu.resilience import watchdog

    armed = []
    real_arm = watchdog.arm_in_graph

    def spy(mpi_name, call_id, comm, rank, timeout):
        armed.append((mpi_name, timeout))
        return real_arm(mpi_name, call_id, comm, rank, timeout)

    monkeypatch.setattr(watchdog, "arm_in_graph", spy)
    # the Python registry, so the test can see that nothing stays armed
    monkeypatch.setattr(native, "watchdog_supported", lambda: False)
    mpx.set_watchdog_timeout(5.0)
    try:
        comm = _world_comm()
        k = comm.Get_size()
        x = jnp.ones((k, 4), jnp.float32)
        pinned = mpx.compile(_step_plain, x, comm=comm, unroll=UNROLL)
        jax.block_until_ready(pinned(x))
    finally:
        resilience_runtime.reset_overrides()
    # an in-loop arm left in flight would abort the whole process 5 s on
    deadline = time.monotonic() + 5.0
    while not watchdog.registry_empty() and time.monotonic() < deadline:
        time.sleep(0.05)  # callbacks may trail block_until_ready
    assert watchdog.registry_empty(), watchdog.inflight_snapshot()
    mega = [(n, t) for n, t in armed if n.startswith("MPI_Megastep")]
    assert len(mega) == 1, armed
    assert mega[0][1] == pytest.approx(5.0 * UNROLL)
    # per-op arms inside the loop keep the per-collective deadline
    assert any(t == pytest.approx(5.0) for n, t in armed
               if not n.startswith("MPI_Megastep")), armed


# ---------------------------------------------------------------------------
# telemetry: one bracket per megastep + the per-step estimate
# ---------------------------------------------------------------------------


def test_events_tier_megastep_bracket_and_estimate():
    mpx.set_telemetry_mode("events")
    try:
        comm = _world_comm()
        k = comm.Get_size()
        x = jnp.ones((k, 4), jnp.float32)
        pinned = mpx.compile(_step_plain, x, comm=comm, unroll=UNROLL)
        jax.block_until_ready(pinned(x))
        mpx.flush()
        snap = mpx.telemetry.snapshot(include_events=True)
        mega = [e for e in snap["events"]
                if e.get("op") == "megastep" and e.get("type") == "op"]
        assert mega, snap["events"][:5]
        assert all(e["unroll"] == UNROLL for e in mega)
        # one bracket per rank per megastep execution — not one per step
        per_rank = {}
        for e in mega:
            per_rank[e["rank"]] = per_rank.get(e["rank"], 0) + 1
        assert set(per_rank.values()) == {1}, per_rank
        from mpi4jax_tpu.telemetry.core import op_key

        step_key = op_key("megastep_step", str(comm.uid), "estimate", "")
        hist = snap["ops"][step_key]["latency"]
        assert hist["count"] >= 1
    finally:
        mpx.set_telemetry_mode(None)


def test_counters_tier_adds_no_bracket_callbacks():
    # counters mode must not change the megastep HLO (no io_callbacks)
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 4), jnp.float32)

    from jax.sharding import PartitionSpec as P

    from mpi4jax_tpu.parallel.region import make_region_body

    def lower_text():
        body = make_region_body(_step_plain, comm, (), (), (), 1,
                                squeeze_in=True, squeeze_out=True,
                                unroll=UNROLL)
        sm = jax.jit(jax.shard_map(
            body, mesh=comm.mesh, in_specs=P(comm.axes[0]),
            out_specs=P(comm.axes[0])))
        return sm.lower(x).as_text()

    base = lower_text()
    mpx.set_telemetry_mode("counters")
    try:
        assert lower_text() == base
    finally:
        mpx.set_telemetry_mode(None)


# ---------------------------------------------------------------------------
# the elastic megastep drill: 8 -> 7 mid-megastep, resume from commit
# ---------------------------------------------------------------------------


@pytest.mark.faults
def test_elastic_run_megastep_shrink_drill():
    """The acceptance drill at megastep granularity: a pinned megastep
    step function (unroll=2) survives an 8 -> 7 shrink — the loop
    advances by 2 per call, commit_every aligns up to the megastep
    boundary, the failure mid-run resumes from the last commit, and the
    budget completes on 7 ranks with a second pin on record."""
    steps, fail_at, unroll = 8, 4, 2
    comm = _world_comm()
    store = mpx.ShardStore(comm)
    worlds = []

    def base(state, step_scalar, comm):
        g, _ = mpx.allreduce(state["p"] * 0.01, op=mpx.SUM, comm=comm)
        return {"p": mpx.varying(state["p"] - g / comm.uniform_size())}

    class Drill:
        def __init__(self):
            self.inner = mpx.aot.compile_step(base, unroll=unroll)
            self.unroll = self.inner.unroll

        def __call__(self, state, step, comm):
            worlds.append((step, comm.Get_size()))
            if step == fail_at and comm.epoch == 0:
                raise mpx.RankFailure({3}, "simulated")
            return self.inner(state, step, comm)

        def repin(self):
            self.inner.repin()
            return self

    p0 = np.full((3, 2), 1.0, np.float32)
    final = mpx.elastic.run(Drill(), {"p": p0}, store, steps=steps,
                            commit_every=1)  # aligns up to 2 internally

    assert el.current_epoch() == 1
    assert store.comm.Get_size() == 7
    # megastep granularity: only even step boundaries were dispatched,
    # and the post-shrink world finished the budget from the last commit
    assert all(s % unroll == 0 for s, _ in worlds), worlds
    assert sorted({s for s, w in worlds if w == 7}) == list(
        range(fail_at, steps, unroll)), worlds
    stats = mpx.cache_stats()["aot"]
    assert stats["pins"] >= 2, stats
    assert stats["stale_raises"] >= 1, stats
    assert np.asarray(final["p"]).shape == (3, 2)


def test_elastic_megastep_equals_single_steps():
    comm = _world_comm()

    def base(state, step_scalar, comm):
        s, _ = mpx.allreduce(state["v"], op=mpx.SUM, comm=comm)
        return {"v": mpx.varying(s / comm.uniform_size() + 0.25)}

    single = mpx.aot.compile_step(base)
    mega = mpx.aot.compile_step(base, unroll=3)
    assert mega.unroll == 3

    s0 = {"v": np.full((4,), 1.0, np.float32)}
    want = s0
    for i in range(3):
        want = single(want, i, comm)
    got = mega(s0, 0, comm)
    np.testing.assert_allclose(np.asarray(got["v"]), np.asarray(want["v"]),
                               rtol=1e-6)


def test_elastic_run_budget_must_align():
    comm = _world_comm()
    store = mpx.ShardStore(comm)

    def base(state, step_scalar, comm):
        return state

    step = mpx.aot.compile_step(base, unroll=3)
    with pytest.raises(ValueError, match="multiple of"):
        mpx.elastic.run(step, {"v": np.ones((2,), np.float32)}, store,
                        steps=8)


# ---------------------------------------------------------------------------
# the cache-warming CLI, end to end
# ---------------------------------------------------------------------------


def test_warm_cli_populates_cache(monkeypatch, tmp_path):
    from mpi4jax_tpu.aot.warm import EXIT_OK, warm_from_manifest

    target = tmp_path / "warmtarget.py"
    target.write_text(textwrap.dedent("""
        import mpi4jax_tpu as mpx

        def step(v):
            s, _ = mpx.allreduce(v, op=mpx.SUM)
            return mpx.varying(s * 0.125)
    """))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "cache"))

    comm = _world_comm()
    k = comm.Get_size()
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps({"programs": [{
        "fn": "warmtarget:step",
        "args": [{"shape": [k, 16], "dtype": "float32"}],
        "unroll": 4,
    }]}))

    code, payload = warm_from_manifest(str(manifest), comm=comm)
    assert code == EXIT_OK, payload
    assert payload["warmed"] == 1 and payload["failed"] == 0
    assert payload["programs"][0]["unroll"] == 4
    stats = mpx.cache_stats()
    assert stats["aot"]["warmed"] == 1
    assert stats["disk_cache"]["writes"] >= 1

    # the warmed artifact serves the real pin: zero re-lowers
    mpx.clear_caches()
    import warmtarget

    x = jnp.ones((k, 16), jnp.float32)
    pinned = mpx.compile(warmtarget.step, x, comm=comm, unroll=4)
    assert pinned.from_disk, "warmed program was not served from disk"
    assert mpx.cache_stats()["disk_cache"]["misses"] == 0
    out = np.asarray(pinned(x))
    assert out.shape == (k, 16)


def test_warm_cli_main_exit_codes(monkeypatch, tmp_path, capsys):
    from mpi4jax_tpu.aot.__main__ import main

    monkeypatch.delenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", raising=False)
    code = main(["warm", str(tmp_path / "nope.json"), "--json"])
    assert code == 2
    payload = json.loads(capsys.readouterr().out.strip())
    assert "COMPILE_CACHE_DIR" in payload["error"]
