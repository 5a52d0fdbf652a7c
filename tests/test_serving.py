"""Traced half of the serving-runtime suite (docs/serving.md): real
programs on the 8-device virtual CPU mesh.

- pinned-per-bucket == un-bucketed reference bit-identity: the engine's
  ``mpx.compile`` prefill/decode programs produce bitwise the outputs of
  plain ``mpx.spmd`` runs of the same step functions, and a decode
  MEGASTEP equals ``unroll`` sequential single steps;
- scheduling invariance: greedy decode tokens depend only on the
  request (lanes are independent), so continuous vs static vs any
  unroll produce identical token streams;
- one program per (bucket, phase): live batches sharing a bucket share
  one pinned program;
- megastep-boundary admission under the deterministic virtual clock;
- MPX136 positive/negative through ``mpx.analyze`` AND the ambient
  error mode (gated on a declared bucket table);
- the serving telemetry surface (per-phase op rows + the report
  section);
- the drain drill (slow): a preemption notice at a megastep boundary
  row-shrinks a (2, 4) world to 4 ranks mid-traffic with zero failed
  requests, in-flight sequences re-admitted from committed history;
- warm-manifest round trip (slow): ``aot warm`` over the emitted
  serving manifest, then a serving run with ``disk_cache.misses == 0``.

The pure half (bucket table, scheduler, allocator, SLO math, manifest
schema, MPX136 checker, cost-model replay) runs under any JAX in
tests/test_serving_pure.py via the isolated loader.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx
from mpi4jax_tpu.resilience import elastic as el
from mpi4jax_tpu.serving import (
    ServingConfig,
    ServingEngine,
    clear_declared_buckets,
    declare_buckets,
    poisson_trace,
    warm_manifest,
)
from mpi4jax_tpu.serving import model as smodel


@pytest.fixture(autouse=True)
def _clean_state():
    el._reset_epoch_for_tests()
    mpx.set_default_mesh(None)
    mpx.clear_caches()
    clear_declared_buckets()
    yield
    mpx.set_telemetry_mode(None)
    mpx.set_analyze_mode(None)
    el._reset_epoch_for_tests()
    mpx.set_default_mesh(None)
    mpx.clear_caches()
    clear_declared_buckets()
    from mpi4jax_tpu.parallel import region as _region

    _region._default_comm = None
    from mpi4jax_tpu.telemetry import core as _tcore

    _tcore.reset()


def _world_comm():
    mesh = mpx.make_world_mesh()
    return mpx.Comm(mesh.axis_names[0], mesh=mesh)


def _tiny_cfg(**overrides):
    base = dict(vocab=32, heads=8, head_dim=2, ffn=32, max_len=32,
                max_prompt=8, max_batch=4, kv_slots=8, unroll=2,
                slo_p99_ms=60_000.0, clock="virtual", seed=11)
    base.update(overrides)
    return ServingConfig(**base)


def _tiny_trace(n=6, rate=300.0, seed=5):
    return poisson_trace(n, rate, seed=seed, prompt_len=(2, 4),
                         max_new=(2, 6), long_frac=0.0, vocab=32)


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# end-to-end serving
# ---------------------------------------------------------------------------


def test_engine_serves_trace_continuous():
    comm = _world_comm()
    engine = ServingEngine(_tiny_cfg(), comm)
    trace = _tiny_trace()
    out = engine.run(trace, scheduler="continuous")
    assert out["completed"] == len(trace)
    assert out["failed"] == 0
    assert out["tokens"] == sum(r.max_new_tokens for r in trace)
    assert out["p99_ms"] is not None and out["slo_met"]
    assert out["world"] == comm.Get_size()
    assert any(p.startswith("decode.b") for p in out["programs"])
    assert any(p.startswith("prefill.b") for p in out["programs"])


def test_engine_static_baseline_completes():
    comm = _world_comm()
    engine = ServingEngine(_tiny_cfg(), comm)
    trace = _tiny_trace()
    out = engine.run(trace, scheduler="static")
    assert out["completed"] == len(trace) and out["failed"] == 0


def test_tokens_invariant_under_scheduling():
    """Lanes are independent (attention reads only the lane's own KV
    slot), so the greedy token stream of a request is a pure function of
    the request — identical under continuous/static scheduling and any
    megastep unroll."""
    comm = _world_comm()
    trace = _tiny_trace(n=5)

    def tokens_for(cfg, sched):
        engine = ServingEngine(cfg, comm)
        engine.run(trace, scheduler=sched)
        return {s.rid: tuple(s.generated)
                for s in engine._sched.finished}

    base = tokens_for(_tiny_cfg(unroll=1), "continuous")
    assert tokens_for(_tiny_cfg(unroll=2), "continuous") == base
    assert tokens_for(_tiny_cfg(unroll=2), "static") == base


def test_one_program_per_bucket():
    """Live batches 3 and 4 share bucket 4: ONE pinned decode program
    serves both compositions (the padded-bucket one-key rule)."""
    from mpi4jax_tpu.serving import Request

    comm = _world_comm()
    engine = ServingEngine(_tiny_cfg(unroll=1), comm)
    # 4 requests at t=0; one finishes after 2 tokens (live batch drops
    # to 3, still bucket 4), the rest together after 4 — the decode
    # bucket is 4 throughout
    budgets = [2, 4, 4, 4]
    trace = [Request(rid=i, arrival_s=0.0, prompt=(1, 2),
                     max_new_tokens=b) for i, b in enumerate(budgets)]
    from mpi4jax_tpu.aot import pinning

    pinning.reset_stats()
    out = engine.run(trace, scheduler="continuous")
    assert out["failed"] == 0
    decode_programs = [p for p in out["programs"]
                       if p.startswith("decode.")]
    assert decode_programs == ["decode.b4"]
    # exactly one pin per program the engine reports
    assert pinning.stats()["pins"] == len(out["programs"])


# ---------------------------------------------------------------------------
# bit-identity vs the un-bucketed reference
# ---------------------------------------------------------------------------


def _manual_args(engine, cfg, comm, n_live=2):
    """Hand-built lane arrays for ``n_live`` sequences in bucket
    ``bucket_for(n_live)`` with freshly allocated slots."""
    k = comm.Get_size()
    bucket = engine.table.bucket_for(n_live)
    rng = np.random.default_rng(3)
    plens = [3, 2][:n_live]
    prompts = np.zeros((bucket, cfg.max_prompt), np.int32)
    for i, pl in enumerate(plens):
        prompts[i, :pl] = rng.integers(1, cfg.vocab, pl)
    prompts_g = engine._prep(np.tile(prompts[None], (k, 1, 1)))
    plens_g = engine._prep(np.tile(np.asarray(
        plens + [1] * (bucket - n_live), np.int32)[None], (k, 1)))
    slots_g = engine._prep(np.tile(np.asarray(
        list(range(n_live)) + [cfg.slots()] * (bucket - n_live),
        np.int32)[None], (k, 1)))
    return bucket, prompts_g, plens_g, slots_g


def test_pinned_prefill_matches_spmd_reference():
    comm = _world_comm()
    cfg = _tiny_cfg()
    engine = ServingEngine(cfg, comm)
    bucket, prompts_g, plens_g, slots_g = _manual_args(engine, cfg, comm)
    args = engine._state + (prompts_g, plens_g, slots_g)
    pinned = engine._program("prefill", bucket)(*args)
    ref = mpx.spmd(smodel.prefill_step, comm=comm)(*args)
    _trees_equal(pinned, ref)


def _chip_smoke():
    """``chip_smoke.py`` (repo root) as a module: it owns the unsharded
    reference forward, written independently of serving/model.py."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_tensor_parallel_prefill_matches_unsharded_forward():
    """The 8-way tensor-parallel prefill reproduces chip_smoke.py's
    plain-jnp forward of the unsharded master weights (the check its
    stage C makes on the chip), and the logits it samples from are the
    ones that pick prefill_step's first token."""
    comm = _world_comm()
    cfg = _tiny_cfg()
    engine = ServingEngine(cfg, comm)
    bucket, prompts_g, plens_g, slots_g = _manual_args(engine, cfg, comm)
    # the engine's state sits on the mesh once, a row per device
    for arr in engine._state:
        assert len(arr.sharding.device_set) == comm.Get_size()
    args = engine._state + (prompts_g, plens_g, slots_g)
    logits = np.asarray(
        mpx.spmd(smodel.prefill_logits, comm=comm)(*args))
    ref = np.asarray(_chip_smoke().reference_prefill_logits(
        engine.master, np.asarray(prompts_g)[0], np.asarray(plens_g)[0]))
    # f32 on the CPU: only the order of the contractions differs
    np.testing.assert_allclose(logits, np.tile(ref[None], (8, 1, 1)),
                               rtol=0, atol=1e-5 * np.abs(ref).max())
    first = np.asarray(mpx.spmd(smodel.prefill_step, comm=comm)(*args)[3])
    np.testing.assert_array_equal(first, logits.argmax(-1))


def test_engine_warm_pins_every_program_before_serving():
    from mpi4jax_tpu.aot import pinning

    pinning.reset_stats()
    comm = _world_comm()
    cfg = _tiny_cfg()
    engine = ServingEngine(cfg, comm)
    assert engine.warm() > 0
    warmed = pinning.stats()
    assert warmed["pins"] == 2 * len(engine.table.buckets)
    out = engine.run(_tiny_trace(), scheduler="continuous")
    assert out["failed"] == 0
    served = pinning.stats()
    # nothing was built inside the serving loop
    assert served["pins"] == warmed["pins"]
    assert served["compiles"] == warmed["compiles"]


def test_decode_megastep_matches_stepwise_reference():
    """One pinned decode megastep (unroll=N) == N sequential un-bucketed
    single-step spmd calls, bit for bit."""
    comm = _world_comm()
    cfg = _tiny_cfg(unroll=2)
    engine = ServingEngine(cfg, comm)
    bucket, prompts_g, plens_g, slots_g = _manual_args(engine, cfg, comm)
    kk, vv, tok, first = mpx.spmd(smodel.prefill_step, comm=comm)(
        *(engine._state + (prompts_g, plens_g, slots_g)))
    state = engine._state[:5] + (kk, vv, tok)
    lens_g = plens_g  # after prefill: lens == plen, last token at col plen
    dec_args = state + (first, lens_g, slots_g)

    meg = engine._program("decode", bucket)(*dec_args)

    ref_step = mpx.spmd(smodel.decode_step, comm=comm, unroll=1)
    cur = dec_args
    for _ in range(cfg.unroll):
        cur = ref_step(*cur)
    _trees_equal(meg, tuple(cur))


# ---------------------------------------------------------------------------
# megastep-boundary admission (virtual clock)
# ---------------------------------------------------------------------------


def test_admission_lands_on_megastep_boundaries():
    comm = _world_comm()
    cfg = _tiny_cfg(unroll=2, tick_s=0.01)
    engine = ServingEngine(cfg, comm)
    # one request up front, one arriving strictly BETWEEN boundary
    # instants: it must be admitted at the next boundary tick, never
    # mid-megastep
    trace = _tiny_trace(n=1, rate=1e6)
    late = poisson_trace(1, 1e6, seed=9, prompt_len=(2, 3),
                         max_new=(2, 4), vocab=32)[0]
    late = type(late)(rid=99, arrival_s=0.015, prompt=late.prompt,
                      max_new_tokens=late.max_new_tokens)
    out = engine.run(trace + [late], scheduler="continuous")
    assert out["failed"] == 0 and out["completed"] == 2
    tick = cfg.tick_s
    for s in engine._sched.finished:
        # admission instants are boundary instants
        ratio = s.admitted_s / tick
        assert abs(ratio - round(ratio)) < 1e-9, s.admitted_s
    late_seq = next(s for s in engine._sched.finished if s.rid == 99)
    assert late_seq.admitted_s >= 0.02  # the boundary AFTER arrival


# ---------------------------------------------------------------------------
# MPX136 through analyze and the ambient error mode
# ---------------------------------------------------------------------------


def _unbucketed_fn(comm):
    def fn(x):  # per-rank payload (5, 16): 5 is not a bucket
        s, _ = mpx.allreduce(x, op=mpx.SUM, comm=comm)
        return mpx.varying(s)

    return fn


def test_mpx136_via_analyze():
    comm = _world_comm()
    k = comm.Get_size()
    declare_buckets((1, 2, 4, 8))
    x = jnp.ones((k, 5, 16), jnp.float32)
    report = mpx.analyze(_unbucketed_fn(comm), x, comm=comm)
    assert any(f.code == "MPX136" for f in report.findings), report
    # in-bucket shape: clean
    x4 = jnp.ones((k, 4, 16), jnp.float32)
    report = mpx.analyze(_unbucketed_fn(comm), x4, comm=comm)
    assert not any(f.code == "MPX136" for f in report.findings), report


def test_mpx136_requires_declared_table():
    comm = _world_comm()
    k = comm.Get_size()
    x = jnp.ones((k, 5, 16), jnp.float32)
    report = mpx.analyze(_unbucketed_fn(comm), x, comm=comm)
    assert not any(f.code == "MPX136" for f in report.findings), report


def test_mpx136_ambient_error_mode():
    comm = _world_comm()
    k = comm.Get_size()
    declare_buckets((1, 2, 4, 8))
    mpx.set_analyze_mode("error")
    x = jnp.ones((k, 5, 16), jnp.float32)
    with pytest.raises(mpx.AnalysisError, match="MPX136"):
        mpx.run(_unbucketed_fn(comm), x, comm=comm)
    mpx.set_analyze_mode(None)


# ---------------------------------------------------------------------------
# telemetry: per-phase rows + the serving report section
# ---------------------------------------------------------------------------


def test_serving_phase_telemetry_and_report_section():
    comm = _world_comm()
    mpx.set_telemetry_mode("events")
    engine = ServingEngine(_tiny_cfg(), comm)
    out = engine.run(_tiny_trace(), scheduler="continuous")
    assert out["failed"] == 0
    from mpi4jax_tpu.telemetry import core as tcore
    from mpi4jax_tpu.telemetry import journal
    from mpi4jax_tpu.telemetry import report as treport

    snap = tcore.snapshot(include_events=True)
    phase_ops = {row["op"] for row in snap["ops"].values()}
    assert "serving.prefill" in phase_ops
    assert "serving.decode" in phase_ops
    # journal brackets per dispatch, with bucket + unroll meta
    recs = [r for r in journal.snapshot_events()
            if r.get("op") == "serving.decode"]
    assert recs and all(r["unroll"] == 2 for r in recs)
    assert all("latency" in r for r in recs)
    text = treport.render([snap])
    assert "serving:" in text
    assert "requests completed" in text
    assert "serving.decode" in text
    meters = snap["meters"]
    assert meters["serving.megasteps"] >= 1
    assert meters["serving.requests_completed"] == 6


# ---------------------------------------------------------------------------
# the drain drill (single-controller): preemption at a boundary
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_drain_drill_row_shrink(monkeypatch):
    """A preemption notice lands at megastep boundary 2: the (2, 4)
    world row-shrinks to 4 ranks between megasteps, survivors re-shard
    the committed parameters, re-admit every in-flight sequence from its
    committed history, and the trace finishes with zero failures —
    exactly one drain incident journalled."""
    monkeypatch.setenv("MPI4JAX_TPU_ELASTIC_FAIL_UNIT", "row")
    mpx.set_telemetry_mode("events")
    mesh = mpx.make_world_mesh((2, 4), ("y", "x"))
    comm = mpx.Comm(("y", "x"), mesh=mesh)
    store = mpx.ShardStore(comm)
    cfg = _tiny_cfg()
    engine = ServingEngine(cfg, comm, store=store)
    trace = _tiny_trace(n=10, rate=400.0)

    from mpi4jax_tpu.parallel import megastep

    def notice(step, **info):
        if step == 2 and el.current_epoch() == 0:
            mpx.request_drain(rank=7)

    unregister = megastep.register_boundary_hook("test-preempt", notice)
    try:
        out = engine.run(trace, scheduler="continuous")
    finally:
        unregister()

    assert out["failed"] == 0
    assert out["completed"] == len(trace)
    assert out["world"] == 4
    assert out["preempt_readmissions"] > 0
    assert el.current_epoch() == 1
    from mpi4jax_tpu.telemetry import journal

    drains = [r for r in journal.snapshot_events()
              if r.get("type") == "instant" and r.get("name") == "drain"]
    assert len(drains) == 1, drains
    # replay programs were pinned for the re-admission
    assert any(p.startswith("replay.") for p in out["programs"])


# ---------------------------------------------------------------------------
# warm manifest -> zero-miss serving run (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_warm_manifest_then_zero_miss_serving(tmp_path, monkeypatch):
    from mpi4jax_tpu.aot import pinning, warm

    cfg = _tiny_cfg()
    manifest = warm_manifest(cfg, jax.device_count())
    path = tmp_path / "serving-manifest.json"
    path.write_text(json.dumps(manifest))
    cache_dir = tmp_path / "cache"
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR",
                       str(cache_dir))

    code, payload = warm.warm_from_manifest(str(path))
    assert code == 0, payload
    assert payload["warmed"] == len(manifest["programs"])
    assert os.path.isdir(cache_dir)

    # a fresh serving run over the warmed cache: every pin deserializes.
    # The engine serves over the same DEFAULT world comm the warm used,
    # so the mesh descriptor — and with it every persistent key — match.
    mpx.clear_caches()
    pinning.reset_stats()
    from mpi4jax_tpu.aot import diskcache

    diskcache.reset_stats()
    engine = ServingEngine(cfg)
    out = engine.run(_tiny_trace(), scheduler="continuous")
    assert out["failed"] == 0
    stats = mpx.cache_stats()
    assert stats["disk_cache"]["misses"] == 0, stats
    assert stats["disk_cache"]["hits"] >= len(out["programs"]), stats
    assert stats["aot"]["compiles"] == 0, stats
