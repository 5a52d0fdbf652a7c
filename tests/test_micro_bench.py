"""Smoke test for benchmarks/micro.py — it must keep producing numbers.

VERDICT r2: micro.py had never been executed by CI, so it could silently
rot.  Run both sweeps at tiny sizes on the test mesh and check the output
schema matches what benchmarks/results/*.json commits.
"""

import os
import sys

import jax

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmarks")
)

import micro  # noqa: E402

import mpi4jax_tpu as mpx  # noqa: E402


def _world_comm():
    mesh = mpx.make_world_mesh(devices=jax.devices())
    return mpx.Comm(mesh.axis_names[0], mesh=mesh)


def test_bench_allreduce_schema():
    comm = _world_comm()
    rows = micro.bench_allreduce(comm, sizes_mb=[0.0001], iters=2)
    assert len(rows) == 1
    r = rows[0]
    assert r["time_us"] > 0
    # tiny payloads round the bandwidth to 0.0 — only presence is asserted
    assert (r["bus_gb_s"] is None) == (comm.Get_size() == 1)


def test_bench_sendrecv_schema():
    comm = _world_comm()
    rows = micro.bench_sendrecv_ring(comm, sizes_kb=[0.004], iters=2)
    assert len(rows) == 1
    r = rows[0]
    assert r["hop_us"] > 0
    assert (r["link_gb_s"] is None) == (comm.Get_size() == 1)


def test_bench_prod_and_split_schema():
    comm = _world_comm()
    rows = micro.bench_prod_and_split(comm, sizes_mb=[0.0001], iters=2)
    assert len(rows) == 1
    r = rows[0]
    assert r["prod_us"] > 0
    assert (r["prod_split_us"] is None) == (comm.Get_size() == 1)
    if r["prod_split_us"] is not None:
        assert r["prod_split_us"] > 0


def test_bench_allreduce_algos_schema():
    # force-compiles BOTH CollectivePermute algorithms (butterfly + ring)
    # at a tiny size: a lowering regression in either fails here, fast
    comm = _world_comm()
    saved = os.environ.get("MPI4JAX_TPU_COLLECTIVE_ALGO")
    rows = micro.bench_allreduce_algos(comm, sizes_mb=[0.0001], iters=2)
    assert os.environ.get("MPI4JAX_TPU_COLLECTIVE_ALGO") == saved  # restored
    assert len(rows) == 1
    r = rows[0]
    assert r["butterfly_us"] > 0 and r["ring_us"] > 0
    assert (r["ring_speedup"] is None) == (comm.Get_size() == 1)


def test_bench_fusion_schema():
    # compiles the fused AND unfused programs at a tiny size: a deferral
    # or packing regression in the fusion layer fails here, fast
    comm = _world_comm()
    saved = os.environ.get("MPI4JAX_TPU_FUSION")
    rows = micro.bench_fusion(comm, counts=(4,), size_kb=0.02, iters=1)
    assert os.environ.get("MPI4JAX_TPU_FUSION") == saved  # restored
    assert len(rows) == 1
    r = rows[0]
    assert r["count"] == 4
    assert r["unfused_us_per_op"] > 0 and r["fused_us_per_op"] > 0
    assert r["fused_speedup"] > 0


def test_bench_overlap_schema():
    comm = _world_comm()
    rows = micro.bench_overlap(comm, sizes_mb=[0.0001], iters=2,
                               compute_dim=8)
    assert len(rows) == 1
    r = rows[0]
    assert r["monolithic_us"] > 0 and r["overlap_us"] > 0
    assert r["chunks"] >= 1 and r["overlap_speedup"] > 0


def test_bench_hierarchy_schema():
    # compiles the flat ring AND the forced two-level lowering under a
    # faked 2x4 host topology at a tiny size: a hierarchy regression in
    # either fails here, fast; a topology spec that does not cover the
    # mesh is skipped, not an error (docs/topology.md)
    comm = _world_comm()
    saved_topo = os.environ.get("MPI4JAX_TPU_TOPOLOGY")
    saved_algo = os.environ.get("MPI4JAX_TPU_COLLECTIVE_ALGO")
    rows = micro.bench_hierarchy(comm, sizes_mb=[0.0001],
                                 topologies=("2x4", "3x9"), iters=2)
    assert os.environ.get("MPI4JAX_TPU_TOPOLOGY") == saved_topo  # restored
    assert os.environ.get("MPI4JAX_TPU_COLLECTIVE_ALGO") == saved_algo
    assert len(rows) == 1  # 3x9 covers 27 ranks, not this mesh: skipped
    r = rows[0]
    assert r["topology"] == "2x4"  # the topology stamp --save commits
    assert r["flat_us"] > 0 and r["hier_us"] > 0
    assert (r["hier_speedup"] is None) == (comm.Get_size() == 1)


def test_bench_alltoall_schema():
    # compiles all three alltoall execution shapes — flat single
    # exchange, the forced two-level lowering, and the chunked async
    # start/wait split — under a faked 2x4 host topology at a tiny
    # size, and checks the modeled DCN byte/message columns ride every
    # uniform-topology row (docs/moe.md); a non-covering spec is
    # skipped, not an error
    comm = _world_comm()
    saved = {k: os.environ.get(k) for k in
             ("MPI4JAX_TPU_TOPOLOGY", "MPI4JAX_TPU_COLLECTIVE_ALGO",
              "MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES")}
    rows = micro.bench_alltoall(comm, sizes_mb=[0.0001],
                                topologies=("2x4", "3x9"), iters=2)
    for k, v in saved.items():
        assert os.environ.get(k) == v, k  # restored
    assert len(rows) == 1  # 3x9 covers 27 ranks, not this mesh: skipped
    r = rows[0]
    assert r["topology"] == "2x4"
    assert r["flat_us"] > 0 and r["hier_us"] > 0 and r["async_us"] > 0
    assert (r["hier_speedup"] is None) == (comm.Get_size() == 1)
    # the modeled DCN columns: the 1/r message aggregation is stamped
    # into every saved row (the acceptance artifact's claim)
    assert r["dcn_msgs_flat"] == r["dcn_msgs_hier"] * r["dcn_msg_reduction"]
    assert r["dcn_msg_reduction"] == 4  # 2x4: r = 4
    assert r["dcn_bytes_hier"] <= r["dcn_bytes_flat"]


def test_alltoall_replay_artifact_current(tmp_path):
    # the committed cost-model replay (BENCH_alltoall.json) must be
    # reproducible from its embedded recipe and carry the acceptance
    # invariants: 1/r DCN message reduction on every row, overlapped
    # MoE step beating the synchronous one
    import json
    import pathlib
    import subprocess

    repo = pathlib.Path(__file__).resolve().parent.parent
    committed = json.loads((repo / "BENCH_alltoall.json").read_text())
    assert committed["schema"] == "mpx-alltoall-replay/1"
    for row in committed["sweep"]:
        assert row["dcn_msgs_flat"] == \
            row["dcn_msgs_hier"] * row["dcn_msg_reduction"], row
    for row in committed["moe_step"]:
        assert row["overlap_speedup"] > 1.0, row
    out = tmp_path / "replay.json"
    subprocess.run(
        [sys.executable, str(repo / "benchmarks" / "alltoall_replay.py"),
         "--out", str(out)],
        capture_output=True, text=True, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert json.loads(out.read_text()) == committed


def test_bench_compression_schema():
    # runs the codec sweep at a tiny size under a faked 2x4 topology:
    # every {off, bf16, fp8} row carries the logical/wire byte split
    # (from ops/_codec.wire_bytes — the shared byte truth), a modeled
    # DCN time, and a measured roundtrip error that is exactly zero
    # only for the exact codec (docs/compression.md)
    from mpi4jax_tpu.ops import _codec

    comm = _world_comm()
    rows = micro.bench_compression(comm, sizes_mb=[0.01], iters=2)
    assert [r["codec"] for r in rows] == ["off", "bf16", "fp8"]
    for r in rows:
        assert r["size_mb"] == 0.01 and r["topology"] == "2x4"
        assert r["wire_dcn_bytes"] == _codec.wire_bytes(
            r["logical_dcn_bytes"], None if r["codec"] == "off"
            else r["codec"])
        assert r["modeled_dcn_us"] > 0
        if r["codec"] == "off":
            assert r["rel_err"] == 0.0
            assert r["wire_dcn_bytes"] == r["logical_dcn_bytes"]
        else:
            assert 0 < r["rel_err"] < 1.0
            assert r["wire_dcn_bytes"] * 2 <= r["logical_dcn_bytes"]


def test_compress_replay_artifact_current(tmp_path):
    # the committed compression replay (BENCH_compress.json) must be
    # reproducible from its embedded recipe and carry the acceptance
    # invariants: >= 2x DCN wire reduction for both codecs, compressed
    # loss curves within the stated parity tolerance of the exact one
    import json
    import pathlib
    import subprocess

    repo = pathlib.Path(__file__).resolve().parent.parent
    committed = json.loads((repo / "BENCH_compress.json").read_text())
    assert committed["schema"] == "mpx-compress-replay/1"
    for row in committed["wire_sweep"]:
        if row["codec"] != "off":
            assert row["wire_reduction"] >= 2.0, row
    for codec, p in committed["convergence"]["parity"].items():
        assert p["max_rel_gap"] <= p["tolerance"], (codec, p)
    out = tmp_path / "replay.json"
    subprocess.run(
        [sys.executable, str(repo / "benchmarks" / "compress_replay.py"),
         "--out", str(out)],
        capture_output=True, text=True, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert json.loads(out.read_text()) == committed


def test_bench_dispatch_schema():
    # compiles all three execution surfaces — eager one-op, spmd, and
    # the mpx.compile-pinned artifact — for the same allreduce at a tiny
    # size: a pinning or dispatch-path regression fails here, fast
    comm = _world_comm()
    rows = micro.bench_dispatch(comm, sizes_kb=[0.004], iters=3)
    assert len(rows) == 1
    r = rows[0]
    assert r["eager_us"] > 0 and r["spmd_us"] > 0 and r["pinned_us"] > 0
    assert r["pinned_vs_spmd"] is not None and r["pinned_vs_spmd"] > 0
    # the sweep pinned at least one program this process
    assert mpx.cache_stats()["aot"]["pins"] >= 1


def test_bench_dispatch_unroll_schema():
    # compiles the same one-allreduce step pinned at two megastep trip
    # counts (mpx.compile(fn, ..., unroll=N)) — a megastep lowering or
    # amortization-math regression fails here, fast (docs/aot.md
    # "Megastep execution"); the full 1/8 amortization assert at
    # unroll=64 lives in the CI aot lane against the saved sweep
    comm = _world_comm()
    du = micro.bench_dispatch_unroll(comm, unrolls=(1, 4), size_kb=0.004,
                                     iters=3)
    assert set(du) == {"size_kb", "onchip_per_step_us", "rows"}
    assert du["onchip_per_step_us"] >= 0
    assert [r["unroll"] for r in du["rows"]] == [1, 4]
    for r in du["rows"]:
        assert r["megastep_us"] > 0 and r["per_step_us"] > 0
        assert r["per_step_host_us"] >= 0
    # amortization direction: per-step host cost must not grow with N
    assert (du["rows"][1]["per_step_host_us"]
            <= du["rows"][0]["per_step_host_us"] + 1e-9)
    assert mpx.cache_stats()["aot"]["pins"] >= 2


def test_bench_health_overhead_schema():
    # all four telemetry configurations of the same eager allreduce at a
    # tiny size — a dispatch-path regression in any tier fails here; the
    # 10% counters+ring bound itself is asserted in the CI smoke lane
    # where iteration counts make the ratio meaningful
    comm = _world_comm()
    saved = {k: os.environ.get(k)
             for k in ("MPI4JAX_TPU_HEALTH", "MPI4JAX_TPU_FLIGHT_RING")}
    rows = micro.bench_health_overhead(comm, sizes_kb=[0.004], iters=2)
    assert len(rows) == 1
    r = rows[0]
    for col in ("off_us", "counters_us", "counters_ring_us", "events_us"):
        assert r[col] > 0, col
    assert r["ring_overhead_ratio"] is not None
    assert r["ring_overhead_ratio"] > 0
    # the sweep must leave no telemetry or health state behind
    assert mpx.telemetry.effective_mode() == "off"
    for k, v in saved.items():
        assert os.environ.get(k) == v, k


def test_health_replay_artifact_current(tmp_path):
    # the committed record-volume replay (BENCH_health.json) must be
    # reproducible from its embedded recipe and carry the overhead
    # invariants: counters+ring pushes exactly one ring record per
    # dispatch and adds ZERO journal records over counters-only
    import json
    import pathlib
    import subprocess

    repo = pathlib.Path(__file__).resolve().parent.parent
    committed = json.loads((repo / "BENCH_health.json").read_text())
    assert committed["schema"] == "mpx-health-replay/1"
    by_mode = {(r["mode"], r["health"]): r for r in committed["configs"]}
    ring = by_mode[("counters", "on")]
    assert ring["ring_pushed_records"] == ring["dispatch_records"]
    assert ring["journal_records"] == \
        by_mode[("counters", "off")]["journal_records"] == 0
    assert by_mode[("events", "on")]["journal_records"] == \
        by_mode[("events", "off")]["journal_records"]
    out = tmp_path / "replay.json"
    subprocess.run(
        [sys.executable, str(repo / "benchmarks" / "health_replay.py"),
         "--out", str(out)],
        capture_output=True, text=True, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert json.loads(out.read_text()) == committed


def test_save_results_roundtrip(tmp_path):
    import json

    payload = {"platform": "cpu", "n_devices": 8, "allreduce": []}
    path = micro.save_results(payload, outdir=str(tmp_path))
    assert os.path.basename(path).startswith("micro_cpu_8dev_")
    with open(path) as f:
        assert json.load(f) == payload


def test_fit_alpha_beta_exact_line():
    # a perfect alpha-beta line fits back exactly: 2 us + bytes at
    # 1 GB/s (== 1000 bytes/us)
    pts = [(b, 2.0 + b / 1e3) for b in (1e3, 1e4, 1e5, 1e6)]
    alpha, bw = micro.fit_alpha_beta(pts)
    assert abs(alpha - 2.0) < 1e-6
    assert abs(bw - 1.0) < 1e-6


def test_fit_alpha_beta_clamps_degenerate():
    # a tiny sweep can fit a negative intercept / non-positive slope;
    # the result must still be loadable (alpha >= 0, bw > 0)
    alpha, bw = micro.fit_alpha_beta([(16.0, 5.0), (32.0, 4.0)])
    assert alpha >= 0 and bw > 0


def test_measured_ring_crossover_interpolates():
    rows = [
        {"size_mb": 0.1, "butterfly_us": 10.0, "ring_us": 20.0,
         "ring_speedup": 0.5},
        {"size_mb": 1.0, "butterfly_us": 40.0, "ring_us": 30.0,
         "ring_speedup": 1.33},
    ]
    x = micro.measured_ring_crossover(rows)
    # delta goes -10 -> +10 over 0.1..1 MB: crossover at the midpoint
    assert x is not None and 0.5e6 < x < 0.6e6
    # one-device sweeps (speedup None) yield no crossover
    assert micro.measured_ring_crossover(
        [{"size_mb": 1.0, "butterfly_us": 1, "ring_us": 1,
          "ring_speedup": None}]) is None


def test_provenance_block_schema():
    # every --save payload carries the self-description the autotune
    # fitter needs: versions, topology, and the config stamp
    prov = micro.provenance_block("cpu", 8)
    assert set(prov) >= {"jax", "jaxlib", "platform", "n_devices",
                         "topology", "config_stamp"}
    assert prov["platform"] == "cpu" and prov["n_devices"] == 8
    assert len(prov["config_stamp"]) == 12
    int(prov["config_stamp"], 16)  # hex content stamp
    assert "x" in prov["topology"]
    assert micro.MICRO_SCHEMA == "mpx-micro-bench/1"


def test_cost_calibrate_schema_loads_verbatim(tmp_path):
    # the --cost-calibrate output IS the tuning file: build it from
    # real (tiny) sweep rows, save it, and load it through BOTH
    # consumers — the cost-model loader (superset schema accepted) and
    # the config tuning layer — schema drift fails here, fast
    from mpi4jax_tpu.analysis import costmodel
    from mpi4jax_tpu.autotune import validate_tuning_dict

    comm = _world_comm()
    pp = micro.bench_sendrecv_ring(comm, sizes_kb=[0.004, 4], iters=2)
    al = micro.bench_allreduce_algos(comm, sizes_mb=[0.0001], iters=2)
    cm = micro.build_cost_model("cpu", comm.Get_size(), pp, al)
    assert cm["schema"] == costmodel.TUNING_SCHEMA
    assert set(cm["links"]) == {"ici", "dcn"}
    assert cm["provenance"]["n_devices"] == comm.Get_size()
    validate_tuning_dict(cm)  # loads whole as an MPI4JAX_TPU_TUNING file
    if "measured" in cm:
        # the measured crossover doubles as the tuned knob value
        assert cm["tuned"]["ring_crossover_bytes"] == \
            cm["measured"]["ring_crossover_bytes"]
    tf = mpx.load_tuning(cm)
    try:
        assert tf.has_links()
    finally:
        mpx.load_tuning(None)
    path = micro.save_cost_model(cm, outdir=str(tmp_path))
    assert os.path.basename(path).startswith("cost_model_cpu_")
    model = costmodel.model_from_file(path)
    assert model.params["links"]["ici"]["gb_per_s"] > 0
    assert model.source == path
    # and the env-flag route resolves the same file
    saved = os.environ.get("MPI4JAX_TPU_COST_MODEL")
    os.environ["MPI4JAX_TPU_COST_MODEL"] = path
    try:
        loaded = costmodel.load_model(None)
        assert loaded.params["links"]["ici"] == \
            model.params["links"]["ici"]
    finally:
        if saved is None:
            os.environ.pop("MPI4JAX_TPU_COST_MODEL", None)
        else:
            os.environ["MPI4JAX_TPU_COST_MODEL"] = saved
