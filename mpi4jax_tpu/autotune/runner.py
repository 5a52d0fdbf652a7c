"""``mpx.autotune()``: measure the perf knobs on the ACTUAL mesh.

The repo already owns every measurement this needs — the microbench
sweeps of ``benchmarks/micro.py`` (``--fusion-sweep``,
``--overlap-sweep``, the forced butterfly-vs-ring algo sweep,
``--hierarchy-sweep``, and the ``--cost-calibrate`` alpha/beta fit).
This module runs them **as a library** (not a subprocess) under a wall
clock budget, feeds the rows through the pure fitters
(autotune/fit.py), and emits one ``mpx-tuning/1`` file
(autotune/schema.py) that the config layer loads between defaults and
environment (``MPI4JAX_TPU_TUNING`` / ``mpx.load_tuning``):

- **ici alpha/beta** by least squares over the sendrecv ring latency
  sweep (the ``--cost-calibrate`` fit), dcn scaled by the documented
  analytic ratios where there is no real inter-host link to measure;
- **ring crossover** interpolated from the forced butterfly-vs-ring
  sweep (falling back to the alpha-beta closed form when the sweep is
  inconclusive — a tiny budget must still emit a usable file);
- **DCN crossover** from the closed form over the fitted dcn class;
- **per-topology crossover overrides** from the flat-vs-hier sweep;
- **fusion bucket bytes** by sweeping candidate caps through the
  fusion bench and keeping the fastest;
- **overlap chunk counts** per payload bucket by sweeping candidate
  counts through the overlap bench;
- **commit pack throughput** by timing ``resilience.elastic
  .pack_leaves`` on a synthetic state — the measured half of
  ``mpx.elastic.run(commit_every='auto')``.

This is the Horovod-autotuning / NCCL-measured-tables move (PAPERS.md):
selection driven by measured per-link latency/bandwidth instead of byte
heuristics — the difference between "fast on this grid" and "fast on
any pod a fleet scheduler hands you" (ROADMAP item 3).

Offline form: ``python -m mpi4jax_tpu.autotune --budget-s N --save
tuning.json`` (autotune/__main__.py; exit 0 full fit / 1 partial / 2
usage-or-mesh failure, the analysis CLI's contract).
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys
import time
from typing import List, Optional, Tuple

from . import fit
from .schema import SCHEMA, TuningFile

# candidate ladders, tiny-first: each phase climbs its ladder while the
# budget lasts, so a 10-second budget still fits every knob (coarsely)
# and a 10-minute budget refines with larger payloads
P2P_SIZES_KB = (0.004, 4.0, 64.0, 1024.0)
ALGO_SIZES_MB = (0.01, 0.1, 0.5, 1.0, 4.0, 16.0)
# default-first (4 MiB is the shipped default): pick_min breaks ties
# toward the earlier row, and a budget-truncated sweep must compare
# against the default before anything exotic can win
FUSION_BUCKET_CANDIDATES = (4 << 20, 1 << 20, 16 << 20, 1 << 18)
OVERLAP_CHUNK_CANDIDATES = (2, 1, 4)
OVERLAP_SIZES_MB = (0.25, 4.0)

# synthetic state for the pack-throughput probe: big enough that the
# per-call overhead amortizes, small enough for any host
PACK_PROBE_BYTES = 8 << 20


def _load_micro():
    """``benchmarks/micro.py`` as a library.  The benchmarks directory
    is a repo-checkout sibling of the package (not an installed
    module), so resolve it relative to this file and load it by path;
    a pip-installed tree without the checkout gets a clear error."""
    for name in ("micro", "benchmarks.micro"):
        mod = sys.modules.get(name)
        if mod is not None and hasattr(mod, "bench_allreduce_algos"):
            return mod
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        "benchmarks", "micro.py",
    )
    if not os.path.exists(path):
        raise RuntimeError(
            "mpx.autotune needs the microbench library "
            "(benchmarks/micro.py), which ships in the repository "
            f"checkout but was not found at {path!r} — run from a "
            "checkout, or pass pre-captured sweep rows to "
            "build_tuning()"
        )
    spec = importlib.util.spec_from_file_location("_mpx_autotune_micro",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_mpx_autotune_micro"] = mod
    spec.loader.exec_module(mod)
    return mod


def _meter(name: str, n: int = 1) -> None:
    try:
        from ..telemetry.core import meter
    except ImportError:
        return
    meter(name, n)


class _Budget:
    """Wall-clock budget: phases poll ``ok()`` before each (incremental)
    measurement and stop climbing their ladder when time is up.  At
    least one rung of every phase always runs — a too-small budget
    yields a coarse file, never an empty one."""

    def __init__(self, budget_s: float):
        self.budget_s = float(budget_s)
        self.t0 = time.monotonic()

    def elapsed(self) -> float:
        return time.monotonic() - self.t0

    def ok(self) -> bool:
        return self.elapsed() < self.budget_s


class _EnvPatch:
    """Set environment knobs for one candidate measurement, restoring
    the caller's values (not just dropping them) on exit — the same
    discipline the micro sweeps use internally."""

    def __init__(self, **env):
        self.env = {k: str(v) for k, v in env.items()}
        self.saved = {}

    def __enter__(self):
        for k, v in self.env.items():
            self.saved[k] = os.environ.get(k)
            os.environ[k] = v
        return self

    def __exit__(self, *exc):
        for k, old in self.saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        return False


def _world_comm():
    import jax

    import mpi4jax_tpu as mpx

    mesh = mpx.make_world_mesh(devices=jax.devices())
    return mpx.Comm(mesh.axis_names[0], mesh=mesh)


def provenance_block(platform: str, n_devices: int) -> dict:
    """The measurement self-description every emitted artifact carries
    (jax/jaxlib versions, topology string, a content stamp of the whole
    declared-flag surface) — the CANONICAL implementation, shared with
    ``benchmarks/micro.py --save`` captures (micro delegates here so
    the two provenance shapes can never drift)."""
    import jax

    from ..utils import config

    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", "unknown")
    except ImportError:
        jaxlib_version = "unknown"
    stamp = hashlib.sha256(
        repr(config.env_fingerprint()).encode()).hexdigest()[:12]
    topo = config.topology_spec()
    if not topo:
        try:
            procs = jax.process_count()
        except Exception:
            procs = 1
        topo = f"{procs}x{n_devices // max(procs, 1)}"
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "platform": platform,
        "n_devices": n_devices,
        "topology": topo,
        "config_stamp": stamp,
    }


def _provenance(n_devices: int, platform: str, budget: _Budget) -> dict:
    prov = provenance_block(platform, n_devices)
    prov.update({
        "budget_s": budget.budget_s,
        "elapsed_s": round(budget.elapsed(), 2),
        "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
    })
    return prov


def _pack_throughput_gb_s() -> Optional[float]:
    """Measured ``ShardStore`` pack throughput (GB/s) over a synthetic
    state — the commit-cost half of the ``commit_every='auto'`` math
    (the step-time half is measured live by the run loop)."""
    import numpy as np

    from ..resilience.elastic import pack_leaves

    leaves = [np.ones(PACK_PROBE_BYTES // 8 // 4, np.float32)
              for _ in range(8)]
    pack_leaves(leaves)  # warm (allocator, first-touch)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        buf, _meta = pack_leaves(leaves)
        best = min(best, time.perf_counter() - t0)
    if best <= 0 or not buf.nbytes:
        return None
    return buf.nbytes / best / 1e9


def autotune(comm=None, budget_s: float = 60.0, save: Optional[str] = None,
             load: bool = True, topologies: Tuple[str, ...] = (),
             verbose: bool = False):
    """Feedback-directed tuning of every perf knob on the actual mesh.

    Runs the microbench sweeps (as a library) under a ``budget_s`` wall
    clock, fits per-(payload-bucket, topology, link-class) crossovers
    and optima, and returns an :class:`AutotuneResult` whose
    ``.payload`` is a validated ``mpx-tuning/1`` dict.  ``save=`` also
    writes it to a path; ``load=True`` (default) installs it as the
    active tuning layer (``mpx.load_tuning``) so the very next trace
    uses the measured values — the stamp folds into the program-cache
    keys, so everything retraces exactly once.

    ``topologies``: ``MPI4JAX_TPU_TOPOLOGY`` specs to sweep flat-vs-hier
    crossovers for (per-topology overrides); default none — on a real
    multi-host pod the derived topology is already active and the flat
    sweeps measure it.
    """
    budget = _Budget(budget_s)
    if budget_s <= 0:
        raise ValueError(f"budget_s must be > 0, got {budget_s}")
    micro = _load_micro()
    if comm is None:
        comm = _world_comm()
    n = comm.Get_size()
    # the tuning is for the devices the sweeps run on: the comm's mesh
    # (only a scripted comm has none — tests/test_autotune_pure.py)
    platform = ("unknown" if comm.mesh is None
                else comm.mesh.devices.flat[0].platform)
    _meter("autotune.runs")

    def note(msg):
        if verbose:
            print(f"autotune: {msg}", file=sys.stderr)

    tuned: dict = {}
    # ``measured`` carries ONLY sweep-derived values (the advisories
    # cite it as "measured"); closed-form/analytic fallbacks go into
    # ``tuned`` alone, with every knob's origin in ``fit_sources``
    measured: dict = {}
    fit_sources: dict = {}
    topo_overrides: dict = {}
    fitted: List[str] = []
    unfitted: List[str] = []

    # -- phase 1: p2p alpha/beta (the --cost-calibrate fit) ---------------
    pp_rows = []
    for kb in P2P_SIZES_KB:
        pp_rows += micro.bench_sendrecv_ring(comm, sizes_kb=[kb], iters=10)
        if not budget.ok():
            break
    alpha_us, gb_per_s = micro.fit_alpha_beta(
        [(r["size_kb"] * 1e3, r["hop_us"]) for r in pp_rows])
    note(f"ici fit: alpha {alpha_us:.3f} us, {gb_per_s:.2f} GB/s "
         f"({len(pp_rows)} point(s))")

    from ..analysis import costmodel

    defaults = costmodel.DEFAULT_PARAMS
    dcn_alpha = alpha_us * (defaults["links"]["dcn"]["alpha_us"]
                            / defaults["links"]["ici"]["alpha_us"])
    dcn_bw = max(gb_per_s * (defaults["links"]["dcn"]["gb_per_s"]
                             / defaults["links"]["ici"]["gb_per_s"]),
                 0.001)
    links = {
        "ici": {"alpha_us": round(alpha_us, 4),
                "gb_per_s": round(gb_per_s, 4)},
        "dcn": {"alpha_us": round(dcn_alpha, 4),
                "gb_per_s": round(dcn_bw, 4)},
    }
    fitted.append("links")
    _meter("autotune.fits")

    # -- phase 2: ring crossover (measured, closed-form fallback) ---------
    algo_rows = []
    for mb in ALGO_SIZES_MB:
        algo_rows += micro.bench_allreduce_algos(comm, sizes_mb=[mb],
                                                 iters=5)
        # stop early once the ring has clearly crossed over: two
        # consecutive ring wins bound the interpolation from above
        if (len(algo_rows) >= 2
                and all(r["ring_speedup"] and r["ring_speedup"] > 1.0
                        for r in algo_rows[-2:])):
            break
        if not budget.ok():
            break
    ring_x = micro.measured_ring_crossover(algo_rows)
    ring_x_source = "sweep"
    if ring_x is None:
        ring_x = fit.analytic_crossover(alpha_us, gb_per_s, n)
        ring_x_source = "alpha-beta fit"
    if ring_x is not None:
        tuned["ring_crossover_bytes"] = int(ring_x)
        if ring_x_source == "sweep":
            measured["ring_crossover_bytes"] = int(ring_x)
        fit_sources["ring_crossover_bytes"] = ring_x_source
        fitted.append("ring_crossover_bytes")
        _meter("autotune.fits")
        note(f"ring crossover: {ring_x} B ({ring_x_source})")
    else:
        unfitted.append("ring_crossover_bytes")
        note("ring crossover: unfitted (group too small for the ring)")

    # -- phase 3: DCN crossover (closed form over the fitted dcn class) --
    from ..parallel.topology import derive_world_topology

    topo = derive_world_topology(comm)
    hosts = topo.num_hosts if topo is not None else 1
    dcn_x = fit.analytic_crossover(dcn_alpha, dcn_bw, max(hosts, 4))
    if dcn_x is not None:
        # closed form over the SCALED dcn class — never a sweep, so it
        # is tuned but deliberately NOT "measured"
        tuned["dcn_crossover_bytes"] = int(dcn_x)
        fit_sources["dcn_crossover_bytes"] = "alpha-beta fit (scaled dcn)"
        fitted.append("dcn_crossover_bytes")
        _meter("autotune.fits")
        note(f"dcn crossover: {dcn_x} B (alpha-beta fit, h>={max(hosts, 4)})")
    else:
        unfitted.append("dcn_crossover_bytes")

    # -- phase 4: per-topology flat-vs-hier crossovers --------------------
    for spec in topologies:
        if not budget.ok():
            note(f"budget exhausted before topology {spec}")
            break
        hier_rows = micro.bench_hierarchy(
            comm, sizes_mb=tuple(ALGO_SIZES_MB[:4]), topologies=(spec,),
            iters=5)
        x = fit.measured_crossover(hier_rows, "size_mb", "flat_us",
                                   "hier_us")
        if x is not None:
            topo_overrides[spec] = {"ring_crossover_bytes": int(x)}
            fitted.append(f"topologies[{spec}]")
            _meter("autotune.fits")
            note(f"hier crossover @ {spec}: {x} B")

    # -- phase 4b: alltoall crossover (flat vs hier, per topology) --------
    # the permutation-family twin of phase 4: the payload where the
    # two-level alltoall first beats the flat exchange, interpolated
    # from the --alltoall-sweep grid (docs/moe.md).  Swept per requested
    # topology (or the ambient derived one on a real multi-host pod);
    # single-host meshes leave the knob untuned — there is no DCN to
    # aggregate messages over.
    a2a_specs = list(topologies) or ([None] if hosts > 1 else [])
    for spec in a2a_specs:
        if not budget.ok():
            note("budget exhausted before the alltoall crossover sweep")
            break
        a2a_rows = micro.bench_alltoall(
            comm, sizes_mb=tuple(ALGO_SIZES_MB[:4]),
            topologies=(spec,), iters=5)
        x = fit.measured_crossover(a2a_rows, "size_mb", "flat_us",
                                   "hier_us")
        if x is None:
            continue
        if spec is None:
            tuned["alltoall_crossover_bytes"] = int(x)
            measured["alltoall_crossover_bytes"] = int(x)
            fit_sources["alltoall_crossover_bytes"] = "sweep"
            fitted.append("alltoall_crossover_bytes")
        else:
            topo_overrides.setdefault(spec, {})[
                "alltoall_crossover_bytes"] = int(x)
            if "alltoall_crossover_bytes" not in tuned:
                # the first fitted topology also seeds the flat knob so
                # an untopologized consumer still gets a measured value
                tuned["alltoall_crossover_bytes"] = int(x)
                measured["alltoall_crossover_bytes"] = int(x)
                fit_sources["alltoall_crossover_bytes"] = (
                    f"sweep @ {spec}")
                fitted.append("alltoall_crossover_bytes")
            fitted.append(f"alltoall[{spec}]")
        _meter("autotune.fits")
        note(f"alltoall crossover @ {spec or 'ambient'}: {x} B")
    if "alltoall_crossover_bytes" not in tuned:
        unfitted.append("alltoall_crossover_bytes")

    # -- phase 5: fusion bucket bytes -------------------------------------
    bucket_rows = []
    for cand in FUSION_BUCKET_CANDIDATES:
        if bucket_rows and not budget.ok():
            break
        with _EnvPatch(MPI4JAX_TPU_FUSION_BUCKET_BYTES=cand):
            rows = micro.bench_fusion(comm, counts=(16,), size_kb=64,
                                      iters=1)
        bucket_rows.append({"bucket_bytes": cand,
                            "fused_us_per_op": rows[0]["fused_us_per_op"]})
    # a single uncompared candidate is not a fit: leave the knob
    # untuned rather than "tuning" it to whatever rung the budget
    # happened to reach first
    best_bucket = (fit.pick_min(bucket_rows, "bucket_bytes",
                                "fused_us_per_op")
                   if len(bucket_rows) >= 2 else None)
    if best_bucket is not None:
        tuned["fusion_bucket_bytes"] = int(best_bucket[0])
        measured["fusion_bucket_bytes"] = int(best_bucket[0])
        fit_sources["fusion_bucket_bytes"] = "sweep"
        fitted.append("fusion_bucket_bytes")
        _meter("autotune.fits")
        note(f"fusion bucket: {best_bucket[0]} B "
             f"({best_bucket[1]:.2f} us/op)")
    else:
        unfitted.append("fusion_bucket_bytes")

    # -- phase 6: overlap chunks per payload bucket -----------------------
    winners = []
    for mb in OVERLAP_SIZES_MB:
        if winners and not budget.ok():
            break
        per_payload = []
        for cand in OVERLAP_CHUNK_CANDIDATES:
            with _EnvPatch(MPI4JAX_TPU_OVERLAP_CHUNKS=cand):
                rows = micro.bench_overlap(comm, sizes_mb=(mb,), iters=5,
                                           compute_dim=64)
            per_payload.append({"chunks": cand,
                                "overlap_us": rows[0]["overlap_us"]})
        best = fit.pick_min(per_payload, "chunks", "overlap_us")
        if best is not None:
            winners.append((int(mb * 1e6), int(best[0])))
    chunks = fit.chunk_buckets(winners)
    if chunks is not None:
        tuned["overlap_chunks"] = chunks
        fit_sources["overlap_chunks"] = "sweep"
        fitted.append("overlap_chunks")
        _meter("autotune.fits")
        note(f"overlap chunks: {chunks}")
    else:
        unfitted.append("overlap_chunks")

    # -- phase 6b: DCN wire codec vs the error budget ---------------------
    # pick the fastest modeled DCN leg whose MEASURED round-trip error
    # fits MPI4JAX_TPU_COMPRESS_ERROR_BUDGET (docs/compression.md);
    # "off" always fits, so the knob is always recorded — a budget no
    # codec meets tunes compression off explicitly.  Payload-bucketed:
    # legs below the DCN crossover are latency-bound, where shrinking
    # bytes buys nothing, so they stay exact.
    bench_comp = getattr(micro, "bench_compression", None)
    if bench_comp is not None and budget.ok():
        from ..utils import config as _config

        err_budget = _config.compress_error_budget()
        comp_rows = bench_comp(comm, sizes_mb=(1.0,), iters=3)
        best_codec, best_us = "off", None
        for row in comp_rows:
            if row["rel_err"] > err_budget:
                continue
            if best_us is None or row["modeled_dcn_us"] < best_us:
                best_codec, best_us = row["codec"], row["modeled_dcn_us"]
            measured[f"compress_rel_err_{row['codec']}"] = row["rel_err"]
        if best_codec == "off":
            tuned["compress"] = "off"
        else:
            bound = int(tuned.get("dcn_crossover_bytes",
                                  _config.dcn_crossover_bytes()))
            tuned["compress"] = [
                {"max_bytes": bound, "codec": "off"},
                {"max_bytes": None, "codec": best_codec},
            ]
        fit_sources["compress"] = "sweep vs error budget"
        fitted.append("compress")
        _meter("autotune.fits")
        note(f"compress codec: {best_codec} "
             f"(error budget {err_budget:g})")
    else:
        unfitted.append("compress")

    # -- phase 7: commit pack throughput ----------------------------------
    pack = _pack_throughput_gb_s()
    if pack is not None:
        tuned["commit"] = {
            "pack_gb_per_s": round(pack, 4),
            "target_overhead": fit.DEFAULT_COMMIT_OVERHEAD,
        }
        fitted.append("commit")
        _meter("autotune.fits")
        note(f"commit pack throughput: {pack:.2f} GB/s")
    else:
        unfitted.append("commit")

    payload = {
        "schema": SCHEMA,
        "source": (f"mpx.autotune ({platform}, {n} devices, "
                   f"budget {budget.budget_s:g}s)"),
        "links": links,
        "gamma_gb_per_s": defaults["gamma_gb_per_s"],
        "compute_gb_per_s": defaults["compute_gb_per_s"],
        "dispatch_us": defaults["dispatch_us"],
        "tuned": tuned,
        "measured": measured,
        "provenance": dict(_provenance(n, platform, budget),
                           fit_sources=fit_sources),
    }
    if topo_overrides:
        payload["topologies"] = topo_overrides
    tf = TuningFile(payload)  # validates — an unloadable emit is a bug here

    path = None
    if save:
        path = save
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        note(f"saved {path} (tuned@{tf.stamp})")
    if load:
        from ..utils import config

        tf = config.load_tuning(path if path else payload)
    return AutotuneResult(payload=payload, tuning=tf, path=path,
                          fitted=tuple(fitted), unfitted=tuple(unfitted),
                          elapsed_s=budget.elapsed())


class AutotuneResult:
    """What one autotune run produced: the validated payload, the
    (possibly installed) :class:`~.schema.TuningFile`, where it was
    saved, and which knobs were fitted vs left untuned — the CLI's
    exit-code discriminator (0 full / 1 partial)."""

    __slots__ = ("payload", "tuning", "path", "fitted", "unfitted",
                 "elapsed_s")

    def __init__(self, payload, tuning, path, fitted, unfitted, elapsed_s):
        self.payload = payload
        self.tuning = tuning
        self.path = path
        self.fitted = fitted
        self.unfitted = unfitted
        self.elapsed_s = elapsed_s

    @property
    def stamp(self) -> str:
        return self.tuning.stamp

    def __repr__(self):
        return (f"AutotuneResult(tuned@{self.stamp}, "
                f"{len(self.fitted)} fitted, "
                f"{len(self.unfitted)} unfitted, "
                f"{self.elapsed_s:.1f}s)")
