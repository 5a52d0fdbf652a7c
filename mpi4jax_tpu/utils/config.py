"""Environment-variable flag system: the declared registry.

TPU-native analog of the reference's env-flag configuration
(ref: mpi4jax/_src/decorators.py:29-34 truthy parser; mpi4jax/_src/utils.py:175-177
``MPI4JAX_PREFER_NOTOKEN``; mpi4jax/_src/xla_bridge/__init__.py:24-28
``MPI4JAX_DEBUG``).

Every ``MPI4JAX_TPU_*`` variable the library reads is DECLARED in
``FLAGS`` below (name, type, default, docstring) and read through
``_getenv`` — reading an undeclared flag raises here at runtime, and the
in-repo lint pack (tests/test_lint.py) statically rejects any
``os.environ``/``os.getenv``/``parse_env_*`` read of an undeclared
``MPI4JAX_TPU_*`` name anywhere under ``mpi4jax_tpu/``.  The same lint
asserts every declared flag is documented in the docs flag tables
(docs/usage.md / docs/resilience.md).
"""

import math
import os
from typing import NamedTuple, Optional, Tuple


class Flag(NamedTuple):
    """One declared environment flag."""

    name: str
    type: str            # "bool" | "float" | "int" | "str" | "choice"
    default: object
    doc: str
    choices: Optional[Tuple[str, ...]] = None


ANALYZE_MODES = ("off", "warn", "error")
COLLECTIVE_ALGOS = ("auto", "butterfly", "ring", "hier")
COMPRESS_MODES = ("off", "bf16", "fp8", "auto")
TELEMETRY_MODES = ("off", "counters", "events")
FUSION_MODES = ("off", "auto", "force")
ELASTIC_FAIL_UNITS = ("rank", "row", "col")
ELASTIC_PLACEMENTS = ("stripe", "neighbor")
ELASTIC_AGREEMENTS = ("coordinator", "gossip")

# default fusion bucket: 4 MiB — large enough that a typical optimizer
# step's small gradient leaves coalesce into a handful of collectives,
# small enough that packing latency (concat + slice traffic) stays below
# the per-collective dispatch cost it removes (Horovod ships 64 MiB,
# PyTorch DDP 25 MiB; our collectives are in-graph, so the sweet spot is
# smaller — measured by ``benchmarks/micro.py --fusion-sweep``).
DEFAULT_FUSION_BUCKET_BYTES = 4 << 20

# default overlap chunk count: 2 = classic double buffering (while chunk
# i's allgather phase is on the wire, chunk i+1's reduce-scatter can run,
# and independent compute interleaves with both).
DEFAULT_OVERLAP_CHUNKS = 2

# bootstrap retry policy defaults (resilience/retry.py semantics): the
# same policy serves the first `init_distributed` rendezvous AND every
# elastic re-bootstrap after a shrink (resilience/elastic.py), so both
# are declared flags instead of constants buried in call sites
DEFAULT_BOOTSTRAP_DEADLINE = 300.0
DEFAULT_BOOTSTRAP_MAX_ATTEMPTS = 0  # 0 = bounded by the deadline only

# default shard replication budget for the elastic in-memory checkpoint
# (resilience/elastic.py ShardStore): each shard lives on redundancy+1
# ranks, tolerating that many simultaneous rank losses at a memory cost
# of (redundancy+1)/k of the state per rank
DEFAULT_ELASTIC_REDUNDANCY = 1

# default port window for the per-epoch elastic rendezvous ports: the
# coordinator of epoch e listens on port_base + (e % span), so a job
# that churns through hundreds of epochs stays inside a declared
# span-wide window instead of walking out of the ephemeral port range.
# 64 keeps the wrapped ports identical to the unwrapped pre-span scheme
# for the first 64 epochs while bounding the footprint at 5*span ports
# (coordinator / join / two control banks / agreement listener —
# resilience/elastic.py).
DEFAULT_ELASTIC_PORT_SPAN = 64

# default seconds a draining (preempted) rank waits for its peers to
# acknowledge the drain notice before it proceeds to the leave boundary
# (resilience/elastic.py request_drain): long enough for a localhost or
# DCN round trip under load, far below any eviction deadline
DEFAULT_DRAIN_GRACE_S = 5.0

# default size cap of the persistent compiled-program cache
# (mpi4jax_tpu/aot/diskcache.py): 1 GiB — a few hundred lowered+compiled
# SPMD programs at typical sizes; oldest-used entries are evicted first
# once the cap is crossed (docs/aot.md).
DEFAULT_COMPILE_CACHE_MAX_BYTES = 1 << 30

# serving-runtime defaults (mpi4jax_tpu/serving/, docs/serving.md): the
# continuous-batching scheduler admits/evicts between decode megasteps
# against a bucketed batch-shape table (powers of two up to the max
# batch), a KV slot budget, and a p99 latency objective.  Every knob
# here only parameterizes the serving engine's own programs — none of
# them shapes a non-serving trace, so none folds into the generic
# cache tokens (a serving pin captures them through the world stamp
# like every other flag).
DEFAULT_SERVING_MAX_BATCH = 8
DEFAULT_SERVING_UNROLL = 4
DEFAULT_SERVING_SLO_P99_MS = 1000.0

# default ring/butterfly crossover: 1 MiB — below it the butterfly's
# ~2·log2(k) rounds beat the ring's ~2·(k-1) per-round latencies; above it
# the ring's O(size) vs O(size·log k) byte volume dominates.  Measured per
# platform by ``benchmarks/micro.py --save`` (docs/microbenchmarks.md).
DEFAULT_RING_CROSSOVER_BYTES = 1 << 20

# default DCN ring crossover for the inter-host phase of the hierarchical
# lowerings (ops/_hierarchy.py): 4 MiB — a DCN round-trip costs roughly an
# order of magnitude more latency than an ICI hop, so the inter-host ring's
# 2·(h-1) rounds need a correspondingly larger shard before they beat the
# butterfly's 2·ceil(log2 h).  Measured per pod by
# ``benchmarks/micro.py --hierarchy-sweep`` (docs/topology.md).
DEFAULT_DCN_CROSSOVER_BYTES = 4 << 20

# default crossover for the two-level hierarchical alltoall
# (ops/_hierarchy.apply_hier_alltoall): 1 MiB — below it the single
# monolithic AllToAll HLO's latency wins; above it the hierarchical
# split's intra-host aggregation pays for itself by cutting the DCN
# message count to 1/r of flat (r·h·(h−1) contiguous host-aggregated
# messages instead of r²·h·(h−1) per-rank ones — docs/moe.md).
# Measured per pod by ``benchmarks/micro.py --alltoall-sweep``.
DEFAULT_ALLTOALL_CROSSOVER_BYTES = 1 << 20

# default relative-error budget for the autotune codec sweep
# (autotune/runner.py compression phase): the cheapest codec whose
# measured round-trip relative error stays under this bound is the one
# recorded in the tuning file.  1e-2 admits fp8's per-chunk-scaled
# quantization on typical gradient distributions while rejecting it for
# payloads whose dynamic range blows the 4-bit exponent; bf16 (rel err
# ~2^-8) always clears it.
DEFAULT_COMPRESS_ERROR_BUDGET = 1e-2

# default capacity-chunk count of the expert-parallel MoE helper
# (parallel/moe.py): the per-expert compute and the combine-alltoall
# split into this many capacity chunks so chunk i's combine exchange
# (issued via alltoall_start) overlaps chunk i+1's expert MLP — the
# same double-buffering default as MPI4JAX_TPU_OVERLAP_CHUNKS.
DEFAULT_MOE_CAPACITY_CHUNKS = 2

# pipeline-parallel schedule knobs (parallel/pipeline.py).  0 means
# "unset": split_microbatches falls back to no splitting and
# PipelineProgram derives the interleaved virtual-stage count from the
# stage-function list.  Tuned values (mpx-tuning/1 knob records) are
# always >= 1.
DEFAULT_PIPELINE_MICROBATCHES = 0
DEFAULT_PIPELINE_VIRTUAL_STAGES = 0

FLAGS = {
    f.name: f
    for f in (
        Flag("MPI4JAX_TPU_DEBUG", "bool", False,
             "Per-op debug logging (``r{rank} | {id} | ...`` format)."),
        Flag("MPI4JAX_TPU_TRACE", "bool", False,
             "Native runtime op tracing: host-side begin/end log lines "
             "with measured wall-clock latency per collective, via the "
             "C++ host-hooks library (see mpi4jax_tpu/native.py)."),
        Flag("MPI4JAX_TPU_PREFER_NOTOKEN", "bool", False,
             "Make the token API delegate to the notoken "
             "(implicit-ordering) implementation."),
        Flag("MPI4JAX_TPU_NO_WARN_JAX_VERSION", "bool", False,
             "Silence the JAX version advisory."),
        Flag("MPI4JAX_TPU_WATCHDOG_TIMEOUT", "float", None,
             "Collective watchdog (resilience/watchdog.py): seconds a "
             "single collective may stay in flight before the process is "
             "killed with per-rank in-flight-op diagnostics.  Unset/0 "
             "disables."),
        Flag("MPI4JAX_TPU_FAULT_SPEC", "str", "",
             "Deterministic fault injection (resilience/faultinject.py): "
             "semicolon-separated clauses, e.g. "
             "``delay:rank=1:op=allreduce:after=3:secs=2``.  Empty "
             "disables."),
        Flag("MPI4JAX_TPU_BOOTSTRAP_DEADLINE", "float",
             DEFAULT_BOOTSTRAP_DEADLINE,
             "Total seconds the ``init_distributed`` coordinator "
             "rendezvous (and every elastic re-bootstrap after a "
             "shrink) may spend retrying before failing with a clear "
             "error (resilience/retry.py).  Default 300."),
        Flag("MPI4JAX_TPU_BOOTSTRAP_MAX_ATTEMPTS", "int",
             DEFAULT_BOOTSTRAP_MAX_ATTEMPTS,
             "Attempt cap for the bootstrap retry policy; 0 (default) "
             "bounds retries by the deadline only.  Applies to "
             "``init_distributed`` and elastic re-bootstrap alike."),
        Flag("MPI4JAX_TPU_ELASTIC_REDUNDANCY", "int",
             DEFAULT_ELASTIC_REDUNDANCY,
             "Replication budget of the elastic in-memory shard "
             "checkpoint (resilience/elastic.py ShardStore): each state "
             "shard is copied to this many neighbor ranks beyond its "
             "owner, so this many SIMULTANEOUS rank losses are "
             "recoverable.  Memory cost per rank is (redundancy+1)/k of "
             "the registered state.  Default 1."),
        Flag("MPI4JAX_TPU_ELASTIC_GROW", "bool", False,
             "Elastic grow (resilience/elastic.py): accept replacement "
             "ranks back into the world.  The current coordinator "
             "listens for join requests and ``mpx.elastic.run`` admits "
             "joiners at commit boundaries (epoch advance + cold-join "
             "state restore).  Off (default) keeps the run loop free of "
             "the per-boundary join poll and the lowered HLO "
             "byte-identical to a build without the grow path."),
        Flag("MPI4JAX_TPU_DRAIN_GRACE_S", "float", DEFAULT_DRAIN_GRACE_S,
             "Graceful-drain notice window in seconds "
             "(resilience/elastic.py request_drain): how long a leaving "
             "rank waits for every peer to acknowledge its drain notice "
             "before stepping to the leave boundary.  Also the default "
             "grace of the ``preempt`` fault verb.  Default 5."),
        Flag("MPI4JAX_TPU_ELASTIC_FAIL_UNIT", "choice", "rank",
             "Granularity of an elastic shrink "
             "(parallel/mesh.shrink_world_mesh): ``rank`` (default) "
             "removes exactly the failed ranks and requires a 1-D mesh; "
             "``row``/``col`` remove every WHOLE grid row/column that "
             "contains a failed rank, so Cartesian (tensor x data) "
             "meshes shrink structurally instead of erroring "
             "(docs/resilience.md 'Grow and graceful drain').",
             choices=ELASTIC_FAIL_UNITS),
        Flag("MPI4JAX_TPU_ELASTIC_PLACEMENT", "choice", "stripe",
             "Shard-replica placement policy for the elastic ShardStore "
             "(resilience/elastic.py): ``stripe`` (default) consults the "
             "host topology so every replica lands on a different host "
             "than the shard's owner — a whole-host loss leaves >=1 live "
             "copy of every shard whenever redundancy >= 1 and hosts >= "
             "2; ``neighbor`` is the classic ring (shard s on ranks "
             "s..s+redundancy mod k).  Without topology information "
             "stripe degrades to neighbor.  Host-side only (never folded "
             "into compiled-program cache keys) but MUST match across "
             "processes — commits record the table in force, and "
             "restores follow the recorded table "
             "(docs/resilience.md 'Replica placement').",
             choices=ELASTIC_PLACEMENTS),
        Flag("MPI4JAX_TPU_ELASTIC_AGREEMENT", "choice", "coordinator",
             "Failure-agreement transport (resilience/elastic.py): "
             "``coordinator`` (default) routes suspect reports through "
             "the epoch coordinator (rank 0) — O(k) connections, with "
             "automatic degradation to peer gossip when the coordinator "
             "is itself a suspect or unreachable; ``gossip`` forces the "
             "all-pairs O(k^2) peer exchange everywhere.  Both converge "
             "to the same pure gossip_agreement fixpoint.  Host-side "
             "only but MUST match across processes "
             "(docs/resilience.md 'Failure agreement').",
             choices=ELASTIC_AGREEMENTS),
        Flag("MPI4JAX_TPU_ELASTIC_PORT_SPAN", "int",
             DEFAULT_ELASTIC_PORT_SPAN,
             "Width of the per-epoch elastic port window: epoch e's "
             "coordinator (and join/control listeners) derive their "
             "ports from ``port_base + (e % span)`` instead of the "
             "unbounded ``port_base + e``, so long-churning jobs never "
             "walk out of the ephemeral range (bind collisions are "
             "absorbed by the bootstrap retry policy).  Default 64."),
        Flag("MPI4JAX_TPU_CHECK_NUMERICS", "bool", False,
             "Abort (via the ``abort_if`` fail-fast path) when a "
             "collective's inputs or outputs contain NaN/Inf, naming the "
             "op (resilience/numerics.py).  When off, the lowered HLO is "
             "byte-identical to a build without the guards."),
        Flag("MPI4JAX_TPU_COLLECTIVE_ALGO", "choice", "auto",
             "Reduction-family algorithm (ops/_algos.py): ``auto`` picks "
             "per call from static payload bytes, group size, and host "
             "topology; ``butterfly``/``ring``/``hier`` force one "
             "lowering (``hier`` = the two-level ICI/DCN lowering of "
             "ops/_hierarchy.py, falling back to flat where "
             "inexpressible).",
             choices=COLLECTIVE_ALGOS),
        Flag("MPI4JAX_TPU_RING_CROSSOVER_BYTES", "int",
             DEFAULT_RING_CROSSOVER_BYTES,
             "Payload size (bytes) at which ``auto`` switches from the "
             "log-depth butterfly to the bandwidth-optimal ring "
             "lowerings.  Default 1 MiB."),
        Flag("MPI4JAX_TPU_TOPOLOGY", "str", "",
             "Host-topology override for the hierarchical collective "
             "layer (parallel/topology.py): ``<hosts>x<ranks_per_host>`` "
             "(e.g. ``2x4``) for uniform pods, or comma-separated "
             "per-host rank counts (e.g. ``3,5``) for heterogeneous "
             "clusters.  Empty (default) derives the topology from the "
             "JAX process layout of the bound mesh.  A spec whose total "
             "rank count does not match a communicator's world falls "
             "back to the flat (single-level) algorithms for that comm "
             "(docs/topology.md)."),
        Flag("MPI4JAX_TPU_DCN_CROSSOVER_BYTES", "int",
             DEFAULT_DCN_CROSSOVER_BYTES,
             "Shard size (bytes) at which the hierarchical lowerings' "
             "inter-host (DCN) phase switches from the log-depth "
             "butterfly to the bandwidth-optimal ring.  Default 4 MiB "
             "(DCN rounds cost ~10x an ICI hop, so the ring needs a "
             "larger payload to win than on ICI)."),
        Flag("MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES", "int",
             DEFAULT_ALLTOALL_CROSSOVER_BYTES,
             "Payload size (bytes) at which a multi-host alltoall "
             "switches from the flat single-exchange lowering to the "
             "two-level hierarchical one (ops/_hierarchy.py: intra-host "
             "transpose over ICI, inter-host exchange of host-aggregated "
             "contiguous blocks over DCN — 1/r the DCN message count of "
             "flat).  Default 1 MiB; bit-identical results either way "
             "(docs/moe.md)."),
        Flag("MPI4JAX_TPU_COMPRESS", "choice", "off",
             "Wire compression for the inter-host (DCN) leg of the "
             "hierarchical lowerings (ops/_compress.py): ``bf16`` casts "
             "float32 DCN payloads to bfloat16 on the wire (2x fewer "
             "bytes), ``fp8`` quantizes to float8 with a per-chunk "
             "scale (~3.7x fewer), ``auto`` takes the tuning layer's "
             "measured pick (bf16 without one).  ICI stays exact in "
             "every mode; compressed results are NOT bit-identical to "
             "the exact run — pair with the error-feedback API "
             "(mpx.compress.ef_allreduce) for unbiased training "
             "(docs/compression.md).  ``off`` (default) keeps cache "
             "tokens and HLO byte-identical to a build without the "
             "codec layer.",
             choices=COMPRESS_MODES),
        Flag("MPI4JAX_TPU_COMPRESS_ERROR_BUDGET", "float",
             DEFAULT_COMPRESS_ERROR_BUDGET,
             "Relative-error budget of the autotune codec sweep "
             "(``mpx.autotune()`` compression phase): the cheapest codec "
             "whose measured round-trip relative error stays under this "
             "bound becomes the tuned ``compress`` knob.  Default 1e-2 "
             "(docs/compression.md)."),
        Flag("MPI4JAX_TPU_MOE_CAPACITY_CHUNKS", "int",
             DEFAULT_MOE_CAPACITY_CHUNKS,
             "Capacity-chunk count of the expert-parallel MoE helper "
             "(parallel/moe.py): expert compute and the combine-alltoall "
             "split into this many chunks so chunk i's combine exchange "
             "(alltoall_start) overlaps chunk i+1's expert MLP.  1 "
             "disables the overlap pipeline (one synchronous combine).  "
             "Default 2 (docs/moe.md)."),
        Flag("MPI4JAX_TPU_PIPELINE_MICROBATCHES", "int",
             DEFAULT_PIPELINE_MICROBATCHES,
             "Microbatch count of the pipeline schedule compiler "
             "(``mpx.pipeline``, parallel/pipeline.py): "
             "``split_microbatches`` slices the global batch into this "
             "many microbatches when no explicit count is passed.  0 "
             "(default) means unset — the tuned ``pipeline_microbatches`` "
             "knob applies if a tuning file is loaded, else no split "
             "(docs/pipeline.md)."),
        Flag("MPI4JAX_TPU_PIPELINE_VIRTUAL_STAGES", "int",
             DEFAULT_PIPELINE_VIRTUAL_STAGES,
             "Virtual stage-chunk count per rank of the interleaved "
             "pipeline schedule (``mpx.pipeline(..., "
             "schedule='interleaved')``).  0 (default) means unset — the "
             "tuned ``pipeline_virtual_stages`` knob applies if a tuning "
             "file is loaded, else the count is derived from the "
             "stage-function list (docs/pipeline.md)."),
        Flag("MPI4JAX_TPU_ANALYZE", "choice", "off",
             "Trace-time collective verifier (analysis/): ``warn`` runs "
             "the MPX checkers over every spmd region / eager op as it "
             "traces and warns on findings; ``error`` raises "
             "``AnalysisError`` instead.  ``off`` (default) records "
             "nothing; the lowered HLO is byte-identical in every mode.",
             choices=ANALYZE_MODES),
        Flag("MPI4JAX_TPU_TUNING", "str", "",
             "Tuning layer (mpi4jax_tpu/autotune/, docs/autotune.md): a "
             "``mpx-tuning/1`` JSON file — what ``mpx.autotune()`` / "
             "``python -m mpi4jax_tpu.autotune`` emits — loaded as a "
             "configuration layer between the static defaults and the "
             "environment: a knob's tuned value applies unless its own "
             "flag is explicitly set (default < tuning < env).  Serves "
             "measured ring/DCN crossovers, fusion bucket bytes, and "
             "overlap chunk counts per (payload, topology) bucket, plus "
             "the cost-model alpha/beta section when "
             "``MPI4JAX_TPU_COST_MODEL`` is unset.  The file's content "
             "stamp folds into every compiled-program cache key, so "
             "loading or changing a file retraces; empty (default) "
             "keeps cache keys and HLO byte-identical to a build "
             "without the tuning layer.  ``mpx.load_tuning(path)`` is "
             "the programmatic form (it wins over this flag)."),
        Flag("MPI4JAX_TPU_COST_MODEL", "str", "",
             "Tuning file for the static communication cost model "
             "(analysis/costmodel.py): a JSON file with measured "
             "alpha/beta parameters per link class (the "
             "``benchmarks/micro.py --cost-calibrate`` output schema, "
             "``mpx-cost-model/1``).  Empty (default) keeps the "
             "documented analytic defaults.  When set, "
             "``mpx.analyze(..., cost=True)`` predicts with measured "
             "numbers and the MPX111/MPX113 advisories cite the "
             "measured crossovers instead of the static env defaults "
             "(docs/analysis.md 'Cost model')."),
        Flag("MPI4JAX_TPU_ANALYZE_COST", "choice", "off",
             "Cost pass of the ambient verifier "
             "(``MPI4JAX_TPU_ANALYZE=warn|error`` + the analysis CLI's "
             "``--cost``): ``on`` extends every cross-rank schedule "
             "pass into the critical-path timing simulation and "
             "attaches ``Report.cost`` (predicted step time, per-op / "
             "per-link-class breakdown, MPX131-MPX135 advisories).  "
             "``off`` (default) keeps reports, cache keys, and HLO "
             "byte-identical to a build without the cost model.",
             choices=("off", "on")),
        Flag("MPI4JAX_TPU_ANALYZE_RANKS", "str", "auto",
             "Cross-rank schedule verification (analysis/crossrank.py) "
             "under ``MPI4JAX_TPU_ANALYZE=warn|error``: each spmd "
             "region is re-traced once per rank at trace time and the "
             "per-rank schedules are matched for deadlock/progress "
             "(MPX120-MPX125).  ``auto`` (default) runs the pass "
             "whenever the comm's size is statically known; ``off`` "
             "disables it; a positive integer N runs it only for comms "
             "of at most N ranks (a cost cap — the pass re-traces once "
             "per rank).  ``python -m mpi4jax_tpu.analysis --ranks N`` "
             "sets this."),
        Flag("MPI4JAX_TPU_TELEMETRY", "choice", "off",
             "Runtime telemetry tier (telemetry/): ``counters`` keeps "
             "host-side per-(op, comm, algo, dtype) call/byte counters "
             "and infrastructure meters (zero device-side ops — the "
             "lowered HLO stays byte-identical to ``off``); ``events`` "
             "additionally journals host-side begin/end brackets around "
             "every collective (per-rank latency + arrival timestamps, "
             "JSONL under ``MPI4JAX_TPU_TELEMETRY_DIR``).  ``off`` "
             "(default) collects nothing.",
             choices=TELEMETRY_MODES),
        Flag("MPI4JAX_TPU_TELEMETRY_DIR", "str", "",
             "Directory for the ``events``-tier per-process JSONL "
             "journals (telemetry/journal.py); merged across ranks by "
             "``python -m mpi4jax_tpu.telemetry merge``.  Empty "
             "(default) keeps the journal in memory only."),
        Flag("MPI4JAX_TPU_FUSION", "choice", "off",
             "Collective fusion (ops/_fusion.py): ``auto`` coalesces "
             "adjacent same-(op, comm, reduction, root) small "
             "collectives inside a managed parallel region into one "
             "flat-buffer collective per dtype bucket (Horovod-style "
             "tensor fusion); ``force`` additionally ignores the bucket "
             "byte cap and packs single-member buckets through the "
             "flat-buffer path.  ``off`` (default) keeps the lowered "
             "HLO byte-identical to a build without the fusion layer.",
             choices=FUSION_MODES),
        Flag("MPI4JAX_TPU_FUSION_BUCKET_BYTES", "int",
             DEFAULT_FUSION_BUCKET_BYTES,
             "Byte cap per fusion bucket (per dtype): a bucket closes "
             "when adding the next member would exceed it.  Default "
             "4 MiB."),
        Flag("MPI4JAX_TPU_COMPILE_CACHE_DIR", "str", "",
             "Persistent compiled-program cache directory "
             "(mpi4jax_tpu/aot/diskcache.py): lowered+compiled SPMD "
             "programs — ``mpx.compile`` pins and ``mpx.spmd`` "
             "program-cache misses — are serialized here keyed by "
             "(jaxpr fingerprint, mesh/topology, dynamic cache token, "
             "jax/jaxlib/libtpu versions), so repeated cold starts and "
             "every rank of a multi-host job deserialize instead of "
             "re-lowering identical programs.  Empty (default) disables "
             "the persistent tier entirely — cache keys and HLO are "
             "byte-identical to a build without the AOT layer "
             "(docs/aot.md)."),
        Flag("MPI4JAX_TPU_COMPILE_CACHE_MAX_BYTES", "int",
             DEFAULT_COMPILE_CACHE_MAX_BYTES,
             "Byte cap of the persistent compiled-program cache: after "
             "each write, least-recently-used artifacts are evicted "
             "until the cache fits.  Default 1 GiB; 0 disables "
             "eviction (unbounded)."),
        Flag("MPI4JAX_TPU_OVERLAP_CHUNKS", "int",
             DEFAULT_OVERLAP_CHUNKS,
             "Chunk count for the async start/wait collectives "
             "(ops/_async.py): the payload splits into this many "
             "independent double-buffered ring pipelines so the XLA "
             "scheduler can interleave independent compute between "
             "chunk phases.  Default 2."),
        Flag("MPI4JAX_TPU_UNROLL_DEFAULT", "int", 1,
             "Default megastep unroll factor (parallel/megastep.py): "
             "``mpx.spmd`` / ``mpx.compile`` calls without an explicit "
             "``unroll=`` keep N step iterations device-resident per "
             "host dispatch by rewriting the step body into a "
             "``lax.fori_loop`` carry (docs/aot.md 'Megastep "
             "execution').  1 (default) disables the rewrite — the "
             "traced body and HLO are byte-identical to a build "
             "without the megastep layer."),
        Flag("MPI4JAX_TPU_SERVING_MAX_BATCH", "int",
             DEFAULT_SERVING_MAX_BATCH,
             "Serving runtime (mpi4jax_tpu/serving/, docs/serving.md): "
             "the continuous-batching scheduler's decode batch cap — the "
             "largest bucket in the batch-shape table, and the most "
             "sequences resident in one decode megastep.  Default 8."),
        Flag("MPI4JAX_TPU_SERVING_BUCKETS", "str", "",
             "Explicit serving batch-bucket table: comma-separated "
             "ascending batch sizes (e.g. ``1,2,4,8``); every live batch "
             "is padded UP to the smallest covering bucket so each "
             "(bucket, phase) maps to exactly ONE pinned program.  Empty "
             "(default) uses powers of two up to "
             "MPI4JAX_TPU_SERVING_MAX_BATCH (docs/serving.md)."),
        Flag("MPI4JAX_TPU_SERVING_KV_SLOTS", "int", 0,
             "KV-cache slot budget of the serving runtime: how many "
             "sequences can hold device KV state at once (admission "
             "blocks when no slot is free; eviction frees slots without "
             "reshaping the pinned programs — slots are scatter-updated "
             "rows).  0 (default) sizes the pool at twice the max "
             "batch."),
        Flag("MPI4JAX_TPU_SERVING_UNROLL", "int", DEFAULT_SERVING_UNROLL,
             "Decode megastep trip count of the serving runtime: each "
             "pinned decode call runs this many device-resident token "
             "steps (mpx.compile unroll=N), and the scheduler "
             "admits/evicts only at megastep boundaries — the "
             "granularity/dispatch-amortization trade of docs/serving.md. "
             " Default 4."),
        Flag("MPI4JAX_TPU_SERVING_SLO_P99_MS", "float",
             DEFAULT_SERVING_SLO_P99_MS,
             "The serving latency objective: the p99 request latency "
             "bound (milliseconds) the serving metric is reported "
             "against (tokens/s/chip AT this p99 bound — "
             "BENCH_serving.json), and the bound the CI serving lane "
             "asserts.  Default 1000."),
        Flag("MPI4JAX_TPU_HEALTH", "choice", "off",
             "Runtime health plane (mpi4jax_tpu/telemetry/health.py, "
             "docs/observability.md 'Runtime health'): ``on`` arms the "
             "flight-recorder ring, the online degradation detector at "
             "megastep/commit boundaries, and postmortem bundles under "
             "MPI4JAX_TPU_TELEMETRY_DIR.  ``off`` (default) keeps HLO "
             "and both program-cache tokens byte-identical to a build "
             "without the health plane — the layer is host-side only.",
             choices=("off", "on")),
        Flag("MPI4JAX_TPU_HEALTH_INTERVAL", "int", 1,
             "Boundary stride of the health detector's cross-rank digest "
             "exchange: every N-th megastep/commit boundary runs one "
             "tiny allgather of per-(op, comm) latency-digest summaries "
             "and the slowdown/skew checks.  Default 1 (every "
             "boundary)."),
        Flag("MPI4JAX_TPU_FLIGHT_RING", "int", 1024,
             "Capacity (records) of the flight-recorder ring: the most "
             "recent op begin/end/incident records kept in memory for "
             "``mpx.telemetry.flight_snapshot()`` and postmortem "
             "bundles.  Older records are overwritten; the ring's "
             "dropped count says how many.  Default 1024."),
        Flag("MPI4JAX_TPU_HEALTH_SUSPECTS", "bool", False,
             "Opt-in straggler handoff: let the health detector post "
             "persistent stragglers (and stalled in-flight collectives) "
             "as suspects into the elastic agreement machinery "
             "(resilience/elastic.py), so the elastic plane can act on "
             "slow-but-alive ranks.  Default off — detection only "
             "journals incidents and bumps meters."),
        Flag("MPI4JAX_TPU_HEALTH_PROM", "bool", False,
             "Write the Prometheus exposition rendering "
             "(``mpx.telemetry.prometheus_text()``) to "
             "``prom-p<process>.prom`` under MPI4JAX_TPU_TELEMETRY_DIR "
             "at every detector boundary, for file-based fleet "
             "scrapers.  Default off — the text surface is still "
             "available on demand."),
    )
}

# ---------------------------------------------------------------------------
# configuration epoch + environment fingerprint (the dispatch fast path)
# ---------------------------------------------------------------------------
#
# Every compiled-program cache key folds in ~10 dynamically-read flags so
# that toggling one retraces.  Re-parsing them on EVERY dispatch made the
# cache-hit path pay float/choice/fault-spec parsing per call.  Instead,
# the parsed token is memoized against a cheap *stamp*:
#
# - ``env_fingerprint()`` — the raw (unparsed) values of every declared
#   flag, one dict read each: catches environment mutation;
# - ``config_epoch()`` — a counter bumped by every programmatic override
#   (``set_watchdog_timeout``, ``set_analyze_mode``, ``set_logging``, ...):
#   catches non-environment configuration.
#
# The memoized consumer (ops/_base._dynamic_state, resilience plan_for)
# recomputes only when the stamp changes.

FLAG_NAMES = tuple(FLAGS)

_config_epoch = 0


def config_epoch() -> int:
    return _config_epoch


def bump_config_epoch() -> None:
    """Invalidate every stamp-memoized configuration consumer.  Called by
    each programmatic ``set_*`` override; environment mutation needs no
    bump (the fingerprint sees it)."""
    global _config_epoch
    _config_epoch += 1


def env_fingerprint() -> tuple:
    """Raw values of every declared flag — no parsing, one read each."""
    return tuple(map(os.environ.get, FLAG_NAMES))


def config_stamp() -> tuple:
    """Cheap change detector for the whole flag surface: memoize parsed
    configuration against this and the parsing cost leaves the per-call
    dispatch path."""
    return (_config_epoch, env_fingerprint())

# ---------------------------------------------------------------------------
# the tuning layer (feedback-directed configuration — docs/autotune.md)
# ---------------------------------------------------------------------------
#
# ``mpx.autotune()`` measures the perf knobs on the actual mesh and emits
# an ``mpx-tuning/1`` file (autotune/schema.py); this layer serves its
# values BETWEEN the static defaults and the environment:
#
#     default  <  tuning file  <  explicitly-set env flag
#
# so a fleet pre-tuned file never overrides an operator's deliberate
# override.  The active file resolves from ``load_tuning()`` (wins) or
# ``MPI4JAX_TPU_TUNING``; its content stamp folds into
# ``ops/_algos.algo_cache_token()`` — and through it into both
# compiled-program cache keys — so loading or changing a file retraces.
# With no file active every getter below returns exactly its pre-layer
# value and the stamp contributes nothing: cache keys and HLO stay
# byte-identical (pinned by tests/test_autotune.py).

_tuning_override = None  # autotune.schema.TuningFile set by load_tuning()


def load_tuning(spec=None):
    """Install a tuning layer programmatically: ``spec`` is a file path,
    a parsed ``mpx-tuning/1`` payload dict, or a ``TuningFile``.
    ``None`` clears the programmatic layer (an ``MPI4JAX_TPU_TUNING``
    env file, if set, becomes active again).  Returns the installed
    ``TuningFile`` (or ``None``).  Bumps the config epoch so every
    stamp-memoized consumer — and with it both program caches —
    retraces."""
    global _tuning_override
    if spec is None:
        _tuning_override = None
        bump_config_epoch()
        return None
    from ..autotune.schema import as_tuning

    # fresh=True: a path is RE-READ even if the env route memoized it —
    # this call is the documented way to pick up an edited file, and
    # the epoch bump below retraces every consumer consistently
    tf = as_tuning(spec, fresh=True)
    _tuning_override = tf
    bump_config_epoch()
    try:  # meter the load (no-op when telemetry is off)
        from ..telemetry.core import meter

        meter("autotune.loads")
    except ImportError:  # isolated loaders without the telemetry package
        pass
    return tf


def active_tuning():
    """The active ``TuningFile``, or ``None`` when no layer is loaded.
    Raises ``ValueError`` on a malformed ``MPI4JAX_TPU_TUNING`` file —
    a typo'd path must not silently run untuned."""
    if _tuning_override is not None:
        return _tuning_override
    path = (_getenv("MPI4JAX_TPU_TUNING") or "").strip()
    if not path:
        return None
    from ..autotune.schema import load_tuning_file_memo

    return load_tuning_file_memo(path)


def tuning_stamp() -> Optional[str]:
    """Content stamp of the active tuning layer (the ``tuned@<stamp>``
    provenance tag), or ``None`` when inactive — the cache-key
    contribution (ops/_algos.algo_cache_token)."""
    tf = active_tuning()
    return tf.stamp if tf is not None else None


def _tuned_knob(name: str, payload_bytes: Optional[int] = None):
    """The active layer's value for one knob (``None`` = untuned),
    resolved per the current topology override and payload bucket.
    Callers apply the env-wins precedence BEFORE consulting this."""
    tf = active_tuning()
    if tf is None:
        return None
    return tf.knob(name, topology=topology_spec() or None,
                   payload_bytes=payload_bytes)


def tuning_snapshot() -> Optional[dict]:
    """JSON-able view of the active layer for telemetry
    (telemetry/core.snapshot -> report's "tuning" section): stamp,
    source path, and per-knob tuned / default / effective values with
    an ``env_wins`` marker where an explicit flag overrides the file.
    ``None`` when the layer is inactive (the snapshot then carries no
    tuning payload at all)."""
    try:
        tf = active_tuning()
    except ValueError:
        return None
    if tf is None:
        return None
    from ..autotune.schema import KNOB_FLAGS

    defaults = {
        "ring_crossover_bytes": DEFAULT_RING_CROSSOVER_BYTES,
        "dcn_crossover_bytes": DEFAULT_DCN_CROSSOVER_BYTES,
        "alltoall_crossover_bytes": DEFAULT_ALLTOALL_CROSSOVER_BYTES,
        "fusion_bucket_bytes": DEFAULT_FUSION_BUCKET_BYTES,
        "overlap_chunks": DEFAULT_OVERLAP_CHUNKS,
        "compress": "off",
        "pipeline_microbatches": DEFAULT_PIPELINE_MICROBATCHES,
        "pipeline_virtual_stages": DEFAULT_PIPELINE_VIRTUAL_STAGES,
    }
    getters = {
        "ring_crossover_bytes": ring_crossover_bytes,
        "dcn_crossover_bytes": dcn_crossover_bytes,
        "alltoall_crossover_bytes": alltoall_crossover_bytes,
        "fusion_bucket_bytes": fusion_bucket_bytes,
        "overlap_chunks": overlap_chunks,
        "compress": compress_mode,
        "pipeline_microbatches": pipeline_microbatches,
        "pipeline_virtual_stages": pipeline_virtual_stages,
    }
    knobs = {}
    for name, flag in KNOB_FLAGS.items():
        raw = _getenv(flag)
        env_wins = raw is not None and bool(raw.strip())
        tuned = tf.knob(name, topology=topology_spec() or None)
        knobs[name] = {
            "tuned": tuned,
            "default": defaults[name],
            "effective": getters[name](),
            "env_wins": env_wins,
        }
    return {
        "stamp": tf.stamp,
        "path": tf.path,
        "knobs": knobs,
        "commit": dict(tf.payload.get("tuned", {}).get("commit", {})),
    }


TRUTHY = ("true", "1", "on", "yes")
FALSY = ("false", "0", "off", "no", "")


def _getenv(name: str) -> Optional[str]:
    """The single environment read point: the flag must be declared."""
    if name not in FLAGS:
        raise RuntimeError(
            f"environment flag {name} is not declared in "
            "mpi4jax_tpu.utils.config.FLAGS; declare it (name, type, "
            "default, docstring) before reading it"
        )
    return os.environ.get(name)


def parse_env_bool(name: str, default: bool = False) -> bool:
    """Parse a truthy/falsy environment variable.

    Raises ``ValueError`` on unrecognized values, like the reference's
    truthy/falsy parser (ref: mpi4jax/_src/decorators.py:29-34).
    """
    raw = _getenv(name)
    if raw is None:
        return default
    val = raw.lower().strip()
    if val in TRUTHY:
        return True
    if val in FALSY:
        return False
    raise ValueError(
        f"Environment variable {name}={raw!r} could not be parsed as a boolean "
        f"(truthy values: {TRUTHY}, falsy values: {FALSY})"
    )


def debug_enabled() -> bool:
    return parse_env_bool("MPI4JAX_TPU_DEBUG", False)


def trace_enabled() -> bool:
    return parse_env_bool("MPI4JAX_TPU_TRACE", False)


def parse_env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    """Parse a non-negative finite float environment variable (empty/unset ->
    ``default``)."""
    raw = _getenv(name)
    if raw is None or not raw.strip():
        return default
    try:
        val = float(raw)
    except ValueError as e:
        raise ValueError(
            f"Environment variable {name}={raw!r} could not be parsed as a "
            "number of seconds"
        ) from e
    # NaN would pass a plain `val < 0` check and then silently defeat every
    # comparison downstream (a NaN watchdog timeout never expires while
    # still instrumenting each op); Inf is equally meaningless as seconds
    if not math.isfinite(val) or val < 0:
        raise ValueError(
            f"Environment variable {name}={raw!r} must be a finite "
            "number >= 0"
        )
    return val


def _parse_env_choice(name: str) -> str:
    """Parse a declared choice-typed flag (empty/unset -> default)."""
    flag = FLAGS[name]
    raw = _getenv(name)
    if raw is None or not raw.strip():
        return flag.default
    val = raw.lower().strip()
    if val not in flag.choices:
        raise ValueError(
            f"Environment variable {name}={raw!r} must be one of "
            f"{flag.choices}"
        )
    return val


def watchdog_timeout() -> Optional[float]:
    """Collective watchdog timeout in seconds; ``None`` = disabled.

    ``MPI4JAX_TPU_WATCHDOG_TIMEOUT`` unset, empty, or ``0`` disables the
    watchdog (see mpi4jax_tpu/resilience/watchdog.py).
    """
    val = parse_env_float("MPI4JAX_TPU_WATCHDOG_TIMEOUT", None)
    if val is None or val == 0:
        return None
    return val


def fault_spec() -> str:
    """Raw ``MPI4JAX_TPU_FAULT_SPEC`` string ('' = no injection).

    Parsed by ``mpi4jax_tpu.resilience.parse_fault_spec`` (grammar in
    docs/resilience.md).
    """
    return (_getenv("MPI4JAX_TPU_FAULT_SPEC") or "").strip()


def bootstrap_deadline() -> float:
    """Total seconds the bootstrap rendezvous may retry
    (``MPI4JAX_TPU_BOOTSTRAP_DEADLINE``; default 300).  Shared by
    ``init_distributed`` and the elastic re-bootstrap."""
    val = parse_env_float("MPI4JAX_TPU_BOOTSTRAP_DEADLINE",
                          DEFAULT_BOOTSTRAP_DEADLINE)
    if val is None or val <= 0:
        raise ValueError(
            "MPI4JAX_TPU_BOOTSTRAP_DEADLINE must be a positive number of "
            f"seconds, got {val!r}"
        )
    return val


def bootstrap_max_attempts() -> int:
    """Attempt cap of the bootstrap retry policy
    (``MPI4JAX_TPU_BOOTSTRAP_MAX_ATTEMPTS``; 0 = deadline-bounded
    only)."""
    return _parse_env_positive_int(
        "MPI4JAX_TPU_BOOTSTRAP_MAX_ATTEMPTS", DEFAULT_BOOTSTRAP_MAX_ATTEMPTS
    )


def elastic_redundancy() -> int:
    """Shard replication budget of the elastic in-memory checkpoint
    (``MPI4JAX_TPU_ELASTIC_REDUNDANCY``; default 1 — each shard lives on
    its owner plus one neighbor, tolerating one simultaneous loss)."""
    return _parse_env_positive_int(
        "MPI4JAX_TPU_ELASTIC_REDUNDANCY", DEFAULT_ELASTIC_REDUNDANCY
    )


def elastic_grow() -> bool:
    """Whether the elastic loop admits replacement ranks
    (``MPI4JAX_TPU_ELASTIC_GROW``; default off — see
    resilience/elastic.py and docs/resilience.md)."""
    return parse_env_bool("MPI4JAX_TPU_ELASTIC_GROW", False)


def drain_grace_s() -> float:
    """Graceful-drain notice window in seconds
    (``MPI4JAX_TPU_DRAIN_GRACE_S``; default 5)."""
    val = parse_env_float("MPI4JAX_TPU_DRAIN_GRACE_S",
                          DEFAULT_DRAIN_GRACE_S)
    if val is None or val <= 0:
        raise ValueError(
            "MPI4JAX_TPU_DRAIN_GRACE_S must be a positive number of "
            f"seconds, got {val!r}"
        )
    return val


def elastic_fail_unit() -> str:
    """Granularity of an elastic shrink
    (``MPI4JAX_TPU_ELASTIC_FAIL_UNIT``): ``rank`` (default) / ``row`` /
    ``col`` — see parallel/mesh.shrink_world_mesh."""
    return _parse_env_choice("MPI4JAX_TPU_ELASTIC_FAIL_UNIT")


def elastic_placement() -> str:
    """Shard-replica placement policy
    (``MPI4JAX_TPU_ELASTIC_PLACEMENT``): ``stripe`` (default) /
    ``neighbor`` — see resilience/elastic.py stripe_placement."""
    return _parse_env_choice("MPI4JAX_TPU_ELASTIC_PLACEMENT")


def elastic_agreement() -> str:
    """Failure-agreement transport
    (``MPI4JAX_TPU_ELASTIC_AGREEMENT``): ``coordinator`` (default) /
    ``gossip`` — see resilience/elastic.py negotiate_failed."""
    return _parse_env_choice("MPI4JAX_TPU_ELASTIC_AGREEMENT")


def elastic_port_span() -> int:
    """Width of the per-epoch elastic port window
    (``MPI4JAX_TPU_ELASTIC_PORT_SPAN``; default 64, minimum 1)."""
    return _parse_env_positive_int(
        "MPI4JAX_TPU_ELASTIC_PORT_SPAN", DEFAULT_ELASTIC_PORT_SPAN,
        minimum=1,
    )


def check_numerics() -> bool:
    """Whether collectives guard their inputs/outputs against NaN/Inf
    (``MPI4JAX_TPU_CHECK_NUMERICS``; see mpi4jax_tpu/resilience/numerics.py)."""
    return parse_env_bool("MPI4JAX_TPU_CHECK_NUMERICS", False)


def collective_algo() -> str:
    """Reduction-family algorithm selection (``MPI4JAX_TPU_COLLECTIVE_ALGO``).

    ``auto`` (default): pick butterfly vs ring per call from static payload
    bytes and group size (ops/_algos.py).  ``butterfly`` / ``ring`` force
    one lowering everywhere it is expressible.
    """
    return _parse_env_choice("MPI4JAX_TPU_COLLECTIVE_ALGO")


def ring_crossover_bytes() -> int:
    """Payload bytes at which ``auto`` prefers the ring lowerings
    (``MPI4JAX_TPU_RING_CROSSOVER_BYTES``; default 1 MiB; a tuning
    layer's measured value applies when the flag is not explicitly
    set — docs/autotune.md)."""
    raw = _getenv("MPI4JAX_TPU_RING_CROSSOVER_BYTES")
    if raw is None or not raw.strip():
        tuned = _tuned_knob("ring_crossover_bytes")
        if tuned is not None:
            return tuned
        return DEFAULT_RING_CROSSOVER_BYTES
    try:
        val = int(raw)
    except ValueError as e:
        raise ValueError(
            f"Environment variable MPI4JAX_TPU_RING_CROSSOVER_BYTES={raw!r} "
            "could not be parsed as an integer number of bytes"
        ) from e
    if val < 0:
        raise ValueError(
            f"Environment variable MPI4JAX_TPU_RING_CROSSOVER_BYTES={raw!r} "
            "must be >= 0"
        )
    return val


def _env_or_tuned(name: str, knob: str, static_default: int,
                  minimum: int = 0,
                  payload_bytes: Optional[int] = None) -> int:
    """One tuned int knob under the default < tuning < env precedence:
    an explicitly set (non-empty) env flag wins WITHOUT consulting the
    tuning layer at all — so a malformed tuning file can never mask a
    deliberate override, and the env fast path skips the knob lookup —
    else the active layer's value, else the static default."""
    raw = _getenv(name)
    if raw is not None and raw.strip():
        return _parse_env_positive_int(name, static_default, minimum)
    tuned = _tuned_knob(knob, payload_bytes=payload_bytes)
    return tuned if tuned is not None else static_default


def dcn_crossover_bytes() -> int:
    """Shard bytes at which the hierarchical lowerings' inter-host (DCN)
    phase prefers the ring (``MPI4JAX_TPU_DCN_CROSSOVER_BYTES``; default
    4 MiB — see docs/topology.md; a tuning layer's measured value
    applies when the flag is not explicitly set)."""
    return _env_or_tuned(
        "MPI4JAX_TPU_DCN_CROSSOVER_BYTES", "dcn_crossover_bytes",
        DEFAULT_DCN_CROSSOVER_BYTES,
    )


def alltoall_crossover_bytes() -> int:
    """Payload bytes at which a multi-host alltoall prefers the
    two-level hierarchical lowering
    (``MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES``; default 1 MiB — see
    docs/moe.md; a tuning layer's measured value applies when the flag
    is not explicitly set)."""
    return _env_or_tuned(
        "MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES", "alltoall_crossover_bytes",
        DEFAULT_ALLTOALL_CROSSOVER_BYTES,
    )


def compress_mode(payload_bytes: Optional[int] = None) -> str:
    """Effective DCN-leg compression codec (``MPI4JAX_TPU_COMPRESS``):
    ``off`` (default) / ``bf16`` / ``fp8`` — the usual default < tuning
    < env precedence, payload-bucketed like :func:`overlap_chunks`.
    ``auto`` (env or tuned) resolves to the tuning layer's measured
    codec for this payload bucket, or ``bf16`` without one — callers
    always see a concrete codec, never ``auto``."""
    mode = _parse_env_choice("MPI4JAX_TPU_COMPRESS")
    raw = _getenv("MPI4JAX_TPU_COMPRESS")
    explicit = raw is not None and bool(raw.strip())
    if not explicit or mode == "auto":
        tuned = _tuned_knob("compress", payload_bytes=payload_bytes)
        if tuned is not None:
            tuned = str(tuned).lower()
            if tuned != "auto":
                return tuned
        if mode == "auto":
            return "bf16"
    return mode


def compress_error_budget() -> float:
    """Relative-error budget of the autotune codec sweep
    (``MPI4JAX_TPU_COMPRESS_ERROR_BUDGET``; default 1e-2)."""
    val = parse_env_float("MPI4JAX_TPU_COMPRESS_ERROR_BUDGET",
                          DEFAULT_COMPRESS_ERROR_BUDGET)
    if val is None or val <= 0:
        raise ValueError(
            "MPI4JAX_TPU_COMPRESS_ERROR_BUDGET must be a positive "
            f"relative error bound, got {val!r}"
        )
    return val


def moe_capacity_chunks() -> int:
    """Capacity-chunk count of the MoE combine/compute pipeline
    (``MPI4JAX_TPU_MOE_CAPACITY_CHUNKS``; default 2, minimum 1 — see
    parallel/moe.py and docs/moe.md)."""
    return _parse_env_positive_int(
        "MPI4JAX_TPU_MOE_CAPACITY_CHUNKS", DEFAULT_MOE_CAPACITY_CHUNKS,
        minimum=1,
    )


def pipeline_microbatches(payload_bytes: Optional[int] = None) -> int:
    """Microbatch count of the pipeline schedule compiler
    (``MPI4JAX_TPU_PIPELINE_MICROBATCHES``; default 0 = unset — see
    parallel/pipeline.py and docs/pipeline.md; a tuning layer's measured
    value applies when the flag is not explicitly set)."""
    return _env_or_tuned(
        "MPI4JAX_TPU_PIPELINE_MICROBATCHES", "pipeline_microbatches",
        DEFAULT_PIPELINE_MICROBATCHES, payload_bytes=payload_bytes,
    )


def pipeline_virtual_stages(payload_bytes: Optional[int] = None) -> int:
    """Virtual stage-chunk count per rank of the interleaved pipeline
    schedule (``MPI4JAX_TPU_PIPELINE_VIRTUAL_STAGES``; default 0 = unset
    — see parallel/pipeline.py and docs/pipeline.md; a tuning layer's
    measured value applies when the flag is not explicitly set)."""
    return _env_or_tuned(
        "MPI4JAX_TPU_PIPELINE_VIRTUAL_STAGES", "pipeline_virtual_stages",
        DEFAULT_PIPELINE_VIRTUAL_STAGES, payload_bytes=payload_bytes,
    )


def topology_spec() -> str:
    """Raw ``MPI4JAX_TPU_TOPOLOGY`` string ('' = derive from the mesh's
    JAX process layout).  Parsed by :func:`parse_topology_spec`."""
    return (_getenv("MPI4JAX_TPU_TOPOLOGY") or "").strip()


def parse_topology_spec(raw: str) -> Optional[Tuple[int, ...]]:
    """Parse a topology spec into per-host rank counts.

    Grammar (docs/topology.md): ``<hosts>x<ranks_per_host>`` for uniform
    pods (``2x4`` -> ``(4, 4)``), or comma-separated per-host counts for
    heterogeneous clusters (``3,5`` -> ``(3, 5)``).  Empty/None ->
    ``None`` (no override).  Raises ``ValueError`` on malformed specs —
    a typo'd override must not silently disable the hierarchical layer.
    """
    if raw is None:
        return None
    raw = raw.strip().lower()
    if not raw:
        return None
    try:
        if "x" in raw:
            hosts_s, _, per_s = raw.partition("x")
            hosts, per = int(hosts_s), int(per_s)
            if hosts < 1 or per < 1:
                raise ValueError
            return (per,) * hosts
        counts = tuple(int(c) for c in raw.split(","))
        if not counts or any(c < 1 for c in counts):
            raise ValueError
        return counts
    except ValueError:
        raise ValueError(
            f"Environment variable MPI4JAX_TPU_TOPOLOGY={raw!r} could not "
            "be parsed: expected '<hosts>x<ranks_per_host>' (e.g. '2x4') "
            "or comma-separated per-host rank counts (e.g. '3,5'), all "
            "positive integers"
        ) from None


def analyze_mode() -> str:
    """Trace-time collective verifier mode (``MPI4JAX_TPU_ANALYZE``):
    ``off`` (default) / ``warn`` / ``error`` — see mpi4jax_tpu/analysis/."""
    return _parse_env_choice("MPI4JAX_TPU_ANALYZE")


def analyze_ranks():
    """Cross-rank pass setting (``MPI4JAX_TPU_ANALYZE_RANKS``):
    ``"auto"`` (default), ``"off"``, or a positive int cap on the comm
    sizes the ambient per-rank re-trace covers."""
    raw = (_getenv("MPI4JAX_TPU_ANALYZE_RANKS") or "").strip().lower()
    if not raw or raw == "auto":
        return "auto"
    if raw == "off":
        return "off"
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"Environment variable MPI4JAX_TPU_ANALYZE_RANKS={raw!r} must "
            "be 'auto', 'off', or a positive integer rank cap"
        ) from None
    if val < 1:
        raise ValueError(
            f"Environment variable MPI4JAX_TPU_ANALYZE_RANKS={raw!r} must "
            "be 'auto', 'off', or a positive integer rank cap"
        )
    return val


def cost_model_path() -> str:
    """Path of the cost-model tuning file (``MPI4JAX_TPU_COST_MODEL``;
    '' = the documented analytic defaults — see analysis/costmodel.py
    and docs/analysis.md 'Cost model')."""
    return (_getenv("MPI4JAX_TPU_COST_MODEL") or "").strip()


def analyze_cost_enabled() -> bool:
    """Whether the ambient verifier's cross-rank pass also runs the
    critical-path cost simulation (``MPI4JAX_TPU_ANALYZE_COST``; default
    off — see analysis/cost.py)."""
    return _parse_env_choice("MPI4JAX_TPU_ANALYZE_COST") == "on"


def telemetry_mode() -> str:
    """Runtime telemetry tier (``MPI4JAX_TPU_TELEMETRY``): ``off``
    (default) / ``counters`` / ``events`` — see mpi4jax_tpu/telemetry/."""
    return _parse_env_choice("MPI4JAX_TPU_TELEMETRY")


def telemetry_dir() -> str:
    """Directory for the events-tier JSONL journals
    (``MPI4JAX_TPU_TELEMETRY_DIR``; '' = in-memory journal only)."""
    return (_getenv("MPI4JAX_TPU_TELEMETRY_DIR") or "").strip()


def health_mode() -> str:
    """Runtime health plane tier (``MPI4JAX_TPU_HEALTH``): ``off``
    (default) / ``on`` — see mpi4jax_tpu/telemetry/health.py and
    docs/observability.md 'Runtime health'."""
    return _parse_env_choice("MPI4JAX_TPU_HEALTH")


def health_interval() -> int:
    """Boundary stride of the health detector's digest exchange
    (``MPI4JAX_TPU_HEALTH_INTERVAL``; default 1 = every boundary)."""
    return _parse_env_positive_int("MPI4JAX_TPU_HEALTH_INTERVAL", 1,
                                   minimum=1)


def flight_ring_capacity() -> int:
    """Flight-recorder ring capacity in records
    (``MPI4JAX_TPU_FLIGHT_RING``; default 1024, minimum 1)."""
    return _parse_env_positive_int("MPI4JAX_TPU_FLIGHT_RING", 1024,
                                   minimum=1)


def health_suspects_enabled() -> bool:
    """Whether the health detector may post persistent stragglers as
    suspects into the elastic agreement machinery
    (``MPI4JAX_TPU_HEALTH_SUSPECTS``; default off)."""
    return parse_env_bool("MPI4JAX_TPU_HEALTH_SUSPECTS", False)


def health_prom_enabled() -> bool:
    """Whether detector boundaries also write the Prometheus exposition
    file under the telemetry dir (``MPI4JAX_TPU_HEALTH_PROM``; default
    off)."""
    return parse_env_bool("MPI4JAX_TPU_HEALTH_PROM", False)


def _parse_env_positive_int(name: str, default: int, minimum: int = 0) -> int:
    """Parse an integer flag with a lower bound (empty/unset -> default)."""
    raw = _getenv(name)
    if raw is None or not raw.strip():
        return default
    try:
        val = int(raw)
    except ValueError as e:
        raise ValueError(
            f"Environment variable {name}={raw!r} could not be parsed as "
            "an integer"
        ) from e
    if val < minimum:
        raise ValueError(
            f"Environment variable {name}={raw!r} must be >= {minimum}"
        )
    return val


def fusion_mode() -> str:
    """Collective-fusion mode (``MPI4JAX_TPU_FUSION``): ``off`` (default)
    / ``auto`` / ``force`` — see mpi4jax_tpu/ops/_fusion.py and
    docs/overlap.md."""
    return _parse_env_choice("MPI4JAX_TPU_FUSION")


def fusion_bucket_bytes() -> int:
    """Byte cap per (dtype-segregated) fusion bucket
    (``MPI4JAX_TPU_FUSION_BUCKET_BYTES``; default 4 MiB; a tuning
    layer's measured value applies when the flag is not explicitly
    set)."""
    return _env_or_tuned(
        "MPI4JAX_TPU_FUSION_BUCKET_BYTES", "fusion_bucket_bytes",
        DEFAULT_FUSION_BUCKET_BYTES,
    )


def overlap_chunks(payload_bytes: Optional[int] = None) -> int:
    """Chunk count for the async start/wait collectives
    (``MPI4JAX_TPU_OVERLAP_CHUNKS``; default 2, minimum 1).  A tuning
    layer may bucket the value by payload: callers that know their
    payload pass it (ops/_async.py) and get the bucket's chunk count;
    the flag, when explicitly set, still wins everywhere."""
    return _env_or_tuned(
        "MPI4JAX_TPU_OVERLAP_CHUNKS", "overlap_chunks",
        DEFAULT_OVERLAP_CHUNKS, minimum=1, payload_bytes=payload_bytes,
    )


def compile_cache_dir() -> str:
    """Persistent compiled-program cache directory
    (``MPI4JAX_TPU_COMPILE_CACHE_DIR``; '' = the persistent tier is
    disabled — see mpi4jax_tpu/aot/diskcache.py and docs/aot.md)."""
    return (_getenv("MPI4JAX_TPU_COMPILE_CACHE_DIR") or "").strip()


def compile_cache_max_bytes() -> int:
    """Byte cap of the persistent compiled-program cache
    (``MPI4JAX_TPU_COMPILE_CACHE_MAX_BYTES``; default 1 GiB, 0 =
    unbounded)."""
    return _parse_env_positive_int(
        "MPI4JAX_TPU_COMPILE_CACHE_MAX_BYTES",
        DEFAULT_COMPILE_CACHE_MAX_BYTES,
    )


def unroll_default() -> int:
    """Default megastep unroll factor (``MPI4JAX_TPU_UNROLL_DEFAULT``;
    default 1 = no device-resident loop — see parallel/megastep.py and
    docs/aot.md 'Megastep execution')."""
    return _parse_env_positive_int("MPI4JAX_TPU_UNROLL_DEFAULT", 1,
                                   minimum=1)


def serving_max_batch() -> int:
    """Decode batch cap of the serving runtime
    (``MPI4JAX_TPU_SERVING_MAX_BATCH``; default 8, minimum 1 — see
    mpi4jax_tpu/serving/ and docs/serving.md)."""
    return _parse_env_positive_int(
        "MPI4JAX_TPU_SERVING_MAX_BATCH", DEFAULT_SERVING_MAX_BATCH,
        minimum=1,
    )


def serving_buckets() -> str:
    """Raw ``MPI4JAX_TPU_SERVING_BUCKETS`` spec ('' = powers of two up
    to :func:`serving_max_batch`).  Parsed by
    ``mpi4jax_tpu.serving.buckets.BucketTable.from_spec``."""
    return (_getenv("MPI4JAX_TPU_SERVING_BUCKETS") or "").strip()


def serving_kv_slots() -> int:
    """KV slot budget of the serving runtime
    (``MPI4JAX_TPU_SERVING_KV_SLOTS``; 0 = twice the max batch)."""
    return _parse_env_positive_int("MPI4JAX_TPU_SERVING_KV_SLOTS", 0)


def serving_unroll() -> int:
    """Decode megastep trip count of the serving runtime
    (``MPI4JAX_TPU_SERVING_UNROLL``; default 4, minimum 1)."""
    return _parse_env_positive_int(
        "MPI4JAX_TPU_SERVING_UNROLL", DEFAULT_SERVING_UNROLL, minimum=1,
    )


def serving_slo_p99_ms() -> float:
    """The serving p99 latency objective in milliseconds
    (``MPI4JAX_TPU_SERVING_SLO_P99_MS``; default 1000)."""
    val = parse_env_float("MPI4JAX_TPU_SERVING_SLO_P99_MS",
                          DEFAULT_SERVING_SLO_P99_MS)
    if val is None or val <= 0:
        raise ValueError(
            "MPI4JAX_TPU_SERVING_SLO_P99_MS must be a positive number of "
            f"milliseconds, got {val!r}"
        )
    return val


def prefer_notoken() -> bool:
    """Whether the token API should delegate to implicit (notoken) ordering.

    Ref: mpi4jax/_src/utils.py:175-177 (``MPI4JAX_PREFER_NOTOKEN``).  In this
    framework the two paths share one lowering, so this only controls whether
    tokens are threaded through ``optimization_barrier`` chains.
    """
    return parse_env_bool("MPI4JAX_TPU_PREFER_NOTOKEN", False)
