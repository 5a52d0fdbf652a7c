"""Trace-time verifier: the pure-Python half (docs/analysis.md).

Drives the checker registry, the report/rendering layer, the jaxpr
walker (with duck-typed fake jaxprs), and the ``MPI4JAX_TPU_ANALYZE``
mode plumbing — all loaded under a private package name
(``_load_isolated``, mirroring tests/test_algos.py) so these tests run
even where the installed JAX is below the package's hard floor and
``import mpi4jax_tpu`` refuses.  One positive (finding fired: code +
message asserted) and one negative (clean graph: no finding of that
code) per graph-level checker; the traced integration half — the same
hazards driven through ``mpx.analyze`` and the env-mode dispatch path —
lives in tests/test_analysis.py.
"""

import importlib
import pathlib
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "mpi4jax_tpu"

_ISO_NAME = "_mpx_analysis_iso"


def _load_isolated():
    """Load analysis/* + utils/config.py under a private package name,
    bypassing ``mpi4jax_tpu/__init__.py`` (whose JAX-floor check refuses
    to import on old JAX) while preserving package context for the
    relative imports."""
    if _ISO_NAME in sys.modules:
        return sys.modules[_ISO_NAME]
    root = types.ModuleType(_ISO_NAME)
    root.__path__ = [str(PKG)]
    sys.modules[_ISO_NAME] = root
    for sub in ("utils", "analysis", "parallel", "ops"):
        m = types.ModuleType(f"{_ISO_NAME}.{sub}")
        m.__path__ = [str(PKG / sub)]
        sys.modules[f"{_ISO_NAME}.{sub}"] = m
        setattr(root, sub, m)
    for mod in ("utils.config", "ops._fusion", "analysis.report",
                "analysis.graph", "analysis.checkers", "analysis.walker",
                "analysis.dataflow", "analysis.hazards",
                "analysis.hook", "analysis.schedule", "analysis.matcher",
                "analysis.progress", "analysis.costmodel", "analysis.cost",
                "parallel.rankspec"):
        importlib.import_module(f"{_ISO_NAME}.{mod}")
    return root


ISO = _load_isolated()
report = sys.modules[f"{_ISO_NAME}.analysis.report"]
graph = sys.modules[f"{_ISO_NAME}.analysis.graph"]
checkers = sys.modules[f"{_ISO_NAME}.analysis.checkers"]
walker = sys.modules[f"{_ISO_NAME}.analysis.walker"]
hook = sys.modules[f"{_ISO_NAME}.analysis.hook"]
config = sys.modules[f"{_ISO_NAME}.utils.config"]
rankspec = sys.modules[f"{_ISO_NAME}.parallel.rankspec"]

E = graph.CollectiveEvent
G = graph.CollectiveGraph


def codes_of(g):
    return [f.code for f in checkers.run_checkers(g)]


# ---------------------------------------------------------------------------
# registry / catalog coverage
# ---------------------------------------------------------------------------


def test_catalog_is_fully_owned():
    # every code is emitted by a graph checker, except MPX108 (the jaxpr
    # walker owns it: control-flow structure is invisible to the event
    # stream), the cross-rank codes (the schedule matcher and the
    # progress checker own those — analysis/matcher.py + progress.py),
    # and MPX129 (owned by the tagged raise site in aot/invalidation.py:
    # a stale pinned call refuses BEFORE dispatch, so no event stream
    # can ever witness one — mpx.analyze converts the raise)
    matcher = sys.modules[f"{_ISO_NAME}.analysis.matcher"]
    progress = sys.modules[f"{_ISO_NAME}.analysis.progress"]
    cost = sys.modules[f"{_ISO_NAME}.analysis.cost"]
    crossrank_owned = set(matcher.CROSSRANK_CODES) | set(
        progress.CROSSRANK_CODES)
    # MPX131-135 are owned by the cost-pass critic (analysis/cost.py):
    # quantified advisories over the timed simulation, never emitted by
    # a graph checker
    cost_owned = set(cost.COST_CODES)
    raise_site_owned = {"MPX129"}
    # MPX141/142 are owned by the dataflow taint pass (analysis/
    # dataflow.py) — jaxpr-level like MPX108; the graph-side hazard
    # checkers (MPX139/140, analysis/hazards.py) register normally
    jaxpr_owned = {"MPX108"} | set(report.HAZARD_JAXPR_CODES)
    assert (checkers.registered_codes() | jaxpr_owned | crossrank_owned
            | cost_owned | raise_site_owned == set(report.CODES))
    # the registries never claim the same code
    assert not crossrank_owned & checkers.registered_codes()
    assert not cost_owned & (crossrank_owned | checkers.registered_codes())


def test_codes_have_severity_and_docs():
    for code, info in report.CODES.items():
        assert info.severity in (report.ERROR, report.ADVISORY)
        assert info.title and info.doc


def test_analysis_doc_lists_every_code():
    doc = (REPO / "docs" / "analysis.md").read_text()
    missing = [c for c in report.CODES if c not in doc]
    assert not missing, f"codes absent from docs/analysis.md: {missing}"


# ---------------------------------------------------------------------------
# MPX101 / MPX102 / MPX106 / MPX110 — p2p matching replay
# ---------------------------------------------------------------------------


def test_mpx101_unmatched_send_fires():
    g = G(events=[E(0, "send", comm_uid=1, tag=3, dtype="float32",
                    shape=(4,))])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX101"
    assert "never" in f.message and "FIFO" in f.message
    assert "matching recv" in f.suggestion


def test_mpx102_recv_without_send_fires():
    g = G(events=[E(0, "recv", comm_uid=1, tag=0)])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX102"
    assert "no matching send" in f.message


def test_matched_pair_is_clean():
    g = G(events=[
        E(0, "send", comm_uid=1, tag=0, dtype="float32", shape=(4,)),
        E(1, "recv", comm_uid=1, tag=0, dtype="float32", shape=(4,)),
    ])
    assert codes_of(g) == []


def test_eager_recv_is_not_replayed():
    # eager p2p uses deferred pairing: the send never enters dispatch, so
    # a lone eager recv event must NOT fire MPX102
    g = G(events=[E(0, "recv", comm_uid=1, tag=0, eager=True)])
    assert codes_of(g) == []


def test_mpx106_signature_mismatch_fires_and_clean():
    g = G(events=[
        E(0, "send", comm_uid=1, tag=0, dtype="float32", shape=(4,)),
        E(1, "recv", comm_uid=1, tag=0, dtype="int32", shape=(4,)),
    ])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX106"
    assert "type-signature" in f.message
    # same element count, different shape: allowed (output typed by
    # template)
    g = G(events=[
        E(0, "send", comm_uid=1, tag=0, dtype="float32", shape=(1, 4)),
        E(1, "recv", comm_uid=1, tag=0, dtype="float32", shape=(4, 1)),
    ])
    assert codes_of(g) == []


def test_mpx110_ambiguous_fifo_fires_and_clean():
    two_sends = [
        E(0, "send", comm_uid=1, tag=0, dtype="f", shape=(1,)),
        E(1, "send", comm_uid=1, tag=0, dtype="f", shape=(1,)),
        E(2, "recv", comm_uid=1, tag=0, dtype="f", shape=(1,)),
        E(3, "recv", comm_uid=1, tag=0, dtype="f", shape=(1,)),
    ]
    codes = codes_of(G(events=two_sends))
    assert codes == ["MPX110"]
    # distinct tags: unambiguous
    g = G(events=[
        E(0, "send", comm_uid=1, tag=0, dtype="f", shape=(1,)),
        E(1, "send", comm_uid=1, tag=1, dtype="f", shape=(1,)),
        E(2, "recv", comm_uid=1, tag=0, dtype="f", shape=(1,)),
        E(3, "recv", comm_uid=1, tag=1, dtype="f", shape=(1,)),
    ])
    assert codes_of(g) == []


# ---------------------------------------------------------------------------
# MPX103 / MPX104 — structural statics (graph events + tagged raise sites)
# ---------------------------------------------------------------------------


def test_mpx103_bare_int_event_and_raise_site():
    g = G(events=[E(0, "sendrecv", comm_uid=1, tag=0,
                    extra={"bare_int_routing": True})])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX103" and "bare int" in f.message
    # the live raise site carries the same code
    with pytest.raises(TypeError, match=r"ambiguous under SPMD.*\[MPX103\]") as ei:
        rankspec.normalize_dest(1, 4, what="send")
    assert ei.value.mpx_code == "MPX103"


def test_mpx104_traced_structure_event():
    g = G(events=[E(0, "bcast", comm_uid=1,
                    extra={"traced_structure": "root"})])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX104" and "tracer" in f.message
    assert codes_of(G(events=[E(0, "bcast", comm_uid=1, root=0,
                                min_size=4)])) == []


# ---------------------------------------------------------------------------
# MPX105 — root range
# ---------------------------------------------------------------------------


def test_mpx105_root_out_of_range_fires_and_clean():
    g = G(events=[E(0, "bcast", comm_uid=1, root=9, min_size=8)])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX105"
    assert "root 9 out of range" in f.message
    assert "[0, 8)" in f.suggestion
    assert codes_of(G(events=[E(0, "bcast", comm_uid=1, root=7,
                                min_size=8)])) == []
    # split comms name the smallest group
    g = G(events=[E(0, "bcast", comm_uid=1, root=3, min_size=3, split=True)])
    (f,) = checkers.run_checkers(g)
    assert "smallest group" in f.message


# ---------------------------------------------------------------------------
# MPX107 — token discipline
# ---------------------------------------------------------------------------


def test_mpx107_forked_token_fires():
    g = G(events=[
        E(0, "allreduce", comm_uid=1, token_in=100, token_out=101),
        E(1, "allreduce", comm_uid=1, token_in=100, token_out=102),
    ])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX107"
    assert "never" in f.message and "older token" in f.message


def test_mpx107_clean_chains():
    # linear chain: final token legitimately unconsumed
    g = G(events=[
        E(0, "allreduce", comm_uid=1, token_in=100, token_out=101),
        E(1, "bcast", comm_uid=1, token_in=101, token_out=102),
    ])
    assert codes_of(g) == []
    # tokenless program
    g = G(events=[E(0, "allreduce", comm_uid=1),
                  E(1, "allreduce", comm_uid=1)])
    assert codes_of(g) == []
    # notoken passthrough (produce returns the same token)
    g = G(events=[
        E(0, "allreduce", comm_uid=1, token_in=100, token_out=100),
        E(1, "allreduce", comm_uid=1, token_in=100, token_out=100),
    ])
    assert codes_of(g) == []
    # independent chains on DIFFERENT comms never interact
    g = G(events=[
        E(0, "allreduce", comm_uid=1, token_in=100, token_out=101),
        E(1, "allreduce", comm_uid=2, token_in=200, token_out=201),
    ])
    assert codes_of(g) == []


# ---------------------------------------------------------------------------
# MPX109 — crossover proximity advisory
# ---------------------------------------------------------------------------


def _algo_graph(payload, algo="butterfly", mode="auto", k=8,
                crossover=1 << 20):
    return G(
        events=[E(0, "allreduce", comm_uid=1, comm_size=k,
                  payload_bytes=payload, algo=algo)],
        meta={"collective_algo": mode, "ring_crossover_bytes": crossover},
    )


def test_mpx109_near_crossover_fires():
    (f,) = checkers.run_checkers(_algo_graph(1 << 20))
    assert f.code == "MPX109"
    assert "within 2x" in f.message
    assert "MPI4JAX_TPU_COLLECTIVE_ALGO" in f.suggestion
    # boundary semantics: [crossover/2, crossover*2)
    assert codes_of(_algo_graph((1 << 19))) == ["MPX109"]
    assert codes_of(_algo_graph((1 << 21) - 1)) == ["MPX109"]


def test_mpx109_negative_cases():
    assert codes_of(_algo_graph(1 << 10)) == []          # far below
    assert codes_of(_algo_graph(1 << 22)) == []          # far above
    assert codes_of(_algo_graph(1 << 20, mode="ring")) == []   # forced
    assert codes_of(_algo_graph(1 << 20, algo="native")) == []  # native HLO
    assert codes_of(_algo_graph(1 << 20, k=2)) == []     # below ring min
    assert checkers.RING_MIN_GROUP == 4  # mirrored from ops/_algos.py


# ---------------------------------------------------------------------------
# MPX113 — flat algorithm on a multi-host comm
# ---------------------------------------------------------------------------


def _hier_graph(payload=1 << 22, algo="ring", hosts=2, k=8,
                op="allreduce", crossover=1 << 20, mode="ring"):
    return G(
        events=[E(0, op, comm_uid=1, comm_size=k, payload_bytes=payload,
                  algo=algo, hosts=hosts)],
        meta={"collective_algo": mode, "ring_crossover_bytes": crossover},
    )


def test_mpx113_flat_over_dcn_fires():
    (f,) = checkers.run_checkers(_hier_graph())
    assert f.code == "MPX113"
    assert f.severity == "advisory"
    assert "2 hosts" in f.message and "'ring'" in f.message
    assert "DCN" in f.message
    assert "MPI4JAX_TPU_COLLECTIVE_ALGO=hier" in f.suggestion
    # a forced butterfly on a multi-host comm fires too, and the payload
    # + topology that triggered it are in the message
    (f2,) = checkers.run_checkers(_hier_graph(algo="butterfly",
                                              payload=1 << 21))
    assert f2.code == "MPX113" and f"{1 << 21} B" in f2.message
    # reduce_scatter and bcast are in the algorithm family
    (f3,) = checkers.run_checkers(_hier_graph(op="reduce_scatter"))
    assert f3.code == "MPX113"


def test_mpx113_negative_cases():
    # the hierarchical lowering actually ran: nothing to advise
    assert codes_of(_hier_graph(algo="hier", mode="hier")) == []
    # single host (or no derivable topology -> hosts is None): flat is right
    assert codes_of(_hier_graph(hosts=1)) == []
    assert codes_of(_hier_graph(hosts=None)) == []
    # below the ring crossover the flat butterfly IS the right choice
    # (MPX109 may still advise about crossover proximity — not this rule)
    assert "MPX113" not in codes_of(_hier_graph(payload=(1 << 20) - 1,
                                                algo="butterfly",
                                                mode="auto"))
    # one rank per host: hier degenerates to flat, nothing to gain
    assert codes_of(_hier_graph(hosts=8, k=8)) == []
    # native HLO is XLA-scheduled; not ours to advise on
    assert codes_of(_hier_graph(algo="native")) == []
    # non-algorithm ops never fire
    assert codes_of(_hier_graph(op="scan")) == []


# ---------------------------------------------------------------------------
# MPX111 — adjacent fusable collectives not fused
# ---------------------------------------------------------------------------

_FUSION_META = {"fusion": "off", "fusion_bucket_bytes": 1 << 20,
                "collective_algo": "auto", "ring_crossover_bytes": 1 << 20}


def _adjacent(op="allreduce", n=2, reduction="sum", payload=64, **kw):
    return [E(i, op, comm_uid=1, reduction=reduction,
              payload_bytes=payload, **kw) for i in range(n)]


def test_mpx111_adjacent_unfused_fires():
    g = G(events=_adjacent(n=3), meta=dict(_FUSION_META))
    (f,) = [x for x in checkers.run_checkers(g) if x.code == "MPX111"]
    assert f.severity == "advisory"
    assert "3 adjacent allreduce" in f.message
    assert "MPI4JAX_TPU_FUSION=auto" in f.suggestion
    assert f.index == 0  # anchored at the run's first event


def test_mpx111_mixed_dtypes_still_bucket():
    # dtype segregation happens inside the flush, so a mixed-dtype run is
    # still one fusion opportunity
    evs = [E(0, "allreduce", comm_uid=1, reduction="sum", payload_bytes=64,
             dtype="float32"),
           E(1, "allreduce", comm_uid=1, reduction="sum", payload_bytes=64,
             dtype="int32")]
    g = G(events=evs, meta=dict(_FUSION_META))
    assert [x.code for x in checkers.run_checkers(g)] == ["MPX111"]


def test_mpx111_negative_cases():
    # fusion already on
    g = G(events=_adjacent(), meta={**_FUSION_META, "fusion": "auto"})
    assert codes_of(g) == []
    # no fusion meta at all (hand-built graph testing another rule)
    assert codes_of(G(events=_adjacent())) == []
    # different reductions never bucket
    evs = _adjacent() + [E(2, "allreduce", comm_uid=1, reduction="max",
                           payload_bytes=64)]
    evs[2].index = 2
    g = G(events=[evs[0], evs[2]], meta=dict(_FUSION_META))
    assert codes_of(g) == []
    # an intervening op breaks adjacency
    evs = [E(0, "allreduce", comm_uid=1, reduction="sum", payload_bytes=64),
           E(1, "barrier", comm_uid=1),
           E(2, "allreduce", comm_uid=1, reduction="sum", payload_bytes=64)]
    assert codes_of(G(events=evs, meta=dict(_FUSION_META))) == []
    # members above the bucket cap don't bucket
    g = G(events=_adjacent(payload=(1 << 20) + 1), meta=dict(_FUSION_META))
    assert codes_of(g) == []
    # eager dispatches compile one program per op: nothing to fuse
    g = G(events=_adjacent(eager=True), meta=dict(_FUSION_META))
    assert codes_of(g) == []
    # different roots never bucket (bcast)
    evs = [E(0, "bcast", comm_uid=1, root=0, payload_bytes=64),
           E(1, "bcast", comm_uid=1, root=1, payload_bytes=64)]
    assert codes_of(G(events=evs, meta=dict(_FUSION_META))) == []
    # same-root bcast run fires
    evs = [E(0, "bcast", comm_uid=1, root=0, payload_bytes=64),
           E(1, "bcast", comm_uid=1, root=0, payload_bytes=64)]
    assert codes_of(G(events=evs, meta=dict(_FUSION_META))) == ["MPX111"]
    # callable reductions never defer (ops/allreduce.py gates on enum
    # Ops), so advising fusion for them would be wrong
    g = G(events=_adjacent(reduction="my_combiner"),
          meta=dict(_FUSION_META))
    assert codes_of(g) == []
    assert checkers.ENUM_REDUCTIONS == tuple(
        o for o in ("sum", "prod", "min", "max", "land", "lor", "lxor",
                    "band", "bor", "bxor"))


def test_fusable_ops_mirror():
    # the checker's literal mirror must match the deferral layer's list
    fusion = sys.modules[f"{_ISO_NAME}.ops._fusion"]
    assert checkers.FUSABLE_OPS == fusion.FUSABLE_OPS


def test_config_snapshot_records_fusion():
    snap = hook.config_snapshot()
    assert snap["fusion"] in config.FUSION_MODES
    assert snap["fusion_bucket_bytes"] == config.fusion_bucket_bytes()
    assert snap["alltoall_crossover_bytes"] == \
        config.alltoall_crossover_bytes()


# ---------------------------------------------------------------------------
# MPX137 — flat alltoall on a multi-host comm (the MPX113 analog)
# ---------------------------------------------------------------------------


def _a2a_meta(crossover=1024):
    return {"alltoall_crossover_bytes": crossover}


def test_mpx137_flat_multihost_alltoall_fires():
    g = G(events=[E(0, "alltoall", comm_uid=1, comm_size=8, hosts=2,
                    payload_bytes=4096, algo="native")],
          meta=_a2a_meta())
    found = [f for f in checkers.run_checkers(g) if f.code == "MPX137"]
    assert len(found) == 1
    f = found[0]
    assert f.severity == "advisory"
    assert "2 hosts" in f.message and "4x the DCN message count" in f.message
    assert "hier" in f.suggestion


def test_mpx137_async_start_counts_like_the_blocking_op():
    g = G(events=[E(0, "alltoall_start", comm_uid=1, comm_size=8, hosts=2,
                    payload_bytes=4096, algo="pairwise", span=7)],
          meta=_a2a_meta())
    assert "MPX137" in [f.code for f in checkers.run_checkers(g)]


def test_mpx137_cites_measured_crossover():
    # a calibrated file's measured value replaces the static one as the
    # threshold AND in the text (the MPX113 contract, mirrored)
    meta = {"alltoall_crossover_bytes": 1 << 20,
            "measured_alltoall_crossover_bytes": 1024,
            "tuned_stamp": "abc123def456"}
    g = G(events=[E(0, "alltoall", comm_uid=1, comm_size=8, hosts=2,
                    payload_bytes=4096, algo="native")], meta=meta)
    (f,) = [x for x in checkers.run_checkers(g) if x.code == "MPX137"]
    assert "measured alltoall crossover" in f.message
    assert "tuned@abc123def456" in f.message
    assert "1024 B" in f.message


def test_mpx137_negatives():
    # hier selected: nothing to advise
    g = G(events=[E(0, "alltoall", comm_uid=1, comm_size=8, hosts=2,
                    payload_bytes=4096, algo="hier", hier=(2, 4))],
          meta=_a2a_meta())
    assert "MPX137" not in codes_of(g)
    # below the crossover: the flat exchange is the right call
    g = G(events=[E(0, "alltoall", comm_uid=1, comm_size=8, hosts=2,
                    payload_bytes=512, algo="native")],
          meta=_a2a_meta())
    assert "MPX137" not in codes_of(g)
    # no hosts annotation (no plan was derivable): flat is the only option
    g = G(events=[E(0, "alltoall", comm_uid=1, comm_size=8,
                    payload_bytes=4096, algo="native")],
          meta=_a2a_meta())
    assert "MPX137" not in codes_of(g)
    # one rank per host: the hierarchy degenerates — nothing to advise
    g = G(events=[E(0, "alltoall", comm_uid=1, comm_size=2, hosts=2,
                    payload_bytes=4096, algo="native")],
          meta=_a2a_meta())
    assert "MPX137" not in codes_of(g)
    # hand-built graph without the crossover meta: testing other rules
    g = G(events=[E(0, "alltoall", comm_uid=1, comm_size=8, hosts=2,
                    payload_bytes=4096, algo="native")])
    assert "MPX137" not in codes_of(g)


# ---------------------------------------------------------------------------
# MPX112 — async start/wait pairing
# ---------------------------------------------------------------------------


def test_mpx112_unwaited_start_fires():
    g = G(events=[E(0, "allreduce_start", comm_uid=1, span=11)])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX112" and f.severity == "error"
    assert "never waited" in f.message
    assert "allreduce_wait" in f.suggestion


def test_mpx112_wait_without_start_fires():
    g = G(events=[E(0, "allreduce_wait", comm_uid=1, span=11)])
    (f,) = checkers.run_checkers(g)
    assert f.code == "MPX112"
    assert "no live matching" in f.message


def test_mpx112_double_wait_fires_once():
    g = G(events=[
        E(0, "allreduce_start", comm_uid=1, span=11),
        E(1, "allreduce_wait", comm_uid=1, span=11),
        E(2, "allreduce_wait", comm_uid=1, span=11),
    ])
    codes = [f.code for f in checkers.run_checkers(g)]
    assert codes == ["MPX112"]


def test_mpx112_clean_pairs_interleaved():
    # two in-flight handles waited out of order: still properly paired
    g = G(events=[
        E(0, "allreduce_start", comm_uid=1, span=1),
        E(1, "reduce_scatter_start", comm_uid=1, span=2),
        E(2, "reduce_scatter_wait", comm_uid=1, span=2),
        E(3, "allreduce_wait", comm_uid=1, span=1),
    ])
    assert codes_of(g) == []


# ---------------------------------------------------------------------------
# MPX108 — jaxpr walker (duck-typed fakes)
# ---------------------------------------------------------------------------


class _Prim:
    def __init__(self, name):
        self.name = name


class _Eqn:
    def __init__(self, name, params=None):
        self.primitive = _Prim(name)
        self.params = params or {}


class _Jaxpr:
    def __init__(self, eqns):
        self.eqns = eqns


class _Closed:
    def __init__(self, jaxpr):
        self.jaxpr = jaxpr


def _cond(branches):
    return _Eqn("cond", {"branches": tuple(_Closed(b) for b in branches)})


def test_mpx108_divergent_cond_fires():
    j = _Jaxpr([_cond([_Jaxpr([_Eqn("psum")]), _Jaxpr([_Eqn("add")])])])
    (f,) = walker.check_cond_divergence(_Closed(j))
    assert f.code == "MPX108"
    assert "disagree" in f.message


def test_mpx108_negative_cases():
    # both branches communicate
    j = _Jaxpr([_cond([_Jaxpr([_Eqn("psum")]), _Jaxpr([_Eqn("ppermute")])])])
    assert walker.check_cond_divergence(_Closed(j)) == []
    # neither branch communicates
    j = _Jaxpr([_cond([_Jaxpr([_Eqn("add")]), _Jaxpr([])])])
    assert walker.check_cond_divergence(_Closed(j)) == []
    # no cond at all
    j = _Jaxpr([_Eqn("psum"), _Eqn("add")])
    assert walker.check_cond_divergence(_Closed(j)) == []


def test_walker_descends_nested_jaxprs():
    inner = _Jaxpr([_cond([_Jaxpr([_Eqn("all_gather")]), _Jaxpr([])])])
    outer = _Jaxpr([_Eqn("pjit", {"jaxpr": _Closed(inner)})])
    (f,) = walker.check_cond_divergence(_Closed(outer))
    assert f.code == "MPX108"


def test_collective_primitive_prefixes():
    assert walker.is_collective("psum")
    assert walker.is_collective("psum2")  # jax renames stay covered
    assert walker.is_collective("all_gather_invariant")
    assert not walker.is_collective("add")
    assert not walker.is_collective("cond")


# ---------------------------------------------------------------------------
# report / rendering
# ---------------------------------------------------------------------------


def test_report_render_and_partitions():
    g = G(events=[
        E(0, "send", comm_uid=1, tag=0, dtype="f", shape=(1,)),
        E(1, "allreduce", comm_uid=1, comm_size=8, payload_bytes=1 << 20,
          algo="ring"),
    ], meta={"collective_algo": "auto", "ring_crossover_bytes": 1 << 20})
    findings = checkers.run_checkers(g)
    rep = report.Report(findings=tuple(findings), events=tuple(g.events))
    assert not rep.ok
    assert {f.code for f in rep.errors} == {"MPX101"}
    assert {f.code for f in rep.advisories} == {"MPX109"}
    text = rep.render()
    assert "MPX101" in text and "MPX109" in text and "fix:" in text
    with pytest.raises(report.AnalysisError) as ei:
        rep.raise_if_findings()
    assert {f.code for f in ei.value.findings} == {"MPX101", "MPX109"}


def test_clean_report():
    rep = report.Report()
    assert rep.ok and "clean" in rep.render()
    rep.raise_if_findings()  # no-op


def test_mpx_error_tags_and_appends_code():
    e = report.mpx_error(ValueError, "MPX105", "root 9 out of range")
    assert isinstance(e, ValueError)
    assert e.mpx_code == "MPX105"
    assert str(e).endswith("[MPX105]")
    f = report.finding_from_exception(e)
    assert f.code == "MPX105" and "root 9" in f.message
    assert report.finding_from_exception(ValueError("plain")) is None


# ---------------------------------------------------------------------------
# env mode plumbing
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_analyze_env(monkeypatch):
    monkeypatch.delenv("MPI4JAX_TPU_ANALYZE", raising=False)
    yield
    hook.set_analyze_mode(None)


def test_analyze_mode_parsing(monkeypatch):
    assert config.analyze_mode() == "off"
    monkeypatch.setenv("MPI4JAX_TPU_ANALYZE", "WARN")  # case-insensitive
    assert config.analyze_mode() == "warn"
    monkeypatch.setenv("MPI4JAX_TPU_ANALYZE", "loud")
    with pytest.raises(ValueError, match="MPI4JAX_TPU_ANALYZE"):
        config.analyze_mode()


def test_mode_override_and_cache_token(monkeypatch):
    assert hook.effective_mode() == "off"
    assert hook.analysis_cache_token() == ("off", "auto")
    hook.set_analyze_mode("error")
    assert hook.effective_mode() == "error"
    assert hook.analysis_cache_token() == ("error", "auto")
    # the cross-rank setting is part of the token: flipping it retraces
    monkeypatch.setenv("MPI4JAX_TPU_ANALYZE_RANKS", "off")
    assert hook.analysis_cache_token() == ("error", "off")
    monkeypatch.delenv("MPI4JAX_TPU_ANALYZE_RANKS")
    hook.set_analyze_mode(None)
    monkeypatch.setenv("MPI4JAX_TPU_ANALYZE", "warn")
    assert hook.effective_mode() == "warn"
    with pytest.raises(ValueError, match="analyze mode"):
        hook.set_analyze_mode("loud")


def test_finish_context_warn_and_error_modes():
    class Ctx:
        pass

    def dirty_ctx(mode):
        ctx = Ctx()
        rec = hook.Recorder(mode)
        rec.events.append(E(0, "send", comm_uid=1, tag=0, dtype="f",
                            shape=(1,)))
        ctx.analysis_recorder = rec
        return ctx

    with pytest.warns(UserWarning, match="MPX101"):
        hook.finish_context(dirty_ctx("warn"), "spmd region f")
    with pytest.raises(report.AnalysisError, match="MPX101"):
        hook.finish_context(dirty_ctx("error"), "spmd region f")
    # clean stream: silent in both modes
    ctx = Ctx()
    ctx.analysis_recorder = hook.Recorder("error")
    hook.finish_context(ctx, "spmd region f")


def test_arm_context_respects_mode():
    class Ctx:
        analysis_recorder = None

    ctx = Ctx()
    hook.arm_context(ctx)
    assert ctx.analysis_recorder is None  # off: zero overhead
    hook.set_analyze_mode("warn")
    hook.arm_context(ctx)
    assert ctx.analysis_recorder is not None
    assert ctx.analysis_recorder.mode == "warn"


def test_clear_analysis_caches():
    hook.analyze_cache()["k"] = "v"
    hook.clear_analysis_caches()
    assert hook.analyze_cache() == {}
