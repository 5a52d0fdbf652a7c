"""Pure half of the megastep execution suite (docs/aot.md "Megastep
execution").

Everything here runs WITHOUT importing mpi4jax_tpu (the isolated loader
below, mirroring tests/test_aot_pure.py), so the loop machinery's pure
core is verified under any JAX version:

- the MPX130 span-straddle checker on hand-built graphs, the MPX128
  loop-body exemption, and both catalog rows;
- the cache-warming manifest parser (aot/warm.py): schema validation,
  static/template splitting, exit-code mapping, the disabled-tier
  refusal;
- megastep granularity plumbing: ``validate_unroll``,
  ``elastic.align_commit_every``, and the ``elastic.run``
  budget/stride validation;
- the journal's synthesized per-step latency estimate (megastep bracket
  latency / unroll -> the ``megastep_step`` histogram).

The traced half (megastep == N eager steps bit-identity, MPX130 through
analyze/env=error, the elastic shrink drill, HLO identity at unroll=1)
is tests/test_megastep.py, which needs jax >= the package floor.
"""

import importlib
import pathlib
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "mpi4jax_tpu"

_ISO_NAME = "_mpx_megastep_iso"


def _load_isolated():
    """Load the pure-Python megastep stack under a private package name
    (bypasses mpi4jax_tpu/__init__.py and its JAX floor; state isolated
    from any real import in the same process)."""
    if _ISO_NAME in sys.modules:
        return sys.modules[_ISO_NAME]
    root = types.ModuleType(_ISO_NAME)
    root.__path__ = [str(PKG)]
    sys.modules[_ISO_NAME] = root
    for sub in ("utils", "analysis", "telemetry", "resilience", "aot",
                "parallel"):
        m = types.ModuleType(f"{_ISO_NAME}.{sub}")
        m.__path__ = [str(PKG / sub)]
        sys.modules[f"{_ISO_NAME}.{sub}"] = m
        setattr(root, sub, m)
    for mod in (
        "utils.config",
        "analysis.report",
        "analysis.graph",
        "analysis.checkers",
        "telemetry.core",
        "telemetry.journal",
        "resilience.elastic",
        "aot.invalidation",
        "aot.warm",
        "parallel.megastep",
    ):
        importlib.import_module(f"{_ISO_NAME}.{mod}")
    return root


ISO = _load_isolated()
config = ISO.utils.config
report = ISO.analysis.report
graph_mod = ISO.analysis.graph
checkers = ISO.analysis.checkers
tcore = ISO.telemetry.core
journal = ISO.telemetry.journal
elastic = ISO.resilience.elastic
inv = ISO.aot.invalidation
warm = ISO.aot.warm
megastep = ISO.parallel.megastep


# ---------------------------------------------------------------------------
# catalog + checker registry
# ---------------------------------------------------------------------------


def test_mpx130_in_catalog_and_registry():
    assert report.CODES["MPX130"].severity == report.ERROR
    assert "megastep" in report.CODES["MPX130"].title
    assert "MPX130" in checkers.registered_codes()


# ---------------------------------------------------------------------------
# MPX130: span straddles a megastep loop boundary
# ---------------------------------------------------------------------------


def _span_events(start_loop, wait_loop, span=7, include_wait=True):
    evts = [graph_mod.CollectiveEvent(
        index=0, op="allreduce_start", comm_uid=1, reduction="sum",
        dtype="float32", shape=(8,), span=span, loop=start_loop,
        unroll=4 if start_loop is not None else None)]
    if include_wait:
        evts.append(graph_mod.CollectiveEvent(
            index=1, op="allreduce_wait", comm_uid=1, reduction="sum",
            dtype="float32", shape=(8,), span=span, loop=wait_loop,
            unroll=4 if wait_loop is not None else None))
    return evts


def _findings(events):
    graph = graph_mod.CollectiveGraph(events=events, meta={"pinned": False})
    return checkers.check_megastep_span_straddle(graph)


def test_mpx130_clean_when_span_inside_one_iteration():
    assert not _findings(_span_events(start_loop=1, wait_loop=1))


def test_mpx130_clean_outside_any_loop():
    assert not _findings(_span_events(start_loop=None, wait_loop=None))


def test_mpx130_start_inside_wait_outside():
    findings = _findings(_span_events(start_loop=1, wait_loop=None))
    assert len(findings) == 1
    f = findings[0]
    assert f.code == "MPX130" and f.severity == "error"
    assert "straddles" in f.message
    assert "unroll" in f.suggestion


def test_mpx130_wait_inside_start_outside():
    findings = _findings(_span_events(start_loop=None, wait_loop=2))
    assert len(findings) == 1
    assert "start is not" in findings[0].message


def test_mpx130_spanning_two_different_loops():
    findings = _findings(_span_events(start_loop=1, wait_loop=2))
    assert len(findings) == 1 and findings[0].code == "MPX130"


def test_mpx130_unwaited_start_inside_loop():
    findings = _findings(
        _span_events(start_loop=3, wait_loop=None, include_wait=False))
    assert len(findings) == 1
    assert "*_wait" in findings[0].message


def test_mpx130_multiple_spans_report_separately():
    events = (_span_events(start_loop=1, wait_loop=None, span=1)
              + _span_events(start_loop=2, wait_loop=2, span=2))
    assert len(_findings(events)) == 1  # only span 1 straddles


# ---------------------------------------------------------------------------
# MPX128: loop-body events are exempt, advisory recommends unroll=
# ---------------------------------------------------------------------------


def _hot_events(n, loop=None):
    return [graph_mod.CollectiveEvent(
        index=i, op="allreduce", comm_uid=1, reduction="sum",
        dtype="float32", shape=(8,), loop=loop,
        unroll=None if loop is None else 8)
        for i in range(n)]


def test_mpx128_skips_megastep_loop_body_events():
    n = checkers.AOT_ADVISORY_MIN_REPEATS
    graph = graph_mod.CollectiveGraph(events=_hot_events(n, loop=5),
                                      meta={"pinned": False})
    assert not checkers.check_unpinned_hot_loop(graph)
    # the same stream outside any loop still fires
    graph = graph_mod.CollectiveGraph(events=_hot_events(n),
                                      meta={"pinned": False})
    assert checkers.check_unpinned_hot_loop(graph)


def test_mpx128_advisory_recommends_unroll():
    n = checkers.AOT_ADVISORY_MIN_REPEATS
    graph = graph_mod.CollectiveGraph(events=_hot_events(n),
                                      meta={"pinned": False})
    (finding,) = checkers.check_unpinned_hot_loop(graph)
    assert "unroll=" in finding.suggestion
    assert "megastep" in finding.suggestion


# ---------------------------------------------------------------------------
# cache-warming manifest (aot/warm.py)
# ---------------------------------------------------------------------------


def _manifest(**program_over):
    program = {
        "fn": "my.mod:step",
        "args": [{"shape": [8, 16], "dtype": "float32"}, {"static": 4}],
        "unroll": 8,
        "donate_argnums": [0],
    }
    program.update(program_over)
    return {"programs": [program]}


def test_parse_manifest_splits_statics_and_templates():
    (spec,) = warm.parse_manifest(_manifest())
    assert spec.fn == "my.mod:step"
    assert spec.import_path() == ("my.mod", "step")
    assert spec.static_argnums == (1,)
    assert spec.unroll == 8
    assert spec.donate_argnums == (0,)
    assert spec.args[0]["shape"] == [8, 16]


@pytest.mark.parametrize("bad, match", [
    ({"programs": []}, "non-empty"),
    ({"nope": 1}, "programs"),
    (_manifest(fn="no_colon"), "module.path:callable"),
    (_manifest(args=[{"shape": [4]}]), "dtype"),
    (_manifest(args=[{"static": 1, "shape": [4]}]), "mixes"),
    (_manifest(args=[{"shape": [-1], "dtype": "f32"}]), "non-negative"),
    (_manifest(unroll=0), "unroll"),
    (_manifest(donate_argnums="x"), "donate_argnums"),
    (_manifest(wrap="yes"), "wrap"),
])
def test_parse_manifest_rejects_malformed(bad, match):
    with pytest.raises(warm.ManifestError, match=match):
        warm.parse_manifest(bad)


def test_load_manifest_unreadable_and_invalid(tmp_path):
    with pytest.raises(warm.ManifestError, match="cannot read"):
        warm.load_manifest(str(tmp_path / "missing.json"))
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(warm.ManifestError, match="not valid JSON"):
        warm.load_manifest(str(bad))


def test_warm_refuses_without_cache_dir(monkeypatch, tmp_path):
    monkeypatch.delenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", raising=False)
    code, payload = warm.warm_from_manifest(str(tmp_path / "m.json"))
    assert code == warm.EXIT_BAD_MANIFEST
    assert "COMPILE_CACHE_DIR" in payload["error"]


def test_warm_bad_manifest_exit_code(monkeypatch, tmp_path):
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    code, payload = warm.warm_from_manifest(str(tmp_path / "missing.json"))
    assert code == warm.EXIT_BAD_MANIFEST
    assert "error" in payload


def test_warm_failed_import_exit_code(monkeypatch, tmp_path):
    monkeypatch.setenv("MPI4JAX_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    path = tmp_path / "m.json"
    import json

    path.write_text(json.dumps({"programs": [{
        "fn": "definitely_not_a_module_xyz:step",
        "args": [{"shape": [4], "dtype": "float32"}],
    }]}))
    code, payload = warm.warm_from_manifest(str(path))
    assert code == warm.EXIT_FAILED
    assert payload["failed"] == 1 and payload["warmed"] == 0
    assert payload["failures"][0]["fn"].startswith("definitely_not")


# ---------------------------------------------------------------------------
# megastep granularity plumbing
# ---------------------------------------------------------------------------


def test_validate_unroll():
    assert megastep.validate_unroll(1) == 1
    assert megastep.validate_unroll(64) == 64
    with pytest.raises(ValueError, match=">= 1"):
        megastep.validate_unroll(0)
    with pytest.raises(TypeError, match="positive integer"):
        megastep.validate_unroll(None)
    assert not megastep.tracing_megastep()


def test_align_commit_every():
    assert elastic.align_commit_every(1, 8) == 8
    assert elastic.align_commit_every(8, 8) == 8
    assert elastic.align_commit_every(9, 8) == 16
    assert elastic.align_commit_every(5, 1) == 5
    assert elastic.align_commit_every(3, 4) == 4


def test_elastic_run_rejects_misaligned_budget():
    class MegaStep:
        unroll = 8

        def __call__(self, state, step, comm):  # pragma: no cover
            return state

    # the validation fires before any store/watchdog touch, so a bare
    # None store is fine — the point is the error, not the loop
    with pytest.raises(ValueError, match="multiple of the step function"):
        elastic.run(MegaStep(), None, None, steps=10)


def test_unroll_default_flag_stales_pins(monkeypatch):
    ws = inv.WorldStamp.capture()
    monkeypatch.setenv("MPI4JAX_TPU_UNROLL_DEFAULT", "8")
    # the default unroll SHAPES traces: moving it must revoke pins
    assert not ws.is_current()


def test_new_flags_declared_and_parsed(monkeypatch):
    assert "MPI4JAX_TPU_UNROLL_DEFAULT" in config.FLAGS
    assert config.unroll_default() == 1
    monkeypatch.setenv("MPI4JAX_TPU_UNROLL_DEFAULT", "16")
    assert config.unroll_default() == 16
    monkeypatch.setenv("MPI4JAX_TPU_UNROLL_DEFAULT", "0")
    with pytest.raises(ValueError):
        config.unroll_default()


# ---------------------------------------------------------------------------
# the journal's synthesized per-step estimate
# ---------------------------------------------------------------------------


def test_journal_megastep_per_step_estimate(monkeypatch):
    monkeypatch.delenv("MPI4JAX_TPU_TELEMETRY_DIR", raising=False)
    tcore.reset()
    try:
        meta = {"op": "megastep", "unroll": 8, "comm_uid": "3",
                "axes": ["x"], "bytes": 0, "dtype": ""}
        journal.begin("cafecafe", 0, meta)
        journal.end("cafecafe", 0, {"algo": "loop"})
        snap = tcore.snapshot()
        mega_key = tcore.op_key("megastep", "3", "loop", "")
        step_key = tcore.op_key("megastep_step", "3", "estimate", "")
        assert "latency" in snap["ops"][mega_key]
        step_hist = snap["ops"][step_key]["latency"]
        assert step_hist["count"] == 1
        # the estimate is bracket latency / unroll
        mega_hist = snap["ops"][mega_key]["latency"]
        assert step_hist["sum"] == pytest.approx(mega_hist["sum"] / 8)
    finally:
        tcore.reset()


def test_journal_single_step_records_no_estimate():
    tcore.reset()
    try:
        journal.begin("beefbeef", 0, {"op": "megastep", "unroll": 1,
                                      "comm_uid": "3"})
        journal.end("beefbeef", 0, {})
        step_key = tcore.op_key("megastep_step", "3", "estimate", "")
        assert step_key not in tcore.snapshot()["ops"]
    finally:
        tcore.reset()
