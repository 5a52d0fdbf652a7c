"""Environment predicates for subprocess tests.

Some tests spawn a fresh interpreter that ``import mpi4jax_tpu``s; in a
sandbox whose installed JAX is below the package's hard floor
(utils/jax_compat.SUPPORTED_JAX_VERSION) that import refuses by design, so the
subprocess can only ever report the version error.  Those tests carry
``pytest.mark.skipif(not jax_meets_package_floor(), ...)`` — the skip
reason documents that this is a container-environment limitation, not a
product bug (CHANGES.md PR 7 triage).

The floor is read from the source text (not imported): importing
``mpi4jax_tpu.utils.jax_compat`` would execute the package ``__init__``
whose version check is the very thing that refuses.
"""

import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parent.parent


def _versiontuple(v: str):
    parts = []
    for p in v.split("."):
        digits = ""
        for ch in p:
            if ch.isdigit():
                digits += ch
            else:
                break
        parts.append(int(digits) if digits else 0)
    return tuple(parts[:3])


def package_jax_floor() -> str:
    src = (REPO / "mpi4jax_tpu" / "utils" / "jax_compat.py").read_text()
    m = re.search(r'SUPPORTED_JAX_VERSION\s*=\s*"([^"]+)"', src)
    assert m, "SUPPORTED_JAX_VERSION not found in utils/jax_compat.py"
    return m.group(1)


def jax_meets_package_floor() -> bool:
    import jax

    return _versiontuple(jax.__version__) >= _versiontuple(
        package_jax_floor())


SUBPROCESS_IMPORT_SKIP = (
    "container-environment-only failure: the subprocess imports "
    "mpi4jax_tpu, whose jax floor (>= {floor}) the installed jax does "
    "not meet — the import refuses by design (see utils/jax_compat.py "
    "and CHANGES.md PR 7 triage)"
)


def subprocess_import_skip_reason() -> str:
    return SUBPROCESS_IMPORT_SKIP.format(floor=package_jax_floor())
