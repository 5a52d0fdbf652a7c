"""Manifest-driven compile-cache warming (``python -m mpi4jax_tpu.aot warm``).

The persistent tier (diskcache.py) makes a fleet cold-start a
deserialization instead of a compilation — but only AFTER something has
compiled each program once.  The warming CLI closes that loop: a
**program manifest** names each program abstractly (function import path
+ abstract argument shapes), and ``warm`` pins every entry through
``mpx.compile`` with the cache dir set, so the artifacts exist before
the first real job boots.

Manifest (JSON)::

    {
      "programs": [
        {
          "fn": "my_model.serving:decode_step",
          "args": [
            {"shape": [8, 4096], "dtype": "float32"},
            {"static": 16}
          ],
          "unroll": 8,          // optional megastep trip count
          "donate_argnums": [0] // optional
        }
      ]
    }

- ``fn`` is ``"module.path:callable"`` (or dotted-attr after the colon);
- each ``args`` entry is either a template ``{"shape": [...], "dtype":
  "..."}`` (a dynamic argument — pinned abstractly, nothing executes)
  or ``{"static": <json value>}`` (folded; its position becomes a
  ``static_argnums`` entry);
- cache keys fold in the mesh descriptor, so warming must run on a mesh
  matching the fleet's (same device count/kinds/process layout — fake it
  with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` where
  appropriate) and under the same flag configuration.

Exit codes (``__main__.py``): ``0`` every program warmed, ``1`` some
program failed to import/pin (the rest are still attempted), ``2`` the
manifest is unreadable or malformed, or the persistent tier is disabled
(warming without ``MPI4JAX_TPU_COMPILE_CACHE_DIR`` would compile into
the void).  Each success bumps the ``aot.warmed`` meter and the
``warmed`` counter in ``mpx.cache_stats()["aot"]``.

Parsing (:func:`parse_manifest`) is pure Python — the isolated test
loader drives it without jax; only :func:`warm_program` touches jax.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

__all__ = ["ProgramSpec", "ManifestError", "parse_manifest",
           "load_manifest", "warm_program", "warm_from_manifest",
           "EXIT_OK", "EXIT_FAILED", "EXIT_BAD_MANIFEST"]

EXIT_OK = 0
EXIT_FAILED = 1
EXIT_BAD_MANIFEST = 2


class ManifestError(ValueError):
    """The manifest is structurally unusable (exit code 2)."""


@dataclass
class ProgramSpec:
    """One warmable program: the abstract form ``mpx.compile`` needs."""

    fn: str                                  # "module.path:attr.path"
    args: Tuple[dict, ...]                   # raw entries, validated
    static_argnums: Tuple[int, ...] = ()
    unroll: int = 1
    donate_argnums: Tuple[int, ...] = ()
    wrap: Optional[bool] = None
    label: str = field(default="", compare=False)

    def import_path(self) -> Tuple[str, str]:
        mod, _, attr = self.fn.partition(":")
        return mod, attr


def _check_template(i: int, entry, where: str) -> dict:
    if not isinstance(entry, dict):
        raise ManifestError(
            f"{where}: args[{i}] must be an object, got "
            f"{type(entry).__name__}")
    if "static" in entry:
        extra = set(entry) - {"static"}
        if extra:
            raise ManifestError(
                f"{where}: args[{i}] mixes 'static' with {sorted(extra)}")
        return entry
    missing = {"shape", "dtype"} - set(entry)
    if missing:
        raise ManifestError(
            f"{where}: args[{i}] needs 'shape' and 'dtype' (or 'static'); "
            f"missing {sorted(missing)}")
    shape = entry["shape"]
    if (not isinstance(shape, list)
            or any(not isinstance(d, int) or d < 0 for d in shape)):
        raise ManifestError(
            f"{where}: args[{i}].shape must be a list of non-negative "
            f"ints, got {shape!r}")
    if not isinstance(entry["dtype"], str) or not entry["dtype"]:
        raise ManifestError(
            f"{where}: args[{i}].dtype must be a non-empty string")
    return entry


def parse_manifest(obj) -> List[ProgramSpec]:
    """Validate a loaded manifest object into :class:`ProgramSpec`\\ s.

    Raises :class:`ManifestError` on any structural problem — a typo'd
    manifest must fail the whole run loudly (exit 2), not silently warm
    a subset."""
    if not isinstance(obj, dict) or "programs" not in obj:
        raise ManifestError(
            "manifest must be an object with a 'programs' array")
    programs = obj["programs"]
    if not isinstance(programs, list) or not programs:
        raise ManifestError("'programs' must be a non-empty array")
    specs = []
    for n, p in enumerate(programs):
        where = f"programs[{n}]"
        if not isinstance(p, dict):
            raise ManifestError(f"{where} must be an object")
        fn = p.get("fn")
        if not isinstance(fn, str) or ":" not in fn or not fn.partition(
                ":")[2]:
            raise ManifestError(
                f"{where}.fn must be 'module.path:callable', got {fn!r}")
        raw_args = p.get("args")
        if not isinstance(raw_args, list):
            raise ManifestError(f"{where}.args must be an array")
        args = tuple(_check_template(i, a, where)
                     for i, a in enumerate(raw_args))
        statics = tuple(i for i, a in enumerate(args) if "static" in a)
        unroll = p.get("unroll", 1)
        if not isinstance(unroll, int) or unroll < 1:
            raise ManifestError(
                f"{where}.unroll must be a positive int, got {unroll!r}")
        donate = p.get("donate_argnums", [])
        if (not isinstance(donate, list)
                or any(not isinstance(d, int) for d in donate)):
            raise ManifestError(
                f"{where}.donate_argnums must be an array of ints")
        wrap = p.get("wrap")
        if wrap is not None and not isinstance(wrap, bool):
            raise ManifestError(f"{where}.wrap must be a boolean")
        specs.append(ProgramSpec(
            fn=fn, args=args, static_argnums=statics, unroll=unroll,
            donate_argnums=tuple(donate), wrap=wrap,
            label=p.get("label") or fn,
        ))
    return specs


def load_manifest(path: str) -> List[ProgramSpec]:
    """Read + parse a manifest file (:class:`ManifestError` on any
    problem, including unreadable/invalid JSON)."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read manifest {path!r}: {e}") from e
    except json.JSONDecodeError as e:
        raise ManifestError(f"manifest {path!r} is not valid JSON: {e}") from e
    return parse_manifest(obj)


def _resolve_fn(spec: ProgramSpec):
    import importlib

    mod_name, attr_path = spec.import_path()
    mod = importlib.import_module(mod_name)
    target = mod
    for part in attr_path.split("."):
        target = getattr(target, part)
    if not callable(target):
        raise TypeError(f"{spec.fn} resolved to a non-callable "
                        f"{type(target).__name__}")
    return target


def _materialize_args(spec: ProgramSpec) -> tuple:
    import jax
    import numpy as np

    out = []
    for entry in spec.args:
        if "static" in entry:
            v = entry["static"]
            out.append(tuple(v) if isinstance(v, list) else v)
        else:
            out.append(jax.ShapeDtypeStruct(
                tuple(entry["shape"]), np.dtype(entry["dtype"])))
    return tuple(out)


def warm_program(spec: ProgramSpec, comm=None) -> dict:
    """Pin one manifest entry (import -> templates -> ``mpx.compile``).

    Returns a JSON-ready result row; raises on failure (the CLI catches
    per program so one broken entry cannot block the rest)."""
    import time

    from . import pinning

    fn = _resolve_fn(spec)
    args = _materialize_args(spec)
    t0 = time.perf_counter()
    program = pinning.compile(
        fn, *args, comm=comm,
        static_argnums=spec.static_argnums or None,
        donate_argnums=spec.donate_argnums,
        wrap=spec.wrap, unroll=spec.unroll,
    )
    wall = time.perf_counter() - t0
    pinning._stats.warmed += 1
    pinning._meter("aot.warmed")
    return {
        "fn": spec.fn,
        "from_disk": program.from_disk,
        "unroll": program.unroll,
        "key": program.key,
        "pin_wall_s": round(wall, 4),
    }


def warm_from_manifest(path: str, comm=None) -> Tuple[int, dict]:
    """Warm every program in ``path``; returns ``(exit_code, payload)``.

    The persistent tier must be enabled (``MPI4JAX_TPU_COMPILE_CACHE_DIR``)
    — warming compiles ONLY to populate it."""
    from ..utils.config import compile_cache_dir

    if not compile_cache_dir():
        return EXIT_BAD_MANIFEST, {
            "error": "MPI4JAX_TPU_COMPILE_CACHE_DIR is not set: warming "
                     "has no persistent tier to populate (docs/aot.md)",
        }
    try:
        specs = load_manifest(path)
    except ManifestError as e:
        return EXIT_BAD_MANIFEST, {"error": str(e)}
    results, failures = [], []
    for spec in specs:
        try:
            results.append(warm_program(spec, comm=comm))
        except Exception as e:  # noqa: BLE001 - keep warming the rest
            failures.append({"fn": spec.fn, "error": f"{type(e).__name__}: {e}"})
    payload = {
        "manifest": path,
        "warmed": len(results),
        "failed": len(failures),
        "programs": results,
        "failures": failures,
    }
    return (EXIT_OK if not failures else EXIT_FAILED), payload
