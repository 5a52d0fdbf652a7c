"""The program's own kept spans of its build path, up to the window's first
call.  No metric of its own: the readers of ``setup_programs_compiled``,
``setup_build_fetch_s`` and ``setup_build_host_s`` build on it.

The program keeps a span round every build — ``mpx.pin`` round a pin,
``mpx.build`` round the first call of a region's or an eager op's new
program (``mpi4jax_tpu/utils/profiling.py``) — whether or not a profiler
session runs, and writes on it what jax itself said under it: the seconds
of tracing, lowering, compiling and fetching, and ``origin`` (``compiled``,
``jax_cache``, ``package_cache``).  They reach a reader in-process, through
``profiling.builds()``.  A *build* is a kept record whose ``attrs["build"]``
is its own ``id`` (the stages inside it name the build's id there).

**Before the window's first call** is decided on the program's own clock:
a build's ``end_ns`` against the start of the first span of the traced
session that is not a kept one — the window's first ``mpx.call`` or
``mpx.region_call``; both are ``time.time_ns()``.

**The counter at the same boundary**: the kept ``mpx.pin`` builds must be as
many as ``mpx.cache_stats()["aot"]["pins"]`` says and, where any was kept,
as many as the driver's programs (the keys of ``counters.calls``, else the
one program of a cell that counts ``legs``).  Where that does not hold,
where a kept span was dropped, where the program has no ``builds`` (a parent
commit), where none was kept or the session has no span, or the trace no
device plane (no chip), :func:`before_window` returns ``None`` and every
reader leaves its metric out.  What it found goes to standard error once a
run, beside the harness's own lines.
"""

import json
import sys

SECONDS = ("trace_s", "lower_s", "fetch_s", "compile_s")


def program_builds():
    """``(kept records, how many were dropped, session records)`` of this
    process, or ``None`` where the program keeps none."""
    try:
        from mpi4jax_tpu.utils import profiling

        return (profiling.builds(), profiling.builds_dropped(),
                profiling.spans())
    except (ImportError, AttributeError):
        return None


def pins_counted() -> int:
    import mpi4jax_tpu as mpx

    return mpx.cache_stats()["aot"]["pins"]


def duration_s(record) -> float:
    return (record["end_ns"] - record["start_ns"]) * 1e-9


def host_s(build) -> float:
    """A build's time outside jax's fetch and XLA's compile: tracing,
    lowering, keys and the library's own work (for an ``mpx.build``, the
    first call's launch too)."""
    attrs = build["attrs"]
    return (duration_s(build) - attrs.get("fetch_s", 0.0)
            - attrs.get("compile_s", 0.0))


def _read(ctx):
    found = program_builds()
    if found is None or not ctx["trace"]["devices"]:
        return None
    records, dropped, session = found
    calls = [r["start_ns"] for r in session if "build" not in r["attrs"]]
    if not records or dropped or not calls:
        return None
    builds = [r for r in records if r["attrs"]["build"] == r["id"]]
    pins = sum(r["attrs"].get("kind") == "pin" for r in builds)
    counters = ctx["counters"]
    programs = len(counters["calls"]) if "calls" in counters else 1
    counted = pins_counted()
    agree = pins == counted and pins in (0, programs)
    first_call = min(calls)
    print("chipbench: builds: " + json.dumps({
        "kept_pins": pins, "counted_pins": counted,
        "driver_programs": programs, "agree": agree,
        "builds": [dict({"name": r["name"],
                         "seconds": round(duration_s(r), 6),
                         "before_window": r["end_ns"] <= first_call},
                        **{k: (round(v, 6) if isinstance(v, float) else v)
                           for k, v in r["attrs"].items() if k != "build"})
                   for r in builds]}), file=sys.stderr)
    if not agree:
        return None
    return [r for r in builds if r["end_ns"] <= first_call]


def before_window(ctx):
    """The builds that ended before the window's first call, in order of
    their end, or ``None``; worked out once a run and kept in ``ctx``."""
    if "setup_builds" not in ctx:
        ctx["setup_builds"] = _read(ctx)
    return ctx["setup_builds"]
