"""The benchmark's harness: one cell, once, in this process.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name (see README.md):

- the cell, its configuration's file and the metrics it reports come from
  ``BENCHMARK.json`` at the root of the checkout;
- the traffic mix is ``<base>/traffic/<traffic>.json``, the driver
  ``<base>/drivers/<driver>.py`` (``driver`` is a key of the configuration's
  file), a per-layer metric ``<base>/layer_metrics/<metric>.py``; ``<base>``
  is the directory two levels above the configuration's file.

A driver module exposes ``Driver(config, traffic, seed, devices, peaks)``
with ``setup()``, ``window(seconds, traced)``, ``release()`` and
``check()``.  The harness owns the clock of ``setup_s`` (process start to
the first measured call, less the TPU runtime's own start-up), the device gate,
the profiler, the memory reading, the result line and ``correct``.
"""

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

from . import trace_reduce, work

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = ".chipbench_trace"


class Refused(SystemExit):
    """The run cannot stand for the cell: wrong platform, unknown device,
    too few chips, unknown name.  Exit code 2, no result line."""

    def __init__(self, why: str):
        print(f"chipbench: refused: {why}", file=sys.stderr)
        super().__init__(2)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A driver or a reader, loaded from its file (names may hold dots)."""
    if not os.path.isfile(path):
        raise Refused(f"no file {path}")
    name = "chipbench_file_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(root: str, workload: str) -> dict:
    """Everything a run of ``workload`` needs, by name from
    ``BENCHMARK.json`` and the data files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config_path = os.path.join(root, entry["file"])
    base = os.path.dirname(os.path.dirname(config_path))
    config = load_json(config_path)
    traffic = load_json(os.path.join(base, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "bench": bench, "cell": cell, "config": config, "traffic": traffic,
        "base": base,
        "driver_path": os.path.join(base, "drivers",
                                    config["driver"] + ".py"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def gate_devices(chips: int, peaks_path: str):
    """The devices of this machine, or a refusal: any platform but ``tpu``,
    a ``device_kind`` the table of peaks does not know, fewer chips than
    the cell asks for."""
    import jax

    devices = jax.devices()
    return check_devices(devices, chips, load_json(peaks_path))


def check_devices(devices, chips: int, peaks: dict):
    first = devices[0]
    if first.platform != "tpu":
        raise Refused(f"needs a TPU, found platform {first.platform!r}")
    if first.device_kind not in peaks:
        raise Refused(f"device kind {first.device_kind!r} is not in the "
                      "table of peaks")
    if len(devices) < chips:
        raise Refused(f"cell needs {chips} chips, found {len(devices)}")
    return list(devices), peaks[first.device_kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


_COMPILE_EVENTS = [0]
_LISTENING = [False]


def count_compile_events() -> int:
    """How many programs jax has compiled, or fetched from its persistent
    cache, in this process since the first call of this function."""
    if not _LISTENING[0]:
        import jax.monitoring

        def on_event(event, _duration, **_kw):
            if event.endswith(("backend_compile_duration",
                               "cache_retrieval_time_sec")):
                _COMPILE_EVENTS[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        _LISTENING[0] = True
    return _COMPILE_EVENTS[0]


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotation
    return opts


def _layer_metrics(resolved, ctx):
    def reader(name):
        return load_module(os.path.join(resolved["base"], "layer_metrics",
                                        name + ".py"))

    ctx["reader"] = reader  # a reader may build on another by name
    out = {}
    for metric in resolved["per_layer"]:
        value = reader(metric["name"]).read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        root: str = ROOT, t0: float = None, devices=None, peaks=None,
        driver_hook=None) -> dict:
    """Run one cell and return the result line as a dict.

    ``devices``/``peaks`` given means the caller has already decided what
    to run on (the tests, which have no chip); otherwise the gate decides.
    ``driver_hook(driver)`` is called after set-up and before the window:
    the tests put the control in the program's place, or break the timed
    path, through it."""
    import jax

    t0 = time.perf_counter() if t0 is None else t0
    stages = {"imports_s": time.perf_counter() - t0, "runtime_start_s": 0.0}
    resolved = resolve_cell(root, workload)
    cell = resolved["cell"]
    if devices is None:
        asked = time.perf_counter()
        devices, peaks = gate_devices(
            cell["chips"], os.path.join(resolved["base"], "peaks.json"))
        stages["runtime_start_s"] = time.perf_counter() - asked
        from mpi4jax_tpu.utils.compile_cache import ensure_compile_cache

        ensure_compile_cache()
    used = devices[: cell["chips"]]
    count_compile_events()

    driver = load_module(resolved["driver_path"]).Driver(
        config=resolved["config"], traffic=resolved["traffic"], seed=seed,
        devices=used, peaks=peaks)
    driver.setup()
    if driver_hook is not None:
        driver_hook(driver)
    compiles_before = driver.compile_count() + count_compile_events()
    # the TPU runtime's own start-up (the first ``jax.devices()``) is left
    # out: 11 to 16 s in which no code of this repository runs, and which
    # vary by more than everything else in set-up together (PERF.md)
    setup_s = time.perf_counter() - t0 - stages["runtime_start_s"]

    trace_dir = os.path.join(root, TRACE_DIR, workload)
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            window = driver.window(seconds, traced)
    finally:
        if traced:
            jax.profiler.stop_trace()
    # where set-up went
    window["counters"]["setup_stages"] = dict(
        stages, **getattr(driver, "stages", {}), setup_s=setup_s)
    window["counters"]["compiles_in_window"] = (
        driver.compile_count() + count_compile_events() - compiles_before)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(used)}
    driver.release()
    checks = driver.check()

    if traced:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        raw = trace_reduce.read_xplane(files[0], window["span_names"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace = trace_reduce.reduce_events(raw)
        ctx = {"trace": trace, "counters": window["counters"],
               "config": resolved["config"], "traffic": resolved["traffic"],
               "peaks": peaks, "chips": cell["chips"], "work": work,
               "reduce": trace_reduce}
        metrics = _layer_metrics(resolved, ctx)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        breakdown = {"device_ops": trace_reduce.top_ops(trace),
                     "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in resolved["end_to_end"]}
        breakdown = None

    correct = (bool(checks) and window["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks))  # NaN fails
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["seed"] = seed
    result["workload"] = workload
    result["counters"] = window["counters"]
    result["readings"] = getattr(driver, "readings", None)
    result["compared"] = {c["name"]: [c["value"], c["limit"]]
                          for c in checks}
    return result


def report(result: dict) -> None:
    """Each number compared beside its limit as the last lines on standard
    error, then the result as the last line on standard output."""
    sys.stdout.flush()
    for name, (value, limit) in result["compared"].items():
        verdict = "ok" if value <= limit else "NOT OK"
        print(f"chipbench: compared {name} = {value:.6g} (limit {limit:.6g})"
              f" {verdict}", file=sys.stderr)
    print(f"chipbench: correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, t0: float = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 t0=t0)
    report(result)
    return 0
