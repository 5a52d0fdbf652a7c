"""From a profiler trace (``.xplane.pb``) to busy and idle intervals, time
per device operation, and idle gaps named after what the host was doing.

Two steps, so the arithmetic can be tested without a chip:

- :func:`read_xplane` turns the file into plain lists of
  ``(name, start_ns, duration_ns)``, per device and for the host spans;
- :func:`reduce_events` does every sum on those lists.

A device plane is one named ``/device:TPU:<n>``.  Its operations are the
events of the line ``XLA Ops``; control-flow operations (``while``,
``conditional``, ``call``) enclose the operations of their bodies on that
line, so a per-operation time is a *self* time: an event's duration less
its direct children's.  Busy time is the union of the intervals, which
nesting does not count twice.  Host spans are ``jax.profiler.
TraceAnnotation`` events on the host plane, found by the names the caller
gives.
"""

import functools
import re
from collections import defaultdict

WINDOW_SPAN = "chipbench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[.\d]+$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


@functools.lru_cache(maxsize=None)  # a loop's events repeat the same text
def op_kind(name: str) -> str:
    """The kind of a device operation, from the HLO text the profiler
    gives as the event's name: the opcode, as in ``%psum_invariant.9 =
    f32[268435456]{0:T(1024)} all-reduce(...)`` to ``all-reduce`` (the name
    before the ``=`` is whatever jax called the value); for a fusion, its
    name without XLA's numbering, as ``broadcast_multiply_fusion``.  A
    bare name (``all-reduce.12``) loses its numbering."""
    head, eq, rest = name.partition(" = ")
    label = _SUFFIX.sub("", head.lstrip("%").split(" ", 1)[0]) or head
    if not eq:
        return label
    m = _OPCODE.search(" " + rest)
    if m is None:
        return label
    return label if m.group(1) == "fusion" else m.group(1)


def read_xplane(path: str, span_names=()) -> dict:
    """Raw events of a trace file.  ``span_names`` are the host spans to
    keep besides the window's own."""
    from jax.profiler import ProfileData

    keep = set(span_names) | {WINDOW_SPAN}
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events):
    """``(name, start, end, self_ns)`` for each event of one line, nesting
    resolved by containment."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [ev[2] for ev in events]
    stack = []
    for i in order:
        _, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= dur
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][1] + events[i][2],
             max(self_ns[i], 0)) for i in order]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_events(raw: dict) -> dict:
    """Everything the readers use, from :func:`read_xplane`'s lists.

    The window is the host span ``chipbench_window``; where there is none
    it is the extent of the device events.  Device events are clipped to
    it.  Returns a dict with

    - ``window_ns``: ``(start, end)``;
    - ``devices``: ``{dev: {"ops": [(name, start, end, self_ns)],
      "busy": [(start, end)]}}``;
    - ``host``: ``[(name, start, end)]`` sorted by start;
    - ``busy_s``, ``window_s``: busy seconds averaged over the devices,
      and the window's length.
    """
    host = sorted(((n, s, s + d) for n, s, d in raw["host"]),
                  key=lambda t: t[1])
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[-1][2]
    else:
        starts = [s for evs in raw["devices"].values() for _, s, _ in evs]
        ends = [s + d for evs in raw["devices"].values() for _, s, d in evs]
        lo, hi = (min(starts), max(ends)) if starts else (0, 0)
    devices = {}
    for dev, events in sorted(raw["devices"].items()):
        events = [ev for ev in events if ev[1] + ev[2] > lo and ev[1] < hi]
        ops = _self_times(events)
        busy = _clip(_union((s, e) for _, s, e, _ in ops), lo, hi)
        devices[dev] = {"ops": ops, "busy": busy}
    n = max(len(devices), 1)
    busy_ns = sum(e - s for d in devices.values() for s, e in d["busy"]) / n
    return {
        "window_ns": (lo, hi),
        "devices": devices,
        "host": [h for h in host if h[0] != WINDOW_SPAN],
        "busy_s": busy_ns * 1e-9,
        "window_s": (hi - lo) * 1e-9,
    }


def spans_named(trace: dict, prefix: str):
    """Host spans whose name starts with ``prefix``, as ``(start, end)``."""
    return [(s, e) for n, s, e in trace["host"] if n.startswith(prefix)]


def call_spans(trace: dict, program: str):
    """The host spans of one program's calls, dispatch and wait, as the
    drivers name them."""
    return (spans_named(trace, "dispatch_" + program)
            + spans_named(trace, "wait_" + program))


def op_ns_by_device(trace: dict, intervals, kinds) -> dict:
    """``{dev: self_ns}`` of the operations of ``kinds`` that start inside
    ``intervals``."""
    per_dev = defaultdict(int)
    for dev, _kind, ns in ops_within(trace, intervals, kinds):
        per_dev[dev] += ns
    return dict(per_dev)


def events_within(trace: dict, intervals):
    """``[(dev, name, self_ns)]`` of the device operations that start
    inside any of ``intervals``, under the profiler's full name."""
    merged = _union(intervals)
    out = []
    for dev, d in trace["devices"].items():
        j = 0
        for name, start, _end, self_ns in d["ops"]:  # sorted by start
            while j < len(merged) and merged[j][1] <= start:
                j += 1
            if j == len(merged):
                break
            if merged[j][0] <= start:
                out.append((dev, name, self_ns))
    return out


def ops_within(trace: dict, intervals, kinds=None):
    """``[(dev, kind, self_ns)]`` of the device operations that start
    inside any of ``intervals``; ``kinds`` keeps only those operation
    kinds (see :func:`op_kind`)."""
    return [(dev, op_kind(name), ns)
            for dev, name, ns in events_within(trace, intervals)
            if kinds is None or op_kind(name) in kinds]


def busy_within(trace: dict, intervals) -> float:
    """Busy seconds inside ``intervals``, averaged over the devices."""
    merged = _union(intervals)
    total = 0
    for d in trace["devices"].values():
        for lo, hi in merged:
            total += sum(e - s for s, e in _clip(d["busy"], lo, hi))
    return total * 1e-9 / max(len(trace["devices"]), 1)


def top_ops(trace: dict, n: int = 10):
    """The ``n`` operation kinds with most self time, in seconds averaged
    over the devices."""
    acc = defaultdict(int)
    for d in trace["devices"].values():
        for name, _s, _e, self_ns in d["ops"]:
            acc[op_kind(name)] += self_ns
    k = max(len(trace["devices"]), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in ranked]


def idle_gaps(trace: dict, n: int = 10):
    """The idle time of the first device inside the window, summed under
    the name of the host span that covers most of each gap (``host_idle``
    where none does); the ``n`` largest sums, in seconds."""
    if not trace["devices"]:
        return []
    lo, hi = trace["window_ns"]
    busy = trace["devices"][min(trace["devices"])]["busy"]
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    acc = defaultdict(int)
    host = trace["host"]  # sorted by start; the driver's spans do not nest
    first = 0
    for g_lo, g_hi in gaps:
        while first < len(host) and host[first][2] <= g_lo:
            first += 1
        best, best_ns = "host_idle", 0
        for name, s, e in host[first:]:
            if s >= g_hi:
                break
            cover = min(e, g_hi) - max(s, g_lo)
            if cover > best_ns:
                best, best_ns = name, cover
        acc[best] += g_hi - g_lo
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]
