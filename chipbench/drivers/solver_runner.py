"""Driver for the shallow-water solver through the program's own whole-run
region: back-to-back legs of ``fused_runner``'s program, pinned.

Where ``drivers/solver.py`` rebuilds the non-wide branch of ``solve_fused``
from the program's pieces, this driver asks the program for the region
itself (``examples/shallow_water.py::fused_runner``, the function
``solve_fused`` runs), so a configuration gets what ``select_steps`` gives
it — for a walled or a decomposed domain the wide-halo kernel on the carried
widened frame.  On a tree without ``fused_runner`` it stops in ``setup()``.

Everything else is that driver's: the state made on the device by the
reference module and placed once with ``mpx.shard_global``, the pin, the
warm-up legs, the window (spans ``dispatch_leg`` / ``wait_leg``, the same
counters), so the solver's per-layer readers read either.  Added: the
counter ``leg_plan`` (the program's own count of what a leg is made of), in
a traced run the counter ``traced_custom_calls_a_leg`` (what the device
trace holds of it), and, in ``check``, ``wall_flow`` beside the six gaps.
"""

import glob
import importlib
import os
import re
import time
from collections import Counter

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx
from chipbench import harness, trace_reduce

_solver = importlib.import_module("chipbench.drivers.solver")
_NUMBERING = re.compile(r"\.\d+$")


def custom_calls_a_leg(raw: dict, legs: int):
    """``{instruction: events per leg and device}`` of the custom calls that
    start inside the legs' spans, from :func:`trace_reduce.read_xplane`'s
    lists; an instruction goes by its name less XLA's numbering
    (``sw_wide_x2``; ``custom-call`` for one XLA named itself).  To be held
    against ``leg_plan`` by whoever reads the line: ``sw_kernel_roofline``
    reads the instruction with the most events, which is the loop's kernel
    only while no other custom call leaves as many.  ``None`` where the
    trace has no device."""
    trace = trace_reduce.reduce_events(raw)
    if not trace["devices"] or not legs:
        return None
    spans = trace_reduce.call_spans(trace, "leg")
    counts = Counter(
        _NUMBERING.sub("", name.partition(" = ")[0].lstrip("%"))
        for _dev, name, _ns in trace_reduce.events_within(trace, spans)
        if trace_reduce.op_kind(name) == "custom-call")
    per = len(trace["devices"]) * legs
    return {name: n / per for name, n in sorted(counts.items())}


@jax.jit
def _wall_cells_over(u, v, most):
    """How many cells of ``u`` on the east wall column and of ``v`` on the
    north wall row hold more than ``most``, and the most each holds."""
    east, north = jnp.abs(u[0, 1:-1, -2]), jnp.abs(v[0, -2, 1:-1])
    return (jnp.sum(east > most) + jnp.sum(north > most),
            jnp.max(east), jnp.max(north))


class Driver(_solver.Driver):
    traced = None  # a traced window's (counters, span names)

    def setup(self):
        sw = _solver._load_program()
        if not hasattr(sw, "fused_runner"):
            raise SystemExit("chipbench: this tree's examples/shallow_water.py "
                             "has no fused_runner: the cell cannot run on it")
        c = self.config
        cfg = sw.Config(
            nx=c["nx"], ny=c["ny"], dx=c["dx"], dy=c["dy"],
            gravity=c["gravity"], depth=c["depth"],
            coriolis_f=c["coriolis_f"], coriolis_beta=c["coriolis_beta"],
            periodic_x=c["periodic_x"], ab_a=c["ab_a"], ab_b=c["ab_b"],
            nproc_y=c["nproc_y"], nproc_x=c["nproc_x"])
        _mesh, comm = sw.make_mesh_and_comm(cfg, devices=self.devices)
        self.fused, self.chunk_size = sw.fused_runner(cfg, comm, c["fast"])
        self.plan = sw.leg_plan(cfg, c["fast"], self.steps)
        self.place = lambda fields: mpx.shard_global(sw.State(*fields), comm)
        start = time.perf_counter()
        self.place_state()
        jax.block_until_ready(self.state)
        placed = time.perf_counter()
        self.program = mpx.compile(self.fused, self.state, self.steps - 1)
        pinned = time.perf_counter()
        # warm-up: the one shape the window uses
        self.warm_up_leg_s = []
        for _ in range(int(self.traffic["warm_up_legs"])):
            t = time.perf_counter()
            jax.block_until_ready(self.program(self.state))
            self.warm_up_leg_s.append(time.perf_counter() - t)
        self.stages = {"state_s": placed - start, "pin_s": pinned - placed,
                       "warm_up_s": time.perf_counter() - pinned}

    def window(self, seconds: float, traced: bool) -> dict:
        window = super().window(seconds, traced)
        window["counters"]["leg_plan"] = self.plan
        # the line's own dict: check() adds what the trace holds
        self.traced = (window["counters"], window["span_names"]) if traced \
            else None
        return window

    def hold_trace_against_plan(self):
        """A traced run: the trace the harness has just closed (it reduces
        and removes it after ``check``), counted beside ``leg_plan``."""
        counters, span_names = self.traced
        files = glob.glob(os.path.join(harness.ROOT, harness.TRACE_DIR, "**",
                                       "*.xplane.pb"), recursive=True)
        if not files:
            return
        raw = trace_reduce.read_xplane(max(files, key=os.path.getmtime),
                                       span_names)
        calls = custom_calls_a_leg(raw, counters["legs"])
        if calls is not None:
            counters["traced_custom_calls_a_leg"] = calls

    def release(self):
        super().release()
        self.fused = None

    def check(self, precision=jnp.float32):
        """The last leg's final state against the plain reference: the six
        gaps as ``drivers/solver.py`` scales them (a share of the
        reference's largest height or speed; a tendency's times the time
        step first), the count of non-finite values, and ``wall_flow``: the
        cells of ``u`` on the east wall column and of ``v`` on the north
        wall row that hold more than twice what the one friction substep
        after the last wall condition can put there (the configuration's
        ``assumed.wall_flow``)."""
        if self.traced:
            self.hold_trace_against_plan()
        limits = self.traffic["limits"]
        names = self.ref.FIELDS
        with jax.default_device(self.devices[0]):
            init = self.initial_fields()
            ref = self.ref.make_run(self.params, self.steps, precision)(*init)
            zero = jnp.zeros_like(init[0])
            # this reference keeps its border columns; that driver's does not
            rows = [[float(x) for x in _solver._gaps(o, r[:, 1:-1],
                                                     i[:, 1:-1])]
                    for o, r, i in zip(self.last, ref,
                                       (*init, zero, zero, zero))]
            del ref, init, zero
            top = {n: row[1] for n, row in zip(names, rows)}
            speed = max(top["u"], top["v"])
            most = 2.0 * self.ref.friction_increment(self.params) * speed
            over, east, north = (float(x) for x in _wall_cells_over(
                self.last.u, self.last.v, most))
        self.last = None
        gap = {n: row[0] for n, row in zip(names, rows)}
        dt = self.params["dt"]
        scale = {"h": top["h"], "u": speed, "v": speed,
                 "dh": top["h"] / dt, "du": speed / dt, "dv": speed / dt}
        self.readings = {
            "gap": gap, "scale": scale,
            "moved": {n: row[2] for n, row in zip(names, rows)},
            "nonfinite": sum(row[3] for row in rows),
            "wall_m_per_s": {"east_u": east, "north_v": north, "most": most}}
        checks = [{"name": f"{n}_gap", "value": gap[n] / scale[n],
                   "limit": limits[f"{n}_gap"]} for n in names]
        checks.append({"name": "wall_flow", "limit": limits["wall_flow"],
                       "value": over})
        checks.append({"name": "nonfinite", "limit": 0,
                       "value": self.readings["nonfinite"]})
        return checks
