"""Driver for the shallow-water solver: back-to-back legs of the pinned
whole-run program.

The program is built from the program's public pieces exactly as
``examples/shallow_water.py::solve_fused`` builds it (``make_mesh_and_comm``,
``select_steps(fast, cfg)``, an ``mpx.spmd`` region around the Euler first
step and ``_run_steps``, ``mpx.compile`` of that region), because
``solve_fused`` keeps its runner to itself and compiles on every call.
The state comes from the benchmark (``chipbench/reference/shallow_water.py``
makes it on the device from the seed) and is placed once with
``mpx.shard_global``.

The window calls the one pinned program again and again, each call from the
same retained initial state, each closed by ``jax.block_until_ready``.  The
last leg's six output fields are what ``check`` compares with the plain
reference, once the program's state is freed.
"""

import importlib
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx

_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__)))), "examples")


def _load_program():
    """``examples/shallow_water.py``, the program's solver (no package)."""
    if _EXAMPLES not in sys.path:
        sys.path.insert(0, _EXAMPLES)
    return importlib.import_module("shallow_water")


@jax.jit
def _gaps(out, ref, init):
    """Per field: the widest gap between program and reference over the
    physical domain, the reference's largest value and its largest change
    from the initial state."""
    got = out[0, 1:-1, 1:-1]
    want = ref[1:-1]
    return (jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want)),
            jnp.max(jnp.abs(want - init[1:-1])),
            jnp.sum(jnp.isnan(got) | jnp.isinf(got)))


class Driver:
    def __init__(self, config, traffic, seed, devices, peaks):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.ref = importlib.import_module(
            "chipbench.reference." + config["reference"])
        self.params = self.ref.params(config)
        self.steps = int(traffic["steps_per_leg"])
        self.last = None

    # -- set-up -------------------------------------------------------------

    def initial_fields(self):
        return self.ref.initial_fields(
            self.params, self.seed, int(self.traffic["seeded_modes"]),
            tuple(self.traffic["mode_amplitude_m"]))

    def setup(self):
        sw = _load_program()
        c = self.config
        cfg = sw.Config(
            nx=c["nx"], ny=c["ny"], dx=c["dx"], dy=c["dy"],
            gravity=c["gravity"], depth=c["depth"],
            coriolis_f=c["coriolis_f"], coriolis_beta=c["coriolis_beta"],
            periodic_x=c["periodic_x"], ab_a=c["ab_a"], ab_b=c["ab_b"],
            nproc_y=c["nproc_y"], nproc_x=c["nproc_x"])
        _mesh, comm = sw.make_mesh_and_comm(cfg, devices=self.devices)
        step, chunk, chunk_size = sw.select_steps(c["fast"], cfg)

        @partial(mpx.spmd, comm=comm, static_argnums=(1,))
        def fused(state, total):
            state = step(state, cfg, comm, first_step=True)
            return sw._run_steps(state, total, cfg, comm, step, chunk,
                                 chunk_size)

        self.chunk_size = chunk_size
        self.place = lambda fields: mpx.shard_global(sw.State(*fields), comm)
        start = time.perf_counter()
        self.place_state()
        jax.block_until_ready(self.state)
        placed = time.perf_counter()
        self.program = mpx.compile(fused, self.state, self.steps - 1)
        pinned = time.perf_counter()
        # warm-up: the one shape the window uses
        self.warm_up_leg_s = []
        for _ in range(int(self.traffic["warm_up_legs"])):
            t = time.perf_counter()
            jax.block_until_ready(self.program(self.state))
            self.warm_up_leg_s.append(time.perf_counter() - t)
        self.stages = {"state_s": placed - start, "pin_s": pinned - placed,
                       "warm_up_s": time.perf_counter() - pinned}

    def place_state(self):
        """The seed's initial state, made on the device and placed on the
        mesh once."""
        with jax.default_device(self.devices[0]):
            h, u, v = self.initial_fields()
            fields = [self.ref.with_halo_columns(a)[None] for a in (h, u, v)]
            del h, u, v
            # three buffers, not one used three times: the program keeps
            # six fields of input state, as a caller's running state is
            fields += [jnp.zeros(fields[0].shape, jnp.float32)
                       for _ in range(3)]
        self.state = self.place(fields)

    def compile_count(self) -> int:
        aot = mpx.cache_stats()["aot"]
        return aot["pins"] + aot["compiles"]

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float, traced: bool) -> dict:
        legs_cap = int(self.traffic["trace_legs"]) if traced else None
        leg_s, dispatch_s = [], []  # each leg: whole call, dispatch alone
        out = None
        start = time.perf_counter()
        while True:
            out = None  # the last leg's fields go before the next are made
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("dispatch_leg"):
                out = self.program(self.state)
            dispatched = time.perf_counter()
            with jax.profiler.TraceAnnotation("wait_leg"):
                jax.block_until_ready(out)
            now = time.perf_counter()
            leg_s.append(now - t)
            dispatch_s.append(dispatched - t)
            if now - start >= seconds or len(leg_s) == legs_cap:
                break
        wall = now - start
        self.last = out
        legs = len(leg_s)
        steps = legs * self.steps
        return {
            "attempted": legs, "failed": 0,
            "end_to_end": {
                "steps_per_s_per_chip": steps / wall / len(self.devices)},
            "counters": {"legs": legs, "steps": steps, "window_wall_s": wall,
                         "in_call_wall_s": sum(leg_s),
                         "leg_wall_s": leg_s, "leg_dispatch_s": dispatch_s,
                         "warm_up_leg_s": self.warm_up_leg_s,
                         "steps_per_leg": self.steps,
                         "steps_per_kernel_call": self.chunk_size},
            "span_names": ["dispatch_leg", "wait_leg"],
        }

    # -- after the window ---------------------------------------------------

    def release(self):
        self.program = None
        self.state = None
        self.place = None

    def check(self, precision=jnp.float32):
        """The last leg's final state against the plain reference.  One
        number per field: the widest gap over the physical domain, as a
        share of the reference's largest height (``h``) or velocity
        (``u``, ``v``).  A tendency's gap is taken times the time step
        first: what it would put into its field in one step.  (Against
        their own size the tendencies are all rounding: the jet is in
        geostrophic balance, so each is a small difference of large
        terms.)"""
        limits = self.traffic["limits"]
        with jax.default_device(self.devices[0]):
            init = self.initial_fields()
            ref = self.ref.make_run(self.params, self.steps, precision)(*init)
            zero = jnp.zeros_like(init[0])
            rows = [[float(x) for x in _gaps(o, r, i)] for o, r, i in
                    zip(self.last, ref, (*init, zero, zero, zero))]
        self.last = None
        names = self.ref.FIELDS
        gap = {n: row[0] for n, row in zip(names, rows)}
        top = {n: row[1] for n, row in zip(names, rows)}
        speed = max(top["u"], top["v"])
        dt = self.params["dt"]
        scale = {"h": top["h"], "u": speed, "v": speed,
                 "dh": top["h"] / dt, "du": speed / dt, "dv": speed / dt}
        self.readings = {
            "gap": gap, "scale": scale,
            "moved": {n: row[2] for n, row in zip(names, rows)},
            "nonfinite": sum(row[3] for row in rows)}
        checks = [{"name": f"{n}_gap", "value": gap[n] / scale[n],
                   "limit": limits[f"{n}_gap"]} for n in names]
        checks.append({"name": "nonfinite", "limit": 0,
                       "value": self.readings["nonfinite"]})
        return checks
