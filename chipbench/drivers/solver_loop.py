"""Driver for the shallow-water solver as the documented driver runs it: the
host loop of ``examples/shallow_water.py::solve()``.

A *run* is what ``solve(collect=False)`` does to a state: one call of the
jitted first step, then calls of ``steps_per_call`` Adams-Bashforth steps
until ``run_model_days`` are reached, every call's state the next call's
input, none read back, and one ``jax.block_until_ready`` on the last
state (inside, the program's loop keeps two calls in flight at the most).
The loop is the program's own (``run_multisteps``, the function ``solve()``
runs) over the two programs of ``make_stepper(cfg, comm,
fast=config["fast"])``, called as they come: un-pinned ``mpx.spmd`` regions.  On a tree without ``run_multisteps`` the
driver stops in ``setup()``.

The window is back-to-back runs, each from the same retained initial state
(made on the device by the reference module, placed once with
``mpx.shard_global``, as ``drivers/solver.py`` does).  The last run's final
state is what ``check`` compares with the plain reference run for the same
steps (``drivers/solver_runner.py``'s check: six gaps, ``wall_flow``,
``nonfinite``).

Spans and counters.  A run's dispatch and its one wait lie under the host
spans ``dispatch_leg_run`` / ``wait_leg_run``: the solver's trace readers
(``sw_kernel_roofline``, ``solver_runner.custom_calls_a_leg``) find the
spans of a timed unit by the prefixes ``dispatch_leg`` / ``wait_leg``, and a
run is this driver's.  Counters: ``runs``; ``calls`` (region calls in the
window by program, which the count of the program's ``mpx.region_call``
spans must equal); ``run_plan`` (the program's own count of what a run and
each of its calls is made of); in a traced line
``traced_custom_calls_a_run`` (the runs' custom-call events a run, by
instruction name, to be held against ``run_plan``).
"""

import glob
import importlib
import math
import os
import time

import jax

import mpi4jax_tpu as mpx
from chipbench import harness, trace_reduce

_runner = importlib.import_module("chipbench.drivers.solver_runner")
_load_program = _runner._solver._load_program

DAY_S = 86_400
DISPATCH_SPAN, WAIT_SPAN = "dispatch_leg_run", "wait_leg_run"


class Driver(_runner.Driver):
    def __init__(self, config, traffic, seed, devices, peaks):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.ref = importlib.import_module(
            "chipbench.reference." + config["reference"])
        self.params = self.ref.params(config)
        self.steps_per_call = int(traffic["steps_per_call"])
        # the calls that follow the first step: the first multiple of
        # steps_per_call that reaches the run's model time
        dt = self.params["dt"]
        self.n_iters = max(0, math.ceil(
            (traffic["run_model_days"] * DAY_S - dt)
            / (dt * self.steps_per_call)))
        # a run's steps: what check() asks of the reference
        self.steps = 1 + self.n_iters * self.steps_per_call
        self.last = None

    # -- set-up -------------------------------------------------------------

    def setup(self):
        sw = _load_program()
        if not hasattr(sw, "run_multisteps"):
            raise SystemExit("chipbench: this tree's examples/shallow_water.py "
                             "has no run_multisteps: the cell cannot run on it")
        c = self.config
        cfg = sw.Config(
            nx=c["nx"], ny=c["ny"], dx=c["dx"], dy=c["dy"],
            gravity=c["gravity"], depth=c["depth"],
            coriolis_f=c["coriolis_f"], coriolis_beta=c["coriolis_beta"],
            periodic_x=c["periodic_x"], ab_a=c["ab_a"], ab_b=c["ab_b"],
            nproc_y=c["nproc_y"], nproc_x=c["nproc_x"])
        _mesh, comm = sw.make_mesh_and_comm(cfg, devices=self.devices)
        self.run_multisteps = sw.run_multisteps
        self.first_step, self.multistep = sw.make_stepper(cfg, comm,
                                                          fast=c["fast"])
        self.plan = sw.run_plan(cfg, c["fast"], self.n_iters,
                                self.steps_per_call)
        if self.plan["steps"] != self.steps:
            raise SystemExit(f"chipbench: the program plans {self.plan['steps']}"
                             f" steps a run, the mix asks for {self.steps}")
        self.chunk_size = self.plan["steps_per_kernel_call"]
        self.place = lambda fields: mpx.shard_global(sw.State(*fields), comm)
        start = time.perf_counter()
        self.place_state()
        jax.block_until_ready(self.state)
        placed = time.perf_counter()
        # both programs traced and compiled (or fetched), on the shapes and
        # the static step count every call of the window has
        jax.block_until_ready(
            self.multistep(self.first_step(self.state), self.steps_per_call))
        compiled = time.perf_counter()
        self.warm_up_run_s = []
        for _ in range(int(self.traffic["warm_up_runs"])):
            t = time.perf_counter()
            jax.block_until_ready(self.run())
            self.warm_up_run_s.append(time.perf_counter() - t)
        self.stages = {"state_s": placed - start,
                       "compile_s": compiled - placed,
                       "warm_up_s": time.perf_counter() - compiled}

    # -- the window ---------------------------------------------------------

    def run(self):
        """One run dispatched: the last call's (future) state."""
        return self.run_multisteps(self.first_step, self.multistep,
                                   self.state, self.n_iters,
                                   self.steps_per_call)

    def window(self, seconds: float, traced: bool) -> dict:
        runs_cap = int(self.traffic["trace_runs"]) if traced else None
        run_s, dispatch_s = [], []  # each run: whole, dispatch alone
        out = None
        start = time.perf_counter()
        while True:
            out = None  # the last run's fields go before the next are made
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(DISPATCH_SPAN):
                out = self.run()
            dispatched = time.perf_counter()
            with jax.profiler.TraceAnnotation(WAIT_SPAN):
                jax.block_until_ready(out)
            now = time.perf_counter()
            run_s.append(now - t)
            dispatch_s.append(dispatched - t)
            if now - start >= seconds or len(run_s) == runs_cap:
                break
        wall = now - start
        self.last = out
        runs = len(run_s)
        steps = runs * self.steps
        counters = {
            "runs": runs, "steps": steps, "window_wall_s": wall,
            "calls": {"first_step": runs, "multistep": runs * self.n_iters},
            "in_call_wall_s": sum(run_s), "run_wall_s": run_s,
            "run_dispatch_s": dispatch_s,
            "warm_up_run_s": self.warm_up_run_s,
            "steps_per_run": self.steps,
            "steps_per_call": self.steps_per_call,
            "steps_per_kernel_call": self.chunk_size,
            "run_plan": self.plan}
        span_names = [DISPATCH_SPAN, WAIT_SPAN]
        # the line's own dict: check() adds what the trace holds
        self.traced = (counters, span_names) if traced else None
        return {
            "attempted": runs, "failed": 0,
            "end_to_end": {
                "steps_per_s_per_chip": steps / wall / len(self.devices)},
            "counters": counters, "span_names": span_names}

    # -- after the window ---------------------------------------------------

    def hold_trace_against_plan(self):
        """A traced run: the trace the harness has just closed (it reduces
        and removes it after ``check``), its custom calls counted beside
        ``run_plan``."""
        counters, span_names = self.traced
        files = glob.glob(os.path.join(harness.ROOT, harness.TRACE_DIR, "**",
                                       "*.xplane.pb"), recursive=True)
        if not files:
            return
        raw = trace_reduce.read_xplane(max(files, key=os.path.getmtime),
                                       span_names)
        calls = _runner.custom_calls_a_leg(raw, counters["runs"])
        if calls is not None:
            counters["traced_custom_calls_a_run"] = calls

    def release(self):
        super().release()
        self.first_step = self.multistep = self.run_multisteps = None
