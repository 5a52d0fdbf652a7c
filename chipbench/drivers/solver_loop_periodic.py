"""Driver for the periodic shallow-water solver as the documented driver runs
it: ``drivers/solver_loop.py``'s host loop with ``drivers/solver.py``'s check.

``solver_loop.Driver`` takes its ``check`` from ``drivers/solver_runner.py``,
which is the walled one (``wall_flow``, ``friction_increment``, a reference
that keeps its border columns).  A configuration that is periodic in x
compares with ``reference/shallow_water.py``, which has neither: so this
driver is that one — ``__init__``, ``setup``, ``run``, ``window``, the spans
``dispatch_leg_run`` / ``wait_leg_run`` and the counters ``runs``, ``calls``,
``run_plan``, ``traced_custom_calls_a_run`` as they are — with the check of
the periodic legs' driver: the six gaps and ``nonfinite`` over the physical
domain, the last run's final state against the reference run for the same
steps.  ``place_state`` / ``initial_fields`` are ``drivers/solver.py``'s
through both (they ask the reference module for ``with_halo_columns``).

The keys of the mix and of the configuration's file are those of
``README_solver_loop.md``, less ``limits.wall_flow``.
"""

import importlib

import jax.numpy as jnp

_loop = importlib.import_module("chipbench.drivers.solver_loop")
_solver = importlib.import_module("chipbench.drivers.solver")


class Driver(_loop.Driver):
    def check(self, precision=jnp.float32):
        if self.traced:
            self.hold_trace_against_plan()
        return _solver.Driver.check(self, precision)
