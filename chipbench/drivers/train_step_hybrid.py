"""Driver for the hybrid training cell: back-to-back optimizer steps of the
program's short-convolution + GQA + MoE decoder
(``mpi4jax_tpu.models.hybrid_moe``), one ``mpx.compile``-pinned call a step
with the training state donated.

It is ``train_step.py``'s driver with the hybrid model in the MLA model's
place: the same set-up (the reference's seeded initial state wrapped in the
program's step-0 state, placed once, the step pinned, ``warm_up_steps``
steps run), the same spans (``dispatch_step`` / ``wait_step``) and a fresh
seeded sequence placed one step ahead, the same counters
(``train_plan``, ``held_loads``, ``dropped``, ``params_here``, and in a
traced line ``traced_kernel_calls_a_step``).  What differs:

- a step reads ``tokens_per_step + 1`` ids (one loss, no MTP module): the
  first ``tokens_per_step + 1`` of ``train_step.sequence``'s ids;
- ``check`` compares one loss (``loss_gap``) where the MLA cell compares
  two, and a parameter whose change the reference makes 0 (a norm scale
  before experts no token chose) compares as 0 where the program's is 0
  too;
- the window keeps one step queued on the chip: the host dispatches step
  ``k + 1`` before it waits for step ``k``, as a training loop that
  fetches its numbers a step behind runs, so a pause of the host shorter
  than a step does not idle the chip.  Waiting on each step before the
  next, the MLA cell's way, this cell's runs held a stray step 20-100 ms
  long about once in 90 and spread past the admission's half-bound
  (PERF.md section 2).

On a tree without ``mpi4jax_tpu.models.hybrid_moe`` it stops in
``setup()``.
"""

import gc
import importlib
import time

import numpy as np

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx
from chipbench.drivers import train_step
from chipbench.drivers.train_step import (DISPATCH_SPAN, ROUTING, WAIT_SPAN,
                                          _change_norms, _rank0)

MODEL = "mpi4jax_tpu.models.hybrid_moe"


def _gap(got: float, want: float) -> float:
    """``|got - want| / want``; 0 where both are 0."""
    if want == 0.0:
        return 0.0 if got == 0.0 else float("inf")
    return abs(got - want) / want


class Driver(train_step.Driver):

    def sequence(self, k: int) -> np.ndarray:
        return super().sequence(k)[:self.tokens + 1]

    def setup(self):
        try:
            self.model = importlib.import_module(MODEL)
        except ImportError as e:
            raise SystemExit(f"chipbench: this tree has no {MODEL} ({e}): "
                             "the cell cannot run on it") from None
        self.dims = self.model.Dims.from_config(self.config)
        mesh = mpx.make_world_mesh((1,), ("ep",), devices=self.devices[:1])
        self.comm = mpx.Comm(("ep",), mesh=mesh)
        self.sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("ep"))
        self.start()

    def start(self):
        """State placed, step pinned, warm-up steps run; again from the
        start where a test has changed ``dims`` or the program."""
        self.state = self.program = None
        gc.collect()
        t0 = time.perf_counter()
        self.state = mpx.shard_global(self.initial_state(), self.comm)
        jax.block_until_ready(self.state)
        placed = time.perf_counter()
        step = self.model.make_train_step(self.dims)
        step.__name__ = "train_step"
        ids = jax.ShapeDtypeStruct((1, self.tokens + 1), jnp.int32,
                                   sharding=self.sharding)
        self.program = mpx.compile(step, self.state, ids, comm=self.comm,
                                   donate_argnums=(0,))
        pinned = time.perf_counter()
        self.outs = []
        warm = int(self.traffic["warm_up_steps"])
        self.next = self.place(self.sequence(0))
        for k in range(warm):
            self.state, out = self.program(self.state, self.next)
            self.next = self.place(self.sequence(k + 1))
            jax.block_until_ready(self.state)
            self.outs.append(_rank0(out))
            if k == 0:
                self.after_first = _rank0((self.state["params"],
                                           self.state["bias"]))
        self.k = warm
        self.stages = {"state_s": placed - t0, "pin_s": pinned - placed,
                       "warm_up_s": time.perf_counter() - pinned}

    def window(self, seconds: float, traced: bool) -> dict:
        """``train_step.Driver.window`` with one step kept queued: step
        ``k + 1`` is dispatched before the host waits for step ``k``,
        whose outputs it then fetches, so the chip waits for the host only
        where the host falls a whole step behind.  A step is dispatched while the window has
        time left (at most ``trace_steps`` in a traced run); every step
        dispatched is waited for and counted.  The wait span holds the
        whole loop, so every operation of the window's steps starts inside
        the steps' spans."""
        cap = int(self.traffic["trace_steps"]) if traced else None
        step_s = []
        first = len(self.outs)
        pending, dispatched = None, 0
        start = done = time.perf_counter()
        with jax.profiler.TraceAnnotation(WAIT_SPAN):
            while True:
                more = done - start < seconds and dispatched != cap
                if more:
                    with jax.profiler.TraceAnnotation(DISPATCH_SPAN):
                        self.state, out = self.program(self.state, self.next)
                        self.next = self.place(self.sequence(self.k + 1))
                    self.k += 1
                    dispatched += 1
                if pending is not None:
                    self.outs.append(_rank0(pending))
                    now = time.perf_counter()
                    step_s.append(now - done)
                    done = now
                pending = out if more else None
                if pending is None:
                    break
            jax.block_until_ready(self.state)
        wall = time.perf_counter() - start
        steps = len(step_s)
        timed = self.outs[first:]
        held = slice(self.dims.held_first,
                     self.dims.held_first + self.dims.held)
        counters = {
            "steps": steps, "window_wall_s": wall,
            "calls": {"train_step": steps},
            "step_wall_s": step_s[:200],
            "tokens_per_step": self.tokens,
            "train_plan": self.model.train_plan(self.dims),
            "params_here": self.model.param_count(self.dims),
            "held_loads": [np.asarray(o["loads"])[:, held].tolist()
                           for o in (timed if traced else timed[-1:])],
            "dropped": max(int(o["dropped"]) for o in self.outs),
            "loss": [[float(x) for x in o["loss"]] for o in self.outs[:200]],
        }
        span_names = [DISPATCH_SPAN, WAIT_SPAN]
        self.traced = (counters, span_names) if traced else None
        return {
            "attempted": steps, "failed": 0,
            "end_to_end": {
                "steps_per_s_per_chip": steps / wall / len(self.devices)},
            "counters": counters, "span_names": span_names}

    def first_step(self, params, bias, ids):
        """What the first step produced, in the reference's terms: the
        program's loss and gradient norms, the norm of each parameter's
        change and the bias's move; or, for the control, the reference's
        own at the control's operands."""
        if self.control is not None:
            return self.ref.first_step(
                dict(self.config, reference_operands=self.control),
                params, bias, ids)
        first = self.outs[0]
        after, moved = self.after_first
        self.after_first = None
        return {"ce": float(first["loss"][0]),
                "grad_norms": dict(zip(self.model.GRAD_GROUPS, (
                    float(x) for x in first["grad_norms"]))),
                "update_norms": {n: float(v) for n, v in jax.device_get(
                    _change_norms(after, params)).items()},
                "bias_move": np.asarray(moved, np.float64)
                - np.asarray(jax.device_get(bias), np.float64)}

    def check(self):
        """The first step against the plain reference's from the same state
        and sequence: the loss, the gradient norms by group, the norm of
        each parameter's change, the selection bias's move; non-finite
        values; ``trains``: the reference's loss of the first sequence at
        the trained parameters against at the initial ones, as a share of
        the latter."""
        if self.traced:
            self.hold_trace_against_plan()
        limits = self.traffic["limits"]
        first_ids = jnp.asarray(self.sequence(0))
        took = {}
        with jax.default_device(self.devices[0]):
            t = time.perf_counter()
            trained = self.ref.first_step(self.config, *self.final,
                                          first_ids)["ce"]
            self.final = None
            took["trained_loss_s"] = time.perf_counter() - t
            params, bias = self.initial_params()
            t = time.perf_counter()
            got = self.first_step(params, bias, first_ids)
            took["first_step_s"] = time.perf_counter() - t
            t = time.perf_counter()
            ref = self.ref.first_step(self.config, params, bias, first_ids)
            took["reference_s"] = time.perf_counter() - t
        del params, bias
        gaps = {g: _gap(v, ref["grad_norms"][g])
                for g, v in got["grad_norms"].items()}
        changes = {n: _gap(v, ref["update_norms"][n])
                   for n, v in got["update_norms"].items()}
        move = np.asarray(ref["bias_move"], np.float64)
        start = ref["ce"]
        values = np.concatenate([np.ravel(np.asarray(o[k], np.float64))
                                 for o in self.outs
                                 for k in ("loss", "grad_norms")])
        nonfinite = int(np.sum(~np.isfinite(values))) + self.final_nonfinite
        self.readings = {
            "reference": {k: ref[k] for k in ("ce", "grad_norms")},
            "first_step": {k: got[k] for k in ("ce", "grad_norms")},
            "grad_norm_gaps": gaps, "update_norm_gaps": changes,
            "losses": [float(o["loss"][0]) for o in self.outs][:200],
            "check_s": took,
            "first_sequence_loss": {"initial": start, "trained": trained}}
        return [
            {"name": "loss_gap", "limit": limits["loss_gap"],
             "value": abs(got["ce"] - start) / start},
            {"name": "grad_norm_gap", "limit": limits["grad_norm_gap"],
             "value": max(v for g, v in gaps.items() if g not in ROUTING)},
            # the router's and the routed experts' gradients follow which
            # experts each token chose: a near-tie of a token's top-4 that
            # rounding flips moves them, so they have a limit of their own
            {"name": "routing_grad_norm_gap",
             "limit": limits["routing_grad_norm_gap"],
             "value": max(gaps[g] for g in ROUTING)},
            # AdamW's first step moves a weight by about the rate whatever
            # the gradient's size; a step left out reads 1
            {"name": "update_norm_gap", "limit": limits["update_norm_gap"],
             "value": max(changes.values())},
            {"name": "bias_move_gap", "limit": limits["bias_move_gap"],
             "value": float(np.linalg.norm(got["bias_move"] - move)
                            / np.linalg.norm(move))},
            {"name": "nonfinite", "limit": limits["nonfinite"],
             "value": nonfinite},
            {"name": "trains", "limit": limits["trains"],
             "value": (trained - start) / start},
        ]
