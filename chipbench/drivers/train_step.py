"""Driver for a training cell: back-to-back optimizer steps of the
program's MLA + MoE + MTP decoder (``mpi4jax_tpu.models.mla_moe``), one
``mpx.compile``-pinned call a step with the training state donated.

Set-up: the seeded initial parameters and selection bias made on the
device by the reference (``init_params``, from the configuration's
standard deviations), wrapped in the program's step-0 state
(``init_state``) and placed once with ``mpx.shard_global``; the step
pinned; ``warm_up_steps`` steps run.  The first of them — the first step
the pinned program ran, from the seeded initial state and the seeded first
sequence — is what ``check`` compares with the plain reference: its losses
and gradient norms, and the parameters and bias it left (copied to the
host).  On a tree without ``mpi4jax_tpu.models.mla_moe`` the driver stops
in ``setup()``.

The window: steps back to back, a fresh seeded sequence each (built on the
host by ``sequence()`` and placed on the device one step ahead), each
dispatched under the host span ``dispatch_step`` and waited for under
``wait_step``; ``steps_per_s_per_chip`` = steps completed / window wall /
chips.  A traced window runs ``trace_steps`` steps.

Counters: ``steps``, ``calls`` (``{"train_step": n}``), ``train_plan``
(the program's own count of the kernel calls a step makes, by name),
``held_loads`` (each traced step's tokens a held expert took, by MoE
layer), ``dropped`` (held assignments not placed, the most of any step),
``params_here``; in a traced line ``traced_kernel_calls_a_step`` (the
trace's custom calls inside the steps' spans, a step, by name).
"""

import gc
import glob
import importlib
import math
import os
import re
import time
from collections import Counter

import numpy as np

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx
from chipbench import harness, trace_reduce

DISPATCH_SPAN, WAIT_SPAN = "dispatch_step", "wait_step"
ROUTING = ("router", "routed")  # the groups whose gradients follow the top-k
_NUMBERING = re.compile(r"(\.\d+)+$")


def instruction(name: str) -> str:
    """A trace event's HLO instruction name without XLA's numbering: a
    Pallas kernel's ``name=``."""
    return _NUMBERING.sub("", name.partition(" = ")[0].lstrip("%"))


def kernel_calls_a_step(trace: dict, steps: int):
    """``{instruction: custom-call events a step and device}`` inside the
    steps' spans, or ``None`` where the trace has no device."""
    if not trace["devices"] or not steps:
        return None
    counts = Counter(
        instruction(name) for _dev, name, _ns in trace_reduce.events_within(
            trace, trace_reduce.call_spans(trace, "step"))
        if trace_reduce.op_kind(name) == "custom-call")
    per = len(trace["devices"]) * steps
    return {name: n / per for name, n in sorted(counts.items())}


def sequence(traffic: dict, vocab: int, seed: int, k: int) -> np.ndarray:
    """Step ``k``'s packed sequence of ``tokens_per_step + 2`` ids:
    documents of log-normal length (median ``doc_median``, sigma
    ``doc_sigma``, at most the sequence), each followed by ``eos_id``; ids
    Zipf(``zipf_s``) by rank over the slice's other ids."""
    rng = np.random.default_rng([int(seed), k])
    n = int(traffic["tokens_per_step"]) + 2
    eos = int(traffic["eos_id"])
    weights = np.arange(1, vocab, dtype=np.float64) ** -float(
        traffic["zipf_s"])
    cdf = np.cumsum(weights)
    ids = np.searchsorted(cdf / cdf[-1], rng.random(n)).astype(np.int32)
    ids += ids >= eos  # the eos id is not drawn
    at = 0
    while True:
        at += int(min(n, max(1, round(float(traffic["doc_median"]) * math.exp(
            float(traffic["doc_sigma"]) * rng.standard_normal())))))
        if at >= n:
            return ids
        ids[at] = eos
        at += 1


def _rank0(out):
    """A step's outputs on the host, without the rank axis of 1."""
    return jax.tree.map(lambda a: np.asarray(a)[0], jax.device_get(out))


@jax.jit
def _nonfinite(tree):
    return sum(jnp.sum(~jnp.isfinite(a)) for a in jax.tree.leaves(tree))


class Driver:
    def __init__(self, config, traffic, seed, devices, peaks):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.ref = importlib.import_module(
            "chipbench.reference." + config["reference"])
        self.tokens = int(traffic["tokens_per_step"])
        self.state = self.program = None
        self.traced = None
        self.readings = None
        # the float8 control sets this: the reference at those operands
        # then stands in the program's place in ``check``
        self.control = None

    # -- set-up -------------------------------------------------------------

    def compile_count(self) -> int:
        return 0

    def sequence(self, k: int) -> np.ndarray:
        return sequence(self.traffic, self.config["vocab_size"], self.seed, k)

    def place(self, ids: np.ndarray):
        return jax.device_put(ids[None], self.sharding)

    def setup(self):
        try:
            self.model = importlib.import_module("mpi4jax_tpu.models.mla_moe")
        except ImportError as e:
            raise SystemExit("chipbench: this tree has no "
                             f"mpi4jax_tpu.models.mla_moe ({e}): the cell "
                             "cannot run on it") from None
        self.dims = self.model.Dims.from_config(self.config)
        mesh = mpx.make_world_mesh((1,), ("ep",), devices=self.devices[:1])
        self.comm = mpx.Comm(("ep",), mesh=mesh)
        self.sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("ep"))
        self.start()

    def initial_state(self):
        """The reference's seeded initial parameters and bias in the
        program's step-0 state, made on the device in the layout the pinned
        step takes (a leading rank axis of 1)."""
        return jax.jit(lambda key: jax.tree.map(
            lambda a: a[None], self.model.init_state(
                self.dims, *self.ref.init_params(self.config, key))),
            out_shardings=self.sharding)(self.ref.seed_key(self.seed))

    def initial_params(self):
        """The same parameters and selection bias, per-rank shapes, for the
        reference."""
        with jax.default_device(self.devices[0]):
            return jax.jit(lambda key: self.ref.init_params(self.config, key))(
                self.ref.seed_key(self.seed))

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
        ids = jax.ShapeDtypeStruct((1, self.tokens + 2), jnp.int32,
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

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float, traced: bool) -> dict:
        cap = int(self.traffic["trace_steps"]) if traced else None
        step_s = []
        first = len(self.outs)
        start = time.perf_counter()
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(DISPATCH_SPAN):
                self.state, out = self.program(self.state, self.next)
                self.next = self.place(self.sequence(self.k + 1))
            with jax.profiler.TraceAnnotation(WAIT_SPAN):
                jax.block_until_ready(self.state)
                self.outs.append(_rank0(out))
            self.k += 1
            now = time.perf_counter()
            step_s.append(now - t)
            if now - start >= seconds or len(step_s) == cap:
                break
        wall = now - start
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

    # -- after the window ---------------------------------------------------

    def release(self):
        """Everything but the trained parameters and selection bias, which
        ``check`` hands to the reference."""
        self.final_nonfinite = int(_nonfinite(self.state["params"]))
        self.final = jax.tree.map(
            lambda a: a[0], (self.state["params"], self.state["bias"]))
        self.state = self.program = self.next = None
        gc.collect()

    def hold_trace_against_plan(self):
        counters, span_names = self.traced
        files = glob.glob(os.path.join(harness.ROOT, harness.TRACE_DIR, "**",
                                       "*.xplane.pb"), recursive=True)
        if not files:
            return
        trace = trace_reduce.reduce_events(trace_reduce.read_xplane(
            max(files, key=os.path.getmtime), span_names))
        calls = kernel_calls_a_step(trace, counters["steps"])
        if calls is not None:
            counters["traced_kernel_calls_a_step"] = calls

    def first_step(self, params, bias, ids):
        """What the first step produced, in the reference's terms: the
        program's losses and gradient norms, the norm of each parameter's
        change and the bias's move; or, for the control, the reference's
        own at the control's operands."""
        if self.control is not None:
            return self.ref.first_step(
                dict(self.config, reference_operands=self.control),
                params, bias, ids)
        first = self.outs[0]
        after, moved = self.after_first
        self.after_first = None
        main, mtp = (float(x) for x in first["loss"])
        return {"ce_main": main, "ce_mtp": mtp,
                "grad_norms": dict(zip(self.model.GRAD_GROUPS, (
                    float(x) for x in first["grad_norms"]))),
                "update_norms": {n: float(v) for n, v in jax.device_get(
                    _change_norms(after, params)).items()},
                "bias_move": _stacked(moved) - _stacked(jax.device_get(bias))}

    def check(self):
        """The first step against the plain reference's from the same state
        and sequence: both losses, the gradient norms by group, the norm of
        each parameter's change, the selection bias's move; non-finite
        values; ``trains``: the reference's loss of the first sequence at
        the trained parameters against at the initial ones, as a share of
        the latter (the same sequence, so no sequence-to-sequence noise)."""
        if self.traced:
            self.hold_trace_against_plan()
        limits = self.traffic["limits"]
        first_ids = jnp.asarray(self.sequence(0))
        took = {}
        weight = self.dims.mtp_weight
        with jax.default_device(self.devices[0]):
            t = time.perf_counter()
            at_end = self.ref.first_step(self.config, *self.final, first_ids)
            self.final = None
            trained = at_end["ce_main"] + weight * at_end["ce_mtp"]
            took["trained_loss_s"] = time.perf_counter() - t
            params, bias = self.initial_params()
            t = time.perf_counter()
            got = self.first_step(params, bias, first_ids)
            took["first_step_s"] = time.perf_counter() - t
            t = time.perf_counter()
            ref = self.ref.first_step(self.config, params, bias, first_ids)
            took["reference_s"] = time.perf_counter() - t
        del params, bias
        gaps = {g: abs(v - ref["grad_norms"][g]) / ref["grad_norms"][g]
                for g, v in got["grad_norms"].items()}
        changes = {n: abs(v - ref["update_norms"][n]) / ref["update_norms"][n]
                   for n, v in got["update_norms"].items()}
        move = np.asarray(ref["bias_move"], np.float64)
        totals = [float(o["loss"][0]) + weight * float(o["loss"][1])
                  for o in self.outs]
        start = ref["ce_main"] + weight * ref["ce_mtp"]
        values = np.concatenate([np.ravel(np.asarray(o[k], np.float64))
                                 for o in self.outs
                                 for k in ("loss", "grad_norms")])
        nonfinite = int(np.sum(~np.isfinite(values))) + self.final_nonfinite
        self.readings = {
            "reference": {k: ref[k] for k in ("ce_main", "ce_mtp",
                                              "grad_norms")},
            "first_step": {k: got[k] for k in ("ce_main", "ce_mtp",
                                               "grad_norms")},
            "grad_norm_gaps": gaps, "update_norm_gaps": changes,
            "losses": totals[:200], "check_s": took,
            "first_sequence_loss": {"initial": start, "trained": trained}}
        return [
            {"name": "main_loss_gap", "limit": limits["main_loss_gap"],
             "value": abs(got["ce_main"] - ref["ce_main"]) / ref["ce_main"]},
            {"name": "mtp_loss_gap", "limit": limits["mtp_loss_gap"],
             "value": abs(got["ce_mtp"] - ref["ce_mtp"]) / ref["ce_mtp"]},
            {"name": "grad_norm_gap", "limit": limits["grad_norm_gap"],
             "value": max(v for g, v in gaps.items() if g not in ROUTING)},
            # the router's and the routed experts' gradients follow which
            # experts each token chose: a near-tie of a token's top-8 that
            # rounding flips moves them by up to a few per cent, so they
            # have a limit of their own
            {"name": "routing_grad_norm_gap",
             "limit": limits["routing_grad_norm_gap"],
             "value": max(gaps[g] for g in ROUTING)},
            # AdamW's first step moves a weight by about the rate whatever
            # the gradient's size, so rounding hardly moves these norms; a
            # step left out reads 1
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


def _stacked(bias) -> np.ndarray:
    """A selection bias as one (MoE layers + 1, experts) array."""
    return np.concatenate([np.asarray(bias["moe"], np.float64),
                           np.asarray(bias["mtp"], np.float64)[None]])


@jax.jit
def _change_norms(after, before):
    """``{leaf: norm of after - before}``, the leaves named by key path."""
    return {jax.tree_util.keystr(path): jnp.linalg.norm(a - b)
            for (path, a), b in zip(jax.tree.flatten_with_path(after)[0],
                                    jax.tree.leaves(before))}
