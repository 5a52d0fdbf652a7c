"""Driver for the collective sweep: rounds over pinned programs of chained
collectives on the world communicator.

Every program is one ``mpx.spmd`` region holding a ``lax.fori_loop`` whose
body is one collective through the library's public op (``mpx.allreduce``,
``mpx.reduce_scatter``, ``mpx.allgather``, ``mpx.alltoall``,
``mpx.sendrecv(..., dest=mpx.shift(1))``) and one elementwise rescale, as
``benchmarks/micro.py``'s ``bench_allreduce`` and ``bench_sendrecv_ring``
chain them; it is pinned with ``mpx.compile`` and called from an input that
stays on the devices from set-up.  No eager call and no per-collective
host dispatch anywhere: the host dispatches once per program call.

What the links compute is stated in ``chipbench/reference/collectives.py``,
which follows sampled positions through the same chain in NumPy.
"""

import importlib
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

import mpi4jax_tpu as mpx

from chipbench import work

_K_BLOCKS = ("reduce_scatter", "alltoall")


def _link(op: str, k: int, growth: float, coef):
    """The loop body of one program: one collective, one rescale."""
    if op == "allreduce":
        def body(_, v):
            s, _tok = mpx.allreduce(v, op=mpx.SUM)
            return mpx.varying(s * (growth / k))
    elif op == "reduce_scatter":
        def body(_, x):
            r, _tok = mpx.reduce_scatter(x, op=mpx.SUM)
            return mpx.varying(r[None, :] * coef[:, None])
    elif op == "allgather":
        def body(_, v):
            g, _tok = mpx.allgather(v)
            return mpx.varying(jnp.sum(g * coef[:, None], axis=0))
    elif op == "alltoall":
        def body(_, x):
            y, _tok = mpx.alltoall(x)
            return mpx.varying(y * growth)
    elif op == "sendrecv":
        def body(_, v):
            r, _tok = mpx.sendrecv(v, v, dest=mpx.shift(1))
            return r
    else:
        raise ValueError(f"no program for op {op!r}")
    return body


class Driver:
    def __init__(self, config, traffic, seed, devices, peaks):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.k = len(devices)
        if self.k != config["ranks"]:
            raise SystemExit(f"configuration wants {config['ranks']} ranks, "
                             f"got {self.k} devices")
        self.ref = importlib.import_module(
            "chipbench.reference." + config["reference"])
        self.specs = traffic["programs"]
        self.programs = {}

    # -- set-up -------------------------------------------------------------

    def _local_shape(self, spec):
        n = int(spec["block_elems"])
        return (self.k, n) if spec["op"] in _K_BLOCKS else (n,)

    def setup(self):
        k = self.k
        mesh = mpx.make_world_mesh(devices=self.devices)
        self.comm = comm = mpx.Comm(mesh.axis_names, mesh=mesh)
        growth = float(self.ref.GROWTH)
        coef = jnp.asarray(self.ref.coefficients(k), jnp.float32)
        self.makers = {}
        self.stages = {"inputs_s": 0.0, "pin_s": 0.0, "warm_up_s": 0.0}
        inputs = self.make_inputs()
        for spec in self.specs:
            t0 = time.perf_counter()
            x = jax.block_until_ready(next(inputs))
            t1 = time.perf_counter()
            body = _link(spec["op"], k, growth, coef)

            @partial(mpx.spmd, comm=comm)
            def chained(x, body=body, n=int(spec["chain"])):
                return jax.lax.fori_loop(0, n, body, x)

            chained.__name__ = spec["name"]
            program = mpx.compile(chained, x)
            t2 = time.perf_counter()
            jax.block_until_ready(program(x))  # warm-up of this shape
            self.stages["inputs_s"] += t1 - t0
            self.stages["pin_s"] += t2 - t1
            self.stages["warm_up_s"] += time.perf_counter() - t2
            self.programs[spec["name"]] = {"spec": spec, "x": x,
                                           "call": program, "last": None}

    def make_inputs(self):
        """One input per program, float32 uniform in [0.5, 1.5), made on
        the devices: a threefry key per program and rank drawn from the
        seed enters as data, so every seed runs the same compiled maker."""
        keys = np.random.default_rng(self.seed).integers(
            0, 2 ** 32, size=(len(self.specs), self.k, 2), dtype=np.uint32)
        for i, spec in enumerate(self.specs):
            shape = self._local_shape(spec)
            if shape not in self.makers:
                @partial(mpx.spmd, comm=self.comm)
                def make(key, shape=shape):
                    key = jax.random.wrap_key_data(key, impl="threefry2x32")
                    return jax.random.uniform(key, shape, jnp.float32,
                                              0.5, 1.5)
                self.makers[shape] = make
            yield self.makers[shape](mpx.shard_global(keys[i], self.comm))

    def compile_count(self) -> int:
        aot = mpx.cache_stats()["aot"]
        return aot["pins"] + aot["compiles"]

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float, traced: bool) -> dict:
        rounds_cap = int(self.traffic["trace_rounds"]) if traced else None
        sets = {}
        calls = {name: 0 for name in self.programs}
        rounds = 0
        start = time.perf_counter()
        while True:
            for name, p in self.programs.items():
                p["last"] = None
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("dispatch_" + name):
                    out = p["call"](p["x"])
                with jax.profiler.TraceAnnotation("wait_" + name):
                    jax.block_until_ready(out)
                wall = time.perf_counter() - t
                p["last"] = out
                spec = p["spec"]
                acc = sets.setdefault(spec["set"], {
                    "wall_s": 0.0, "bus_bytes": 0.0, "collectives": 0,
                    "calls": 0})
                acc["wall_s"] += wall
                acc["bus_bytes"] += spec["chain"] * work.bus_bytes(
                    spec["op"], spec["block_elems"], self.k)
                acc["collectives"] += spec["chain"]
                acc["calls"] += 1
                calls[name] += 1
            rounds += 1
            if (time.perf_counter() - start >= seconds
                    or rounds == rounds_cap):
                break
        wall = time.perf_counter() - start
        end_to_end = {}
        if "large" in sets:
            end_to_end["large_busbw_GBps"] = (
                sets["large"]["bus_bytes"] / sets["large"]["wall_s"] / 1e9)
        if "small" in sets:
            end_to_end["small_lat_us"] = (
                sets["small"]["wall_s"] / sets["small"]["collectives"] * 1e6)
        return {
            "attempted": sum(calls.values()), "failed": 0,
            "end_to_end": end_to_end,
            "counters": {"rounds": rounds, "window_wall_s": wall,
                         "sets": sets, "calls": calls},
            "span_names": [pre + name for name in self.programs
                           for pre in ("dispatch_", "wait_")],
        }

    # -- after the window ---------------------------------------------------

    def release(self):
        for p in self.programs.values():
            p["call"] = None

    def sample_positions(self, spec) -> np.ndarray:
        n = int(spec["block_elems"])
        rng = np.random.default_rng([self.seed, len(spec["name"]), n])
        picks = rng.integers(0, n, min(int(self.traffic["sample"]), n))
        return np.unique(np.concatenate([picks, [0, n - 1]]))

    def check(self, dtype=np.float32):
        """Each program's last output against the NumPy reference at the
        sampled positions: the widest gap as a share of the reference's
        largest value."""
        take = jax.jit(lambda a, idx: jnp.take(a, idx, axis=-1))
        checks = []
        for name, p in self.programs.items():
            spec = p["spec"]
            idx = jnp.asarray(self.sample_positions(spec))
            x = np.asarray(take(p["x"], idx))
            got = np.asarray(take(p["last"], idx))
            p["x"] = p["last"] = None
            want = self.ref.run_chain(spec["op"], x, int(spec["chain"]),
                                      dtype)
            gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
            if not np.all(np.isfinite(got)):
                gap = float("nan")
            checks.append({"name": name, "value": float(gap),
                           "limit": spec["limit"]})
        return checks
