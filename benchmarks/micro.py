"""Microbenchmarks: allreduce bandwidth + point-to-point latency.

The reference publishes no microbenchmarks (BASELINE.md: `published: {}`);
these fill that gap with the two north-star metrics from BASELINE.json:

- **allreduce bus bandwidth** (GB/s per device) over a size sweep — on a
  TPU slice this measures ICI; algorithmic bytes per device for a ring
  allreduce are ``2 * (n-1)/n * size`` (the standard bus-bandwidth
  convention, so numbers are comparable across device counts);
- **sendrecv ring latency** (µs per hop) — the halo-exchange primitive.

plus the butterfly-vs-ring allreduce sweep that measures the payload-aware
algorithm layer's crossover (``MPI4JAX_TPU_COLLECTIVE_ALGO``,
ops/_algos.py; the measured table lives in docs/microbenchmarks.md).

Usage:  python benchmarks/micro.py [--json] [--save]

Timing protocol: each measurement chains ``iters`` collectives inside one
jitted program (so dispatch overhead amortizes), closes the timed region
with ``jax.block_until_ready`` on the program's outputs, and reports the
best of 3 trials.  Operands are committed to the mesh once
(``mpx.shard_global``), so no timed call re-shards them off one device.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mpi4jax_tpu as mpx  # noqa: E402


def _ones(comm, *shape):
    """A global f32 operand of ones, committed to ``comm``'s mesh."""
    return mpx.shard_global(np.ones(shape, np.float32), comm)


def _time_program(fn, args, trials=3):
    """Best-of-N wall time of ``fn(*args)``, device work included."""
    jax.block_until_ready(fn(*args))  # compile + drain queue
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_allreduce(comm, sizes_mb, iters=20):
    n = comm.Get_size()
    rows = []
    for mb in sizes_mb:
        nelem = max(1, int(mb * 1e6 / 4))

        @mpx.spmd(comm=comm)
        def prog(x):
            def body(_, v):
                s, _tok = mpx.allreduce(v, op=mpx.SUM)
                return mpx.varying(s * (1.0 / n))  # keep values bounded

            return jax.lax.fori_loop(0, iters, body, x)

        x = _ones(comm, n, nelem)
        t = _time_program(prog, (x,)) / iters
        # ring-allreduce bus bandwidth per device
        bus_bytes = 2 * (n - 1) / n * nelem * 4
        rows.append({
            "size_mb": round(nelem * 4 / 1e6, 3),
            "time_us": round(t * 1e6, 1),
            "bus_gb_s": round(bus_bytes / t / 1e9, 2) if n > 1 else None,
        })
    return rows


def bench_sendrecv_ring(comm, sizes_kb, iters=50):
    n = comm.Get_size()
    rows = []
    for kb in sizes_kb:
        nelem = max(1, int(kb * 1e3 / 4))

        @mpx.spmd(comm=comm)
        def prog(x):
            def body(_, v):
                r, _tok = mpx.sendrecv(v, v, dest=mpx.shift(1))
                return r

            return jax.lax.fori_loop(0, iters, body, x)

        x = _ones(comm, n, nelem)
        t = _time_program(prog, (x,)) / iters
        rows.append({
            "size_kb": round(nelem * 4 / 1e3, 2),
            "hop_us": round(t * 1e6, 2),
            "link_gb_s": round(nelem * 4 / t / 1e9, 2) if n > 1 else None,
        })
    return rows


def bench_prod_and_split(comm, sizes_mb, iters=20):
    """The log-depth butterfly family: PROD allreduce (no native HLO
    collective) on the whole comm and on an even/odd color split — the
    lowerings tests/test_scale.py gates at 64 devices, timed here."""
    n = comm.Get_size()
    split = comm.Split([r % 2 for r in range(n)]) if n > 1 else None
    rows = []
    for mb in sizes_mb:
        nelem = max(1, int(mb * 1e6 / 4))

        @mpx.spmd(comm=comm)
        def prog(x):
            def body(_, v):
                s, _tok = mpx.allreduce(v, op=mpx.PROD)
                return mpx.varying(jnp.clip(s, 0.5, 2.0))  # keep bounded

            return jax.lax.fori_loop(0, iters, body, x)

        x = _ones(comm, n, nelem)
        t_whole = _time_program(prog, (x,)) / iters

        t_split = None
        if split is not None:

            @mpx.spmd(comm=comm)
            def prog_split(x):
                def body(_, v):
                    s, _tok = mpx.allreduce(v, op=mpx.PROD, comm=split)
                    return mpx.varying(jnp.clip(s, 0.5, 2.0))

                return jax.lax.fori_loop(0, iters, body, x)

            t_split = _time_program(prog_split, (x,)) / iters
        rows.append({
            "size_mb": round(nelem * 4 / 1e6, 3),
            "prod_us": round(t_whole * 1e6, 1),
            "prod_split_us": (
                round(t_split * 1e6, 1) if t_split is not None else None
            ),
        })
    return rows


def bench_allreduce_algos(comm, sizes_mb, iters=20):
    """Forced butterfly vs forced ring for the SAME PROD allreduce over a
    size sweep — the measured crossover table of docs/microbenchmarks.md.
    PROD has no native HLO collective, so the two forced settings time the
    CollectivePermute algorithm layer itself (``MPI4JAX_TPU_COLLECTIVE_ALGO``
    is folded into the program cache keys, so each setting retraces)."""
    n = comm.Get_size()
    rows = []
    saved = os.environ.get("MPI4JAX_TPU_COLLECTIVE_ALGO")
    try:
        for mb in sizes_mb:
            nelem = max(1, int(mb * 1e6 / 4))
            row = {"size_mb": round(nelem * 4 / 1e6, 3)}
            for algo in ("butterfly", "ring"):
                os.environ["MPI4JAX_TPU_COLLECTIVE_ALGO"] = algo

                @mpx.spmd(comm=comm)
                def prog(x):
                    def body(_, v):
                        s, _tok = mpx.allreduce(v, op=mpx.PROD)
                        return mpx.varying(jnp.clip(s, 0.5, 2.0))

                    return jax.lax.fori_loop(0, iters, body, x)

                x = _ones(comm, n, nelem)
                t = _time_program(prog, (x,)) / iters
                row[f"{algo}_us"] = round(t * 1e6, 1)
            # on 1 device both settings lower to the identity — no crossover
            row["ring_speedup"] = (
                round(row["butterfly_us"] / row["ring_us"], 2) if n > 1
                else None
            )
            rows.append(row)
    finally:
        # restore (not just drop) the user's global algorithm setting
        if saved is None:
            os.environ.pop("MPI4JAX_TPU_COLLECTIVE_ALGO", None)
        else:
            os.environ["MPI4JAX_TPU_COLLECTIVE_ALGO"] = saved
    return rows


def bench_hierarchy(comm, sizes_mb=(1, 4), topologies=("2x4", "4x2"),
                    iters=10):
    """The hierarchy sweep (``--hierarchy-sweep``): flat ring vs the
    forced two-level lowering for the SAME PROD allreduce over a payload
    x topology grid (docs/topology.md).  Each topology is faked via
    ``MPI4JAX_TPU_TOPOLOGY`` (the same knob the CI topology lane uses on
    the 8-device CPU mesh); the spec is stamped into every row so saved
    captures say which host partition produced which number.  Both knobs
    fold into the program cache keys, so every cell compiles its own
    program."""
    from mpi4jax_tpu.utils.config import parse_topology_spec

    n = comm.Get_size()
    rows = []
    saved_algo = os.environ.get("MPI4JAX_TPU_COLLECTIVE_ALGO")
    saved_topo = os.environ.get("MPI4JAX_TPU_TOPOLOGY")
    try:
        for topo in topologies:
            counts = parse_topology_spec(topo)
            if sum(counts) != n:
                print(f"hierarchy sweep: skipping topology {topo} "
                      f"(covers {sum(counts)} ranks, mesh has {n})",
                      file=sys.stderr)
                continue
            os.environ["MPI4JAX_TPU_TOPOLOGY"] = topo
            for mb in sizes_mb:
                nelem = max(1, int(mb * 1e6 / 4))
                row = {"size_mb": round(nelem * 4 / 1e6, 3),
                       "topology": topo}
                for label, algo in (("flat", "ring"), ("hier", "hier")):
                    os.environ["MPI4JAX_TPU_COLLECTIVE_ALGO"] = algo

                    @mpx.spmd(comm=comm)
                    def prog(x):
                        def body(_, v):
                            s, _tok = mpx.allreduce(v, op=mpx.PROD)
                            return mpx.varying(jnp.clip(s, 0.5, 2.0))

                        return jax.lax.fori_loop(0, iters, body, x)

                    x = _ones(comm, n, nelem)
                    t = _time_program(prog, (x,)) / iters
                    row[f"{label}_us"] = round(t * 1e6, 1)
                row["hier_speedup"] = (
                    round(row["flat_us"] / row["hier_us"], 2) if n > 1
                    else None
                )
                rows.append(row)
    finally:
        for key, val in (("MPI4JAX_TPU_COLLECTIVE_ALGO", saved_algo),
                         ("MPI4JAX_TPU_TOPOLOGY", saved_topo)):
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
    return rows


def bench_alltoall(comm, sizes_mb=(0.25, 1), topologies=(None,), iters=10,
                   compute_dim=64):
    """The alltoall sweep (``--alltoall-sweep``): flat single-exchange
    vs the forced two-level hierarchical lowering vs the chunked async
    start/wait split (with synthetic compute in the gap), over a
    payload x topology grid (docs/moe.md) — the MoE dispatch/combine
    primitive's three execution shapes.

    A ``None`` topology entry measures under the ambient (derived)
    topology; spec strings are faked via ``MPI4JAX_TPU_TOPOLOGY`` like
    the hierarchy sweep.  Each row also carries the MODELED per-rank
    DCN byte and message columns from the pinned byte models
    (``ops/_hierarchy``): the hierarchical exchange ships the same
    bytes in ``1/r`` the DCN messages (``dcn_msg_reduction``), which is
    the latency/message-rate lever the crossover measures."""
    from mpi4jax_tpu.ops import _hierarchy
    from mpi4jax_tpu.utils.config import parse_topology_spec

    n = comm.Get_size()
    rows = []
    saved = {k: os.environ.get(k) for k in
             ("MPI4JAX_TPU_COLLECTIVE_ALGO",
              "MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES",
              "MPI4JAX_TPU_TOPOLOGY")}
    try:
        for topo in topologies:
            counts = parse_topology_spec(topo) if topo else None
            if counts is not None and sum(counts) != n:
                print(f"alltoall sweep: skipping topology {topo} "
                      f"(covers {sum(counts)} ranks, mesh has {n})",
                      file=sys.stderr)
                continue
            if topo:
                os.environ["MPI4JAX_TPU_TOPOLOGY"] = topo
            else:
                os.environ.pop("MPI4JAX_TPU_TOPOLOGY", None)
            for mb in sizes_mb:
                per = max(1, int(mb * 1e6 / 4 / n))
                nbytes = n * per * 4
                row = {"size_mb": round(nbytes / 1e6, 4),
                       "topology": topo or "derived"}

                def timed(env, fn):
                    for k, v in env.items():
                        os.environ[k] = str(v)
                    try:
                        x = _ones(comm, n, n, per)
                        w = jnp.full((n, compute_dim, compute_dim), 0.01,
                                     jnp.float32)
                        return _time_program(fn(), (x, w)) / iters
                    finally:
                        for k in env:
                            os.environ.pop(k, None)

                def sync_prog():
                    @mpx.spmd(comm=comm)
                    def prog(x, w):
                        def body(_, carry):
                            v, m = carry
                            r, _tok = mpx.alltoall(v)
                            m = jnp.tanh(m @ m)
                            return (mpx.varying(r), m)

                        return jax.lax.fori_loop(0, iters, body, (x, w))

                    return prog

                def async_prog():
                    @mpx.spmd(comm=comm)
                    def prog(x, w):
                        def body(_, carry):
                            v, m = carry
                            h, _tok = mpx.alltoall_start(v)
                            m = jnp.tanh(m @ m)  # overlaps the exchange
                            r, _tok = mpx.alltoall_wait(h)
                            return (mpx.varying(r), m)

                        return jax.lax.fori_loop(0, iters, body, (x, w))

                    return prog

                huge = 1 << 60  # flat: the crossover can never trip
                row["flat_us"] = round(timed(
                    {"MPI4JAX_TPU_COLLECTIVE_ALGO": "auto",
                     "MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES": huge},
                    sync_prog) * 1e6, 1)
                row["hier_us"] = round(timed(
                    {"MPI4JAX_TPU_COLLECTIVE_ALGO": "hier"},
                    sync_prog) * 1e6, 1)
                row["async_us"] = round(timed(
                    {"MPI4JAX_TPU_COLLECTIVE_ALGO": "auto",
                     "MPI4JAX_TPU_ALLTOALL_CROSSOVER_BYTES": huge},
                    async_prog) * 1e6, 1)
                row["hier_speedup"] = (
                    round(row["flat_us"] / row["hier_us"], 2)
                    if n > 1 and row["hier_us"] else None
                )
                if counts is not None and len(set(counts)) == 1:
                    h, r = len(counts), counts[0]
                    row["dcn_bytes_flat"] = _hierarchy.flat_link_bytes(
                        "alltoall", "native", nbytes, n, h)[1]
                    row["dcn_bytes_hier"] = _hierarchy.hier_link_bytes(
                        "alltoall", nbytes, h, r)[1]
                    mf, mh = _hierarchy.alltoall_dcn_messages(h, r)
                    row["dcn_msgs_flat"] = mf
                    row["dcn_msgs_hier"] = mh
                    row["dcn_msg_reduction"] = r
                rows.append(row)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return rows


def bench_fusion(comm, counts=(8, 32), size_kb=64, iters=1):
    """The collective-fusion sweep (``--fusion-sweep``): N small allreduces
    per program, fused (``MPI4JAX_TPU_FUSION=auto``, issue-then-consume
    idiom) vs unfused, reporting per-op wall µs — per-call dispatch plus
    per-collective latency, the two costs bucketing removes
    (docs/overlap.md).  The fusion mode is folded into the program cache
    keys, so each setting compiles its own program."""
    n = comm.Get_size()
    nelem = max(1, int(size_kb * 1e3 / 4))
    rows = []
    saved = os.environ.get("MPI4JAX_TPU_FUSION")
    try:
        for count in counts:
            row = {"count": count, "size_kb": round(nelem * 4 / 1e3, 2)}
            for label, mode in (("unfused", "off"), ("fused", "auto")):
                os.environ["MPI4JAX_TPU_FUSION"] = mode

                @mpx.spmd(comm=comm)
                def prog(xs):
                    # the fusion idiom: issue the whole batch, then
                    # consume — under auto the first use flushes one
                    # fused flat-buffer collective per dtype bucket
                    red = [mpx.allreduce(x, op=mpx.SUM)[0] for x in xs]
                    return [mpx.varying(r * (1.0 / n)) for r in red]

                xs = tuple(
                    jnp.full((n, nelem), float(i % 5 + 1), jnp.float32)
                    for i in range(count)
                )
                t = _time_program(prog, (xs,))
                row[f"{label}_us_per_op"] = round(t / count * 1e6, 2)
            row["fused_speedup"] = round(
                row["unfused_us_per_op"] / row["fused_us_per_op"], 2
            )
            rows.append(row)
    finally:
        if saved is None:
            os.environ.pop("MPI4JAX_TPU_FUSION", None)
        else:
            os.environ["MPI4JAX_TPU_FUSION"] = saved
    return rows


def bench_overlap(comm, sizes_mb=(1, 4), iters=10, compute_dim=128):
    """The async-overlap sweep (``--overlap-sweep``): chunked
    ``allreduce_start``/``_wait`` with independent synthetic compute
    issued in the gap, vs the monolithic allreduce followed by the same
    compute.  Measures how much of the collective the scheduler hides
    behind the matmul chain (``MPI4JAX_TPU_OVERLAP_CHUNKS`` chunks;
    docs/overlap.md)."""
    n = comm.Get_size()
    rows = []
    for mb in sizes_mb:
        nelem = max(1, int(mb * 1e6 / 4))

        @mpx.spmd(comm=comm)
        def mono(x, w):
            def body(_, carry):
                v, m = carry
                s, _tok = mpx.allreduce(v, op=mpx.SUM)
                m = jnp.tanh(m @ m)
                return (mpx.varying(s * (1.0 / n)), m)

            return jax.lax.fori_loop(0, iters, body, (x, w))

        @mpx.spmd(comm=comm)
        def ovl(x, w):
            def body(_, carry):
                v, m = carry
                h, _tok = mpx.allreduce_start(v, op=mpx.SUM)
                m = jnp.tanh(m @ m)  # independent: overlaps the phases
                s, _tok = mpx.allreduce_wait(h)
                return (mpx.varying(s * (1.0 / n)), m)

            return jax.lax.fori_loop(0, iters, body, (x, w))

        x = _ones(comm, n, nelem)
        w = jnp.full((n, compute_dim, compute_dim), 0.01, jnp.float32)
        from mpi4jax_tpu.utils.config import overlap_chunks

        t_mono = _time_program(mono, (x, w)) / iters
        t_ovl = _time_program(ovl, (x, w)) / iters
        rows.append({
            "size_mb": round(nelem * 4 / 1e6, 3),
            "chunks": overlap_chunks(),
            "monolithic_us": round(t_mono * 1e6, 1),
            "overlap_us": round(t_ovl * 1e6, 1),
            "overlap_speedup": round(t_mono / t_ovl, 2),
        })
    return rows


def bench_compression(comm, sizes_mb=(0.25, 1, 4), topology="2x4",
                      iters=3):
    """The wire-codec sweep (``--compression-sweep``): one row per
    {off, bf16, fp8} x payload cell, carrying

    - the LOGICAL vs WIRE DCN bytes of the hierarchical allreduce
      (the pinned PR-6 byte model x the codec byte math — exactly what
      the telemetry logical/wire split records);
    - the MODELED DCN-leg time through the alpha-beta cost model with
      the codec priced in (``collective_cost(codec=...)``);
    - the MEASURED round-trip max relative error of the codec on
      synthetic gradient-scale data — the autotuner's
      codec-vs-error-budget input (docs/compression.md).

    The timing columns are modeled, not wall-clock: a single-host CI
    mesh has no DCN, and the codec's win is a byte-count fact the cost
    model prices — the convergence harness (BENCH_compress.json)
    carries the measured accuracy half."""
    from mpi4jax_tpu.analysis import costmodel
    from mpi4jax_tpu.compress import roundtrip, wire_bytes
    from mpi4jax_tpu.ops import _hierarchy
    from mpi4jax_tpu.utils.config import parse_topology_spec

    counts = parse_topology_spec(topology)
    h, r = len(counts), counts[0]
    k = h * r
    model = costmodel.load_model()
    rows = []
    for mb in sizes_mb:
        n_elems = max(1, int(mb * 1e6 / 4))
        nbytes = n_elems * 4
        logical = _hierarchy.hier_link_bytes("allreduce", nbytes, h, r)[1]
        for codec in ("off", "bf16", "fp8"):
            c = None if codec == "off" else codec
            cost_c = costmodel.collective_cost(
                "allreduce", "hier", nbytes, k, hosts=h, hier=(h, r),
                codec=c)
            if c is None:
                err = 0.0
            else:
                err = 0.0
                for i in range(iters):
                    x = jax.random.normal(
                        jax.random.PRNGKey(i), (n_elems,),
                        jnp.float32) * 0.02
                    y = roundtrip(x, c)
                    denom = max(float(jnp.max(jnp.abs(x))), 1e-30)
                    err = max(err,
                              float(jnp.max(jnp.abs(y - x))) / denom)
            rows.append({
                "size_mb": round(nbytes / 1e6, 4),
                "codec": codec,
                "topology": topology,
                "logical_dcn_bytes": int(logical),
                "wire_dcn_bytes": int(wire_bytes(int(logical), c)),
                "modeled_dcn_us": round(model.link_time_us(
                    "dcn", cost_c.dcn.rounds, cost_c.dcn.nbytes), 2),
                "rel_err": round(err, 8),
            })
    return rows


def bench_dispatch(comm, sizes_kb=(0.004, 4, 64), iters=100):
    """The dispatch sweep (``--dispatch-sweep``): per-CALL overhead of
    the three execution surfaces for the SAME one-allreduce program —

    - **eager**: ``mpx.allreduce`` outside any region (the one-op
      compiled-program cache; per call: flag-stamp check + interned key
      probe + cached jit call);
    - **spmd**: an ``mpx.spmd``-decorated program (per call: statics
      normalization, program-cache key build + probe, then the jit
      call);
    - **pinned**: ``mpx.compile`` (per call: one stamp validation, then
      the compiled executable — no key work at all; docs/aot.md).

    At the smallest payload the device op is noise and the numbers are
    pure host dispatch — the gap ``mpx.compile`` exists to close.  Each
    loop is timed whole (N calls then one sync), so per-call numbers
    amortize the device queue the way a real hot loop does.
    """
    n = comm.Get_size()
    rows = []
    for kb in sizes_kb:
        nelem = max(1, int(kb * 1e3 / 4))
        x = _ones(comm, n, nelem)

        def eager_call(v):
            return mpx.allreduce(v, op=mpx.SUM)[0]

        @mpx.spmd(comm=comm)
        def prog(v):
            return mpx.varying(mpx.allreduce(v, op=mpx.SUM)[0])

        def per_rank(v):
            return mpx.varying(mpx.allreduce(v, op=mpx.SUM)[0])

        pinned = mpx.compile(per_rank, x, comm=comm)

        def time_per_call(fn):
            fn(x)
            jax.block_until_ready(fn(x))  # compile + drain
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = fn(x)
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t0) / iters)
            return best

        rows.append({
            "size_kb": round(nelem * 4 / 1e3, 3),
            "eager_us": round(time_per_call(eager_call) * 1e6, 2),
            "spmd_us": round(time_per_call(prog) * 1e6, 2),
            "pinned_us": round(time_per_call(pinned) * 1e6, 2),
        })
        rows[-1]["pinned_vs_spmd"] = round(
            rows[-1]["spmd_us"] / rows[-1]["pinned_us"], 2
        ) if rows[-1]["pinned_us"] else None
    return rows


def bench_dispatch_unroll(comm, unrolls=(1, 8, 64), size_kb=0.004,
                          iters=50):
    """The megastep amortization sweep (``--dispatch-sweep``'s unroll
    axis): the SAME one-allreduce step pinned at ``unroll=N`` for each N
    (``mpx.compile(fn, ..., unroll=N)`` — one host dispatch executes N
    device-resident steps, docs/aot.md "Megastep execution"), timed per
    megastep call.

    Per-step **host** cost is separated from per-step device cost with a
    two-point fit: per-call wall is ``wall(N) = D + N * d`` (D = fixed
    host dispatch per call, d = on-chip per-step time), so ``d`` falls
    out of the difference between the two largest unrolls — the dispatch
    term cancels — and each row's ``per_step_host_us = wall(N)/N - d``
    is an independent measurement.  The 1/N amortization claim is then
    checkable from the saved artifact: host cost at unroll=64 should be
    ~1/64 of unroll=1 (CI asserts < 1/8).
    """
    n = comm.Get_size()
    nelem = max(1, int(size_kb * 1e3 / 4))
    x = _ones(comm, n, nelem)
    unrolls = sorted(set(int(u) for u in unrolls))

    def per_rank(v):
        return mpx.varying(mpx.allreduce(v, op=mpx.SUM)[0] * (1.0 / n))

    walls = {}
    for u in unrolls:
        pinned = mpx.compile(per_rank, x, comm=comm, unroll=u)
        pinned(x)
        jax.block_until_ready(pinned(x))  # compile + drain
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = pinned(x)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / iters)
        walls[u] = best

    # on-chip per-step estimate d from the two largest unrolls (the
    # host dispatch term cancels in the difference); one unroll = no fit
    if len(unrolls) >= 2:
        hi, lo = unrolls[-1], unrolls[-2]
        d = max(0.0, (walls[hi] - walls[lo]) / (hi - lo))
    else:
        d = 0.0
    rows = []
    for u in unrolls:
        wall = walls[u]
        rows.append({
            "unroll": u,
            "megastep_us": round(wall * 1e6, 2),
            "per_step_us": round(wall / u * 1e6, 3),
            "per_step_host_us": round(max(0.0, wall / u - d) * 1e6, 3),
        })
    return {
        "size_kb": round(nelem * 4 / 1e3, 3),
        "onchip_per_step_us": round(d * 1e6, 3),
        "rows": rows,
    }


def bench_health_overhead(comm, sizes_kb=(0.004, 4, 64), iters=200):
    """The health-plane overhead sweep (``--health-overhead``): per-call
    dispatch cost of the SAME eager one-allreduce program under four
    telemetry configurations — off, counters, counters + the armed
    flight-recorder ring (``MPI4JAX_TPU_HEALTH=on``), and full events —
    across payload sizes (docs/observability.md "Runtime health").

    The acceptance bar the sweep documents: ``counters_ring_us`` within
    10% of ``counters_us`` (``ring_overhead_ratio <= 1.10``) — the ring
    spill is one dict build + one list store riding the counter commit
    the counters tier already pays, with no new io_callbacks.  At the
    smallest payload the device op is noise and the columns are pure
    host dispatch, the worst case for relative overhead."""
    n = comm.Get_size()
    modes = (("off", "off", "off"),
             ("counters", "counters", "off"),
             ("counters_ring", "counters", "on"),
             ("events", "events", "on"))
    rows = []
    saved = {k: os.environ.get(k) for k in
             ("MPI4JAX_TPU_HEALTH", "MPI4JAX_TPU_FLIGHT_RING")}
    try:
        for kb in sizes_kb:
            nelem = max(1, int(kb * 1e3 / 4))
            x = _ones(comm, n, nelem)
            row = {"size_kb": round(nelem * 4 / 1e3, 3)}

            def eager_call(v):
                return mpx.allreduce(v, op=mpx.SUM)[0]

            for label, tmode, hmode in modes:
                os.environ["MPI4JAX_TPU_HEALTH"] = hmode
                mpx.telemetry.reset()
                mpx.set_telemetry_mode(tmode)
                eager_call(x)
                jax.block_until_ready(eager_call(x))  # compile + drain
                best = float("inf")
                for _ in range(5):
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = eager_call(x)
                    jax.block_until_ready(out)
                    best = min(best, (time.perf_counter() - t0) / iters)
                row[f"{label}_us"] = round(best * 1e6, 3)
            row["ring_overhead_ratio"] = (
                round(row["counters_ring_us"] / row["counters_us"], 3)
                if row["counters_us"] else None
            )
            rows.append(row)
    finally:
        mpx.set_telemetry_mode(None)
        mpx.telemetry.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return rows


# saved-sweep schema version: bumped when the --save payload shape
# changes, so the autotune fitter (mpi4jax_tpu/autotune/) can reject
# captures it does not understand instead of misreading them
MICRO_SCHEMA = "mpx-micro-bench/1"


def provenance_block(platform, n_devices):
    """The self-description every ``--save`` capture carries: jax/jaxlib
    versions, the topology the rows were measured under, and a content
    stamp of the whole declared-flag surface — so a saved sweep is a
    self-describing input to the autotune fitter (no more guessing what
    configuration produced which number).  One implementation serves
    every emitted artifact: this delegates to the canonical
    ``mpi4jax_tpu.autotune.runner.provenance_block``."""
    from mpi4jax_tpu.autotune.runner import provenance_block as _pb

    return _pb(platform, n_devices)


def fit_alpha_beta(points):
    """Least-squares fit of the alpha-beta line ``t_us = alpha_us +
    bytes / (gb_per_s * 1e3)`` over ``points`` = [(bytes, us), ...].
    Returns ``(alpha_us, gb_per_s)``, clamped into the cost-model
    schema's valid ranges (a tiny sweep can fit a negative intercept or
    a non-positive slope; the emitted file must still load verbatim)."""
    xs = np.asarray([p[0] for p in points], dtype=float)
    ys = np.asarray([p[1] for p in points], dtype=float)
    if len(points) >= 2 and float(np.ptp(xs)) > 0:
        slope, intercept = np.polyfit(xs, ys, 1)
    else:  # single size: all latency, analytic bandwidth
        slope, intercept = 0.0, float(ys.mean()) if len(points) else 0.0
    alpha_us = max(float(intercept), 0.001)
    # slope is us/byte; 1 GB/s == 1000 bytes/us
    gb_per_s = (1.0 / (float(slope) * 1e3)) if slope > 0 else 1e4
    gb_per_s = min(max(gb_per_s, 0.001), 1e4)
    return alpha_us, gb_per_s


def measured_ring_crossover(algo_rows):
    """The payload (bytes) where the measured ring first beats the
    measured butterfly, linearly interpolated between the straddling
    sweep points — the measured twin of
    ``MPI4JAX_TPU_RING_CROSSOVER_BYTES`` the MPX109/111/113 advisories
    cite when a tuning file is loaded.  ``None`` when the ring never
    wins in the sweep (or the sweep ran on one device — marked by a
    ``None`` speedup).  The interpolation itself is the canonical
    ``autotune.fit.measured_crossover`` (one copy of the math)."""
    from mpi4jax_tpu.autotune.fit import measured_crossover

    if any(row.get("ring_speedup") is None for row in algo_rows):
        return None  # 1-device sweep: no crossover is meaningful
    return measured_crossover(algo_rows, "size_mb", "butterfly_us",
                              "ring_us")


def build_cost_model(platform, n_devices, sendrecv_rows, algo_rows):
    """The ``--cost-calibrate`` payload: a complete ``mpx-tuning/1``
    file — the SUPERSET schema (mpi4jax_tpu/autotune/schema.py) that
    both ``MPI4JAX_TPU_COST_MODEL`` (the cost model keeps accepting
    plain ``mpx-cost-model/1`` files too — documented alias, no
    breaking change) and the ``MPI4JAX_TPU_TUNING`` config layer load
    verbatim: one calibration capture feeds the selector and the cost
    model alike (docs/autotune.md).

    ICI alpha/beta are fit by least squares over the sendrecv ring
    latency sweep (one hop = one alpha + payload/bandwidth — exactly
    the model's p2p term); the DCN class is scaled from the ICI fit by
    the documented analytic ratios (the virtual CPU mesh has no real
    DCN to measure; a multi-host capture overwrites it by hand or via a
    future ``mpx.autotune()``).  The measured ring crossover is
    interpolated from the forced butterfly-vs-ring sweep.
    """
    from mpi4jax_tpu.analysis import costmodel

    pts = [(r["size_kb"] * 1e3, r["hop_us"]) for r in sendrecv_rows]
    alpha_us, gb_per_s = fit_alpha_beta(pts)
    defaults = costmodel.DEFAULT_PARAMS
    dcn_alpha_ratio = (defaults["links"]["dcn"]["alpha_us"]
                       / defaults["links"]["ici"]["alpha_us"])
    dcn_bw_ratio = (defaults["links"]["dcn"]["gb_per_s"]
                    / defaults["links"]["ici"]["gb_per_s"])
    payload = {
        "schema": costmodel.TUNING_SCHEMA,
        "source": (f"benchmarks/micro.py --cost-calibrate ({platform}, "
                   f"{n_devices} devices; dcn scaled from the ici fit "
                   "by the analytic ratios)"),
        "links": {
            "ici": {"alpha_us": round(alpha_us, 4),
                    "gb_per_s": round(gb_per_s, 4)},
            "dcn": {"alpha_us": round(alpha_us * dcn_alpha_ratio, 4),
                    "gb_per_s": round(max(gb_per_s * dcn_bw_ratio,
                                          0.001), 4)},
        },
        "gamma_gb_per_s": defaults["gamma_gb_per_s"],
        "compute_gb_per_s": defaults["compute_gb_per_s"],
        "dispatch_us": defaults["dispatch_us"],
    }
    crossover = measured_ring_crossover(algo_rows)
    if crossover is not None:
        payload["measured"] = {"ring_crossover_bytes": crossover}
        # the superset's tuned section: the config layer serves this
        # value to resolve_algo when the file loads as MPI4JAX_TPU_TUNING
        payload["tuned"] = {"ring_crossover_bytes": crossover}
    payload["provenance"] = provenance_block(platform, n_devices)
    # the emitted file must load verbatim through BOTH consumers —
    # validate against the superset schema (which delegates the
    # cost-model section to the cost model's own rules) before anyone
    # saves it
    from mpi4jax_tpu.autotune.schema import validate_tuning_dict

    validate_tuning_dict(payload)
    return payload


def save_cost_model(payload, outdir=None):
    """Write a ``--cost-calibrate`` tuning file to
    ``benchmarks/results/`` (dated like ``save_results``), returning
    the path — the file ``MPI4JAX_TPU_COST_MODEL`` points at."""
    import datetime
    import re

    if outdir is None:
        outdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "results")
    os.makedirs(outdir, exist_ok=True)
    stamp = datetime.date.today().strftime("%Y%m%d")
    m = re.search(r"\((\w+), (\d+) devices", payload.get("source", ""))
    tag = f"{m.group(1)}_{m.group(2)}dev" if m else "unknown"
    path = os.path.join(outdir, f"cost_model_{tag}_{stamp}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def save_results(payload, outdir=None):
    """Write one sweep payload to ``benchmarks/results/`` (the ``--save``
    flag): ``micro_{platform}_{n}dev_{YYYYMMDD}.json``, returning the path
    (dated so committed captures are never silently clobbered)."""
    import datetime

    if outdir is None:
        outdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "results")
    os.makedirs(outdir, exist_ok=True)
    stamp = datetime.date.today().strftime("%Y%m%d")
    path = os.path.join(
        outdir,
        f"micro_{payload['platform']}_{payload['n_devices']}dev_{stamp}.json",
    )
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--json", action="store_true", help="machine-readable output")
    p.add_argument("--save", action="store_true",
                   help="write the sweep to benchmarks/results/")
    p.add_argument("--telemetry", action="store_true",
                   help="run under MPI4JAX_TPU_TELEMETRY=counters and embed "
                        "a per-section counter snapshot (algorithm "
                        "selections, bytes, cache stats) in the payload, so "
                        "saved BENCH files carry which algorithm actually "
                        "ran for each sweep (docs/observability.md)")
    p.add_argument("--sizes-mb", type=float, nargs="+",
                   default=[0.004, 0.25, 1, 4, 16, 64])
    p.add_argument("--sizes-kb", type=float, nargs="+",
                   default=[0.004, 4, 64, 1024])
    p.add_argument("--fusion-sweep", action="store_true",
                   help="also run the collective-fusion sweep (N small "
                        "allreduces fused vs unfused, per-op dispatch µs; "
                        "docs/overlap.md)")
    p.add_argument("--fusion-counts", type=int, nargs="+", default=[8, 32],
                   help="allreduce counts for --fusion-sweep")
    p.add_argument("--fusion-size-kb", type=float, default=64,
                   help="per-allreduce payload for --fusion-sweep (KiB)")
    p.add_argument("--overlap-sweep", action="store_true",
                   help="also run the async-overlap sweep (chunked "
                        "start/wait vs monolithic allreduce with "
                        "synthetic compute in the gap)")
    p.add_argument("--overlap-sizes-mb", type=float, nargs="+",
                   default=[1, 4],
                   help="payload sizes for --overlap-sweep (MB)")
    p.add_argument("--hierarchy-sweep", action="store_true",
                   help="also run the hierarchical-collective sweep "
                        "(flat ring vs the forced two-level ICI/DCN "
                        "lowering over a payload x topology grid; each "
                        "topology faked via MPI4JAX_TPU_TOPOLOGY and "
                        "stamped into the saved rows; docs/topology.md)")
    p.add_argument("--hierarchy-topologies", nargs="+",
                   default=["2x4", "4x2"],
                   help="MPI4JAX_TPU_TOPOLOGY specs for "
                        "--hierarchy-sweep (must cover the mesh size; "
                        "non-matching specs are skipped with a note)")
    p.add_argument("--hierarchy-sizes-mb", type=float, nargs="+",
                   default=[1, 4],
                   help="payload sizes for --hierarchy-sweep (MB)")
    p.add_argument("--alltoall-sweep", action="store_true",
                   help="also run the alltoall sweep (flat single-"
                        "exchange vs the forced two-level ICI/DCN "
                        "lowering vs the chunked async start/wait "
                        "split, over a payload x topology grid with "
                        "the modeled DCN byte/message columns; "
                        "docs/moe.md)")
    p.add_argument("--alltoall-topologies", nargs="+",
                   default=["2x4", "4x2"],
                   help="MPI4JAX_TPU_TOPOLOGY specs for "
                        "--alltoall-sweep (non-matching specs are "
                        "skipped with a note)")
    p.add_argument("--alltoall-sizes-mb", type=float, nargs="+",
                   default=[0.25, 1],
                   help="payload sizes for --alltoall-sweep (MB)")
    p.add_argument("--compression-sweep", action="store_true",
                   help="also run the wire-codec sweep (logical vs wire "
                        "DCN bytes, modeled DCN-leg time, and measured "
                        "round-trip error for {off,bf16,fp8} over a "
                        "payload grid; docs/compression.md)")
    p.add_argument("--compression-sizes-mb", type=float, nargs="+",
                   default=[0.25, 1, 4],
                   help="payload sizes for --compression-sweep (MB)")
    p.add_argument("--compression-topology", default="2x4",
                   help="modeled MPI4JAX_TPU_TOPOLOGY spec for "
                        "--compression-sweep's DCN-leg byte math")
    p.add_argument("--dispatch-sweep", action="store_true",
                   help="also run the dispatch sweep (per-call overhead "
                        "of eager vs spmd vs mpx.compile-pinned for the "
                        "same one-allreduce program across payload "
                        "sizes; docs/aot.md)")
    p.add_argument("--dispatch-sizes-kb", type=float, nargs="+",
                   default=[0.004, 4, 64],
                   help="payload sizes for --dispatch-sweep (KiB)")
    p.add_argument("--dispatch-iters", type=int, default=100,
                   help="calls per timed loop for --dispatch-sweep")
    p.add_argument("--dispatch-unrolls", type=int, nargs="+",
                   default=[1, 8, 64],
                   help="megastep trip counts for --dispatch-sweep's "
                        "unroll axis (mpx.compile(fn, ..., unroll=N): "
                        "per-step host cost amortizes ~1/N; "
                        "docs/aot.md 'Megastep execution')")
    p.add_argument("--health-overhead", action="store_true",
                   help="also run the health-plane overhead sweep "
                        "(per-call dispatch cost under off / counters / "
                        "counters+flight-ring / events across payloads; "
                        "the counters+ring column must stay within 10%% "
                        "of counters-only — docs/observability.md "
                        "'Runtime health')")
    p.add_argument("--health-sizes-kb", type=float, nargs="+",
                   default=[0.004, 4, 64],
                   help="payload sizes for --health-overhead (KiB)")
    p.add_argument("--health-iters", type=int, default=200,
                   help="calls per timed loop for --health-overhead")
    p.add_argument("--cost-calibrate", action="store_true",
                   help="fit the static cost model's alpha/beta per "
                        "link class (least squares over the sendrecv "
                        "latency sweep) plus the measured ring "
                        "crossover, and emit an mpx-cost-model/1 "
                        "tuning file that MPI4JAX_TPU_COST_MODEL loads "
                        "verbatim (with --save: written to "
                        "benchmarks/results/cost_model_*.json; "
                        "docs/analysis.md 'Cost model')")
    args = p.parse_args()

    from mpi4jax_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    devices = jax.devices()
    mesh = mpx.make_world_mesh(devices=devices)
    comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)
    n = comm.Get_size()

    telemetry_sections = {}

    def _section(name, fn, *fn_args):
        """Run one sweep; under --telemetry, bracket it with a counter
        reset/snapshot so each section's snapshot attributes ITS traffic
        (algo selections per op, bytes, cache churn) and nothing else's.
        cache_stats are process-cumulative (reset only by clear_caches),
        so the section embeds the DELTA over the sweep."""
        if not args.telemetry:
            return fn(*fn_args)
        mpx.telemetry.reset()
        cache_before = mpx.cache_stats()
        rows = fn(*fn_args)
        cache_after = mpx.cache_stats()
        snap = mpx.telemetry.snapshot()
        telemetry_sections[name] = {
            "ops": snap["ops"],
            "meters": snap["meters"],
            "cache_stats": {
                k: (cache_after[k] - cache_before[k]
                    if k in ("hits", "misses", "evictions")
                    else cache_after[k])
                for k in cache_after
            },
        }
        return rows

    if args.telemetry:
        mpx.set_telemetry_mode("counters")

    ar = _section("allreduce", bench_allreduce, comm, args.sizes_mb)
    pp = _section("sendrecv_ring", bench_sendrecv_ring, comm, args.sizes_kb)
    pr = _section("prod_butterfly", bench_prod_and_split, comm,
                  args.sizes_mb[:4])
    al = _section("allreduce_algos", bench_allreduce_algos, comm,
                  args.sizes_mb)
    fu = (_section("fusion", bench_fusion, comm, tuple(args.fusion_counts),
                   args.fusion_size_kb)
          if args.fusion_sweep else None)
    ov = (_section("overlap", bench_overlap, comm,
                   tuple(args.overlap_sizes_mb))
          if args.overlap_sweep else None)
    hs = (_section("hierarchy", bench_hierarchy, comm,
                   tuple(args.hierarchy_sizes_mb),
                   tuple(args.hierarchy_topologies))
          if args.hierarchy_sweep else None)
    a2a = (_section("alltoall", bench_alltoall, comm,
                    tuple(args.alltoall_sizes_mb),
                    tuple(args.alltoall_topologies))
           if args.alltoall_sweep else None)
    cp = (_section("compression", bench_compression, comm,
                   tuple(args.compression_sizes_mb),
                   args.compression_topology)
          if args.compression_sweep else None)
    ds = (_section("dispatch", bench_dispatch, comm,
                   tuple(args.dispatch_sizes_kb), args.dispatch_iters)
          if args.dispatch_sweep else None)
    du = (_section("dispatch_unroll", bench_dispatch_unroll, comm,
                   tuple(args.dispatch_unrolls),
                   min(args.dispatch_sizes_kb), args.dispatch_iters)
          if args.dispatch_sweep else None)
    # NOT under _section: the sweep manages its own telemetry modes
    ho = (bench_health_overhead(comm, tuple(args.health_sizes_kb),
                                args.health_iters)
          if args.health_overhead else None)

    payload = {
        "schema": MICRO_SCHEMA,
        "platform": devices[0].platform,
        "n_devices": n,
        # self-description (jax/jaxlib, topology, config stamp): saved
        # sweeps are fitter inputs, so they must say what produced them
        "provenance": provenance_block(devices[0].platform, n),
        # with a single device there is no interconnect to measure:
        # never read 1-device numbers as link bandwidth or latency
        "environment": (
            f"{n}-device {devices[0].platform} "
            f"({devices[0].device_kind})"
            + ("; no interconnect to measure" if n == 1 else "")
        ),
        "allreduce": ar,
        "sendrecv_ring": pp,
        "prod_butterfly": pr,
        "allreduce_algos": al,
    }
    if fu is not None:
        payload["fusion"] = fu
    if ov is not None:
        payload["overlap"] = ov
    if hs is not None:
        payload["hierarchy"] = hs
        payload["hierarchy_topologies"] = list(args.hierarchy_topologies)
    if a2a is not None:
        payload["alltoall"] = a2a
        payload["alltoall_topologies"] = list(args.alltoall_topologies)
    if cp is not None:
        payload["compression"] = cp
        payload["compression_topology"] = args.compression_topology
    if ds is not None:
        payload["dispatch"] = ds
        # the AOT/persistent-cache counters are the sweep's provenance:
        # whether the pinned column was served from disk or compiled
        # (one cache_stats() call — it walks the disk tier when enabled)
        cstats = mpx.cache_stats()
        payload["dispatch_cache_stats"] = {
            k: cstats[k] for k in ("aot", "disk_cache")
        }
    if du is not None:
        payload["dispatch_unroll"] = du
    if ho is not None:
        payload["health_overhead"] = ho
    if args.cost_calibrate:
        cm = build_cost_model(devices[0].platform, n, pp, al)
        payload["cost_model"] = cm
        if args.save:
            path = save_cost_model(cm)
            print(f"saved cost model: {path}", file=sys.stderr)
    if args.telemetry:
        payload["telemetry"] = telemetry_sections
        mpx.set_telemetry_mode(None)
    if args.save:
        path = save_results(payload)
        print(f"saved: {path}", file=sys.stderr)
    if args.json:
        print(json.dumps(payload))
        return

    print(f"platform={devices[0].platform} n_devices={n}")
    print("\nallreduce (SUM, f32)          time/op      bus bandwidth/device")
    for r in ar:
        bw = f"{r['bus_gb_s']} GB/s" if r["bus_gb_s"] is not None else "n/a (1 device)"
        print(f"  {r['size_mb']:>10.3f} MB   {r['time_us']:>10.1f} us   {bw}")
    print("\nsendrecv ring (shift(1))      time/hop     link bandwidth")
    for r in pp:
        bw = (f"{r['link_gb_s']} GB/s" if r["link_gb_s"] is not None
              else "n/a (1 device)")
        print(f"  {r['size_kb']:>10.2f} KB   {r['hop_us']:>10.2f} us   {bw}")
    print("\nPROD butterfly (log-depth)    whole comm   even/odd split")
    for r in pr:
        sp = (f"{r['prod_split_us']:>10.1f} us"
              if r["prod_split_us"] is not None else "n/a (1 device)")
        print(f"  {r['size_mb']:>10.3f} MB   {r['prod_us']:>10.1f} us   {sp}")
    print("\nPROD algo crossover           butterfly    ring         ring speedup")
    for r in al:
        sp = (f"{r['ring_speedup']:>6.2f}x"
              if r["ring_speedup"] is not None else "n/a (1 device)")
        print(f"  {r['size_mb']:>10.3f} MB   {r['butterfly_us']:>10.1f} us"
              f"   {r['ring_us']:>10.1f} us   {sp}")
    if fu is not None:
        print("\nfusion sweep (SUM, f32)       unfused      fused        speedup")
        for r in fu:
            print(f"  {r['count']:>4} x {r['size_kb']:>7.1f} KB"
                  f"   {r['unfused_us_per_op']:>8.2f} us"
                  f"   {r['fused_us_per_op']:>8.2f} us"
                  f"   {r['fused_speedup']:>6.2f}x")
    if ov is not None:
        print("\noverlap sweep (SUM, f32)      monolithic   start/wait   speedup")
        for r in ov:
            print(f"  {r['size_mb']:>10.3f} MB   {r['monolithic_us']:>8.1f} us"
                  f"   {r['overlap_us']:>8.1f} us"
                  f"   {r['overlap_speedup']:>6.2f}x")
    if hs is not None:
        print("\nhierarchy sweep (PROD, f32)   topology   flat ring"
              "    two-level    hier speedup")
        for r in hs:
            sp = (f"{r['hier_speedup']:>6.2f}x"
                  if r["hier_speedup"] is not None else "n/a (1 device)")
            print(f"  {r['size_mb']:>10.3f} MB   {r['topology']:>8}"
                  f"   {r['flat_us']:>8.1f} us   {r['hier_us']:>8.1f} us"
                  f"   {sp}")
    if a2a is not None:
        print("\nalltoall sweep (f32)          topology   flat"
              "         two-level    async        hier speedup")
        for r in a2a:
            sp = (f"{r['hier_speedup']:>6.2f}x"
                  if r["hier_speedup"] is not None else "n/a (1 device)")
            print(f"  {r['size_mb']:>10.4f} MB   {r['topology']:>8}"
                  f"   {r['flat_us']:>8.1f} us   {r['hier_us']:>8.1f} us"
                  f"   {r['async_us']:>8.1f} us   {sp}")
    if cp is not None:
        print("\ncompression sweep (f32)       codec  logical DCN"
              "   wire DCN     modeled      max rel err")
        for r in cp:
            print(f"  {r['size_mb']:>10.4f} MB   {r['codec']:>4}"
                  f"   {r['logical_dcn_bytes']:>10}   {r['wire_dcn_bytes']:>10}"
                  f"   {r['modeled_dcn_us']:>8.2f} us   {r['rel_err']:.2e}")
    if ds is not None:
        print("\ndispatch sweep (SUM, f32)     eager        spmd"
              "         pinned       pinned vs spmd")
        for r in ds:
            sp = (f"{r['pinned_vs_spmd']:>6.2f}x"
                  if r["pinned_vs_spmd"] is not None else "-")
            print(f"  {r['size_kb']:>10.3f} KB   {r['eager_us']:>8.2f} us"
                  f"   {r['spmd_us']:>8.2f} us   {r['pinned_us']:>8.2f} us"
                  f"   {sp}")
    if ho is not None:
        print("\nhealth overhead (eager SUM)   off          counters"
              "     +ring        events       ring/counters")
        for r in ho:
            ratio = (f"{r['ring_overhead_ratio']:>6.3f}x"
                     if r["ring_overhead_ratio"] is not None else "-")
            print(f"  {r['size_kb']:>10.3f} KB   {r['off_us']:>8.2f} us"
                  f"   {r['counters_us']:>8.2f} us"
                  f"   {r['counters_ring_us']:>8.2f} us"
                  f"   {r['events_us']:>8.2f} us   {ratio}")
    if du is not None:
        print(f"\nmegastep unroll sweep ({du['size_kb']} KB; on-chip "
              f"~{du['onchip_per_step_us']} us/step)"
              "\n  unroll   megastep/call   per step     host/step")
        for r in du["rows"]:
            print(f"  {r['unroll']:>6}   {r['megastep_us']:>10.2f} us"
                  f"   {r['per_step_us']:>8.3f} us"
                  f"   {r['per_step_host_us']:>8.3f} us")
    if args.cost_calibrate:
        cm = payload["cost_model"]
        ici = cm["links"]["ici"]
        print(f"\ncost model fit (ici): alpha {ici['alpha_us']} us, "
              f"{ici['gb_per_s']} GB/s"
              + (f"; measured ring crossover "
                 f"{cm['measured']['ring_crossover_bytes']} B"
                 if "measured" in cm else ""))


if __name__ == "__main__":
    main()
