"""Benchmark driver — prints ONE JSON line.  Needs a TPU: it refuses to run
on any other platform (a CPU run of this workload is Pallas interpret mode,
which nobody deploys and no number from it is a device number).

Workload: the reference's published benchmark (BASELINE.md) — the
shallow-water solver at 10x linear scale (3600 x 1800 interior), 0.1
simulated days, timed after warm-up compile, exactly the reference's
protocol (ref docs/shallow-water.rst:44-55).

Metric: steps/sec/chip.  ``vs_baseline`` compares wall time against the
reference's best published single-device result (Tesla P100, 6.28 s for
the same workload, ref docs/shallow-water.rst:81-83): values > 1 mean
faster than the reference's GPU.

The timed region is one dispatch of the AOT-pinned whole-run program,
closed by ``jax.block_until_ready`` on its outputs.
"""

import argparse
import json
import os
import sys

import jax


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--unroll", type=int, default=0,
        help="megastep trip count: run the solve as pinned megastep "
             "dispatches of N device-resident steps each instead of one "
             "whole-run program (mpx.compile(fn, ..., unroll=N); "
             "docs/aot.md 'Megastep execution').  0 (default) keeps the "
             "whole-run program.")
    args = parser.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        sys.exit(f"bench.py needs a TPU; jax {jax.__version__} found {device}")

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "examples")
    )
    from shallow_water import DAY_IN_SECONDS, Config, pick_process_grid, solve_fused

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    nproc_y, nproc_x = pick_process_grid(len(devices))
    cfg = Config(nproc_y=nproc_y, nproc_x=nproc_x, nx=3600, ny=1800)
    t1 = 0.1 * DAY_IN_SECONDS

    # fast="auto": single-device runs use the fused whole-step Pallas
    # kernel (model_step_pallas); multi-device meshes use the carried-
    # frame wide-halo kernel (model_step_pallas_wide: widen once, 4
    # margin-band messages per pair of steps), or the split-phase
    # kernels (model_step_pallas_halo) below its 16-cell minimum local
    # interior.  pinned=True: the timed call executes an
    # mpx.compile-pinned artifact (docs/aot.md); a failed pin raises.
    wall, n_steps = solve_fused(cfg, t1, devices=devices, fast="auto",
                                pinned=True, unroll=args.unroll)
    aot_stats = mpx.cache_stats()["aot"]

    ref_gpu_wall = 6.28  # Tesla P100, 1 process (BASELINE.md)
    # achieved HBM bandwidth, state-traffic model: each step must at least
    # read and write the six (ny_l, nx_l) f32 state fields — a *lower
    # bound* on real traffic (intermediates add more)
    field_bytes = cfg.nproc * cfg.ny_local * cfg.nx_local * 4
    gbps = 12 * field_bytes * n_steps / wall / 1e9 / len(devices)
    print(
        json.dumps(
            {
                "metric": "shallow-water steps/sec/chip (3600x1800, 0.1 days)",
                "value": round(n_steps / wall / len(devices), 2),
                "unit": "steps/s/chip",
                "vs_baseline": round(ref_gpu_wall / wall, 3),
                "state_traffic_gb_per_s": round(gbps, 1),
                "wall_s": round(wall, 3),
                "n_steps": n_steps,
                "pins": aot_stats["pins"],
                "pinned_calls": aot_stats["calls"],
                "unroll": args.unroll,
                "device": device,
                "jax": jax.__version__,
            }
        )
    )


if __name__ == "__main__":
    main()
