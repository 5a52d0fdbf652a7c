"""Entry point: ``python3 chipbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` (or ``python3 -m chipbench.run``), from the
root of a checkout.  See ``harness.py``."""

import time

_T0 = time.perf_counter()  # setup_s counts from here: before jax is imported

import os  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=_T0))
