"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration from the seed (weights and data on the
device), runs its first rounds as set-up (compiling every program the
window uses; the persistent compilation cache lives in the checkout's
``.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set), then calls
``FLServer.run_round()`` back to back for ``--seconds``. Afterwards it
checks the first rounds against the plain reference and prints one JSON
line: ``correct``, ``attempted`` and ``failed`` client updates, the
cell's end-to-end metrics (``--trace 0``) or per-layer metrics from a
profiler trace (``--trace 1``), the device, and last the numbers
compared with their limits. Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
