#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

  python3 benchmarks/chip/run.py --workload granite-8b.react_decode \\
      --seed 7 --seconds 40 --trace 0

Run from the root of a checkout, on a machine with the TPU chips the cell
asks for; it exits non-zero, printing no result, where JAX finds none.  The
last line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and ``compared`` last); the last lines of standard error give the numbers
compared with their limits.  ``--trace 1`` reports the per-layer metrics and
profiles a stretch of the window; ``--trace 0`` the end-to-end ones.  With
``CHIPBENCH_KEEP_TRACE=<dir>`` a traced run keeps a copy of its
``.xplane.pb`` there (``data/cut_trace.py`` cuts one down for the tests);
with ``CHIPBENCH_DUMP=<dir>`` a run keeps its turns, decode steps and
prefills there as JSON.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from chipbench import harness
    try:
        harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                         bench_root=HERE, bench_json=os.path.join(ROOT, "BENCHMARK.json"),
                         t_start=T_START)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
