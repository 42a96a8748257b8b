#!/usr/bin/env python3
"""The readings that the limit of ``correct`` is set from, for one cell.

For each seed, in one process: serve a short window at the cell's own load
through the timed path, then compare a sample of the served tokens with the
float32 reference (the program's reading), and run the control over the
same prompts and tokens: the reference with its matrix products in the
precision below the configuration's (float8 e4m3 below bfloat16), read as
the gap of the token it puts first at each position.  Both are judged by
the predicate that decides ``correct`` in a run: a sound program reads
``program_correct`` true, the control ``control_correct`` false.

  python3 benchmarks/chip/control.py --workload granite-8b.react_decode \\
      --seeds 11,12,13 --seconds 15

One JSON line per seed on standard output; the exit code is 1 where the
program fails or the control passes on any seed.  The benchmark's own runs
do not run the control.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from chipbench import harness
    c = harness.Cell(args.workload, HERE, os.path.join(ROOT, "BENCHMARK.json"))
    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rec = harness.measure(c, seed, args.seconds, False)
        gap, picked, ctrl = harness.compare(c, rec, control=True)
        ok = harness.is_correct(rec, picked, gap, c.limit)
        ctrl_ok = harness.is_correct(rec, picked, ctrl, c.limit)
        sound = sound and ok and not ctrl_ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program_gap": gap, "program_correct": ok,
                          "control_gap": ctrl, "control_correct": ctrl_ok,
                          "limit": c.limit, "turns": len(picked),
                          "served_tokens": sum(len(t.tokens) for t in picked),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
