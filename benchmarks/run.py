"""Benchmark entrypoint: one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

``--smoke`` runs a minutes-scale sanity pass (scheduler + admission + a
reduced eval plan) for the tier-1 loop; the full suite is the default.
``--only SECTION`` filters sections by substring.
``--json PATH`` additionally writes every row (plus its section) as a JSON
list — CI artifacts this so bench regressions are diffable across runs.
A section that raises prints an ``ERROR`` row, the other sections still run,
and the exit code is non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from typing import Callable, Iterable, List, Optional, Tuple

Section = Tuple[str, Callable[[], Iterable[dict]]]


def run_sections(sections: List[Section], json_path: Optional[str] = None) -> int:
    """Print every section's rows as CSV; return the exit code, 1 when any
    section raised."""
    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for title, fn in sections:
        print(f"# --- {title} ---", file=sys.stderr)
        try:
            for row in fn():
                print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"")
                all_rows.append({"section": title, **row})
        except Exception as e:  # report the section, run the others
            traceback.print_exc()
            failed.append(title)
            print(f"{title},0,\"ERROR: {type(e).__name__}: {e}\"")
            all_rows.append({"section": title, "name": title,
                             "us_per_call": 0.0,
                             "derived": f"ERROR: {type(e).__name__}: {e}"})
    if json_path:
        with open(json_path, "w") as f:
            json.dump(all_rows, f, indent=2)
        print(f"# wrote {len(all_rows)} rows to {json_path}", file=sys.stderr)
    if failed:
        print(f"# failed sections: {failed}", file=sys.stderr)
        return 1
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fast sanity pass: scheduler, admission, reduced eval plan")
    ap.add_argument("--only", default=None,
                    help="run only sections whose name contains this substring")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all rows as a JSON list to PATH")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_ablation, bench_admission, bench_beam,
                            bench_engine, bench_eval_plan, bench_kernels,
                            bench_memo, bench_scheduler, bench_serving,
                            bench_table1, roofline)

    if args.smoke:
        sections = [
            ("scheduler (runtime overhead)",
             lambda: bench_scheduler.run(smoke=True)),
            ("admission (fused vs reference)",
             lambda: bench_admission.run(smoke=True)),
            ("beam (tree assembly occupancy/reuse)",
             lambda: bench_beam.run(smoke=True)),
            ("serving (concurrent episodes, shared beam)",
             lambda: bench_serving.run(smoke=True)),
            ("memo (result store, cache-served commits)",
             lambda: bench_memo.run(smoke=True)),
            ("eval_plan (paper SS9 metrics, smoke)",
             lambda: bench_eval_plan.run(smoke=True)),
        ]
    else:
        sections = [
            ("table1 (paper Table 1: end-to-end speedup)", bench_table1.run),
            ("eval_plan (paper SS9 metrics)", bench_eval_plan.run),
            ("ablation (EU objective / beam width)", bench_ablation.run),
            ("scheduler (runtime overhead)", bench_scheduler.run),
            ("admission (fused vs reference)", bench_admission.run),
            ("beam (tree assembly occupancy/reuse)", bench_beam.run),
            ("serving (concurrent episodes, shared beam)", bench_serving.run),
            ("memo (result store, cache-served commits)", bench_memo.run),
            ("engine (B-PASTE x serving engine integration)", bench_engine.run),
            ("kernels", bench_kernels.run),
            ("roofline (dry-run derived)", roofline.run),
        ]
    if args.only:
        sections = [(t, f) for t, f in sections if args.only in t]
    sys.exit(run_sections(sections, args.json))


if __name__ == "__main__":
    main()
