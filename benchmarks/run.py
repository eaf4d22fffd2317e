"""Benchmark harness: one function per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV lines.

  python -m benchmarks.run              # everything (+roofline when the
                                        # dry-run artifacts exist)
  python -m benchmarks.run --roofline   # force §Roofline
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--art-dir", default="experiments/dryrun")
    args, _ = ap.parse_known_args()

    from repro.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    from benchmarks.kernel_bench import bench_kernels
    from benchmarks.paper_tables import (bench_fig5_fig6, bench_table5,
                                         bench_table7)
    from benchmarks.scheduler_scale import bench_scheduler_scale

    print("name,us_per_call,derived")
    for bench in (bench_table5, bench_table7, bench_fig5_fig6,
                  bench_scheduler_scale, bench_kernels):
        _, csv = bench()
        for line in csv:
            print(line)

    have_art = os.path.isdir(args.art_dir) and \
        len(os.listdir(args.art_dir)) >= 40
    if args.roofline or have_art:
        from benchmarks.roofline import (bench_roofline, compare_baseline,
                                         to_markdown)
        rows, csv = bench_roofline(args.art_dir)
        for line in csv:
            print(line)
        base_dir = os.path.join(os.path.dirname(args.art_dir) or ".",
                                "dryrun_baseline")
        if os.path.isdir(base_dir):
            for line in compare_baseline(base_dir, args.art_dir):
                print(line)
        md = to_markdown(rows)
        out = os.path.join(os.path.dirname(args.art_dir) or ".",
                           "roofline.md")
        with open(out, "w") as f:
            f.write(md + "\n")
        print(f"# roofline table written to {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
