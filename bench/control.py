"""Readings that set the limits of `correct`, for one cell over many seeds.

    python3 bench/control.py --workload table6.replan --seconds 3 \
        --seeds 1 2 3 --control-seeds 1 2 3

For every seed in `--seeds` this builds the cell, warms it and drives a
short window at the cell's own load (as bench/run.py does), then prints
the numbers `correct` compares as the program gives them: the lower
readings. For every seed in `--control-seeds` it also prints the same
numbers with the plain reference, computed in bfloat16, put in the
program's place (each runner's `control()`): the upper readings. One
JSON line per seed and side, then a summary line with the largest sound
and smallest control reading of each number beside its limit. Needs the
chip, like bench/run.py; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common, run  # noqa: E402  (sets TPU_LOG_DIR)


def readings(parts: dict, seeds, control_seeds, seconds: float,
             answers: int | None = None):
    """Yields one dict per seed and side, then the summary. `parts` is
    bench/run.py's `prepare`; the device is whatever JAX holds."""
    limits = parts["limits"]
    sound, control = {}, {}
    for seed in sorted(set(seeds) | set(control_seeds)):
        runner = common.runner(parts["mix"]["runner"]).Cell(
            parts["config"], parts["mix"], seed)
        runner.setup()
        runner.window(seconds, annotate=False)
        if seed in seeds:
            r = runner.check()
            yield {"seed": seed, "side": "program", **r}
            for k, v in r.items():
                sound[k] = max(sound.get(k, v), v)
        if seed in control_seeds:
            t = time.perf_counter()
            r = runner.control(answers)
            yield {"seed": seed, "side": "control", **r,
                   "control_s": time.perf_counter() - t}
            for k, v in r.items():
                control[k] = min(control.get(k, v), v)
    yield {"summary": {k: {"lower": sound.get(k), "upper": control.get(k),
                           "limit": lim} for k, lim in limits.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--answers", type=int, default=None,
                    help="control only the window's first N answers")
    args = ap.parse_args(argv)
    parts = run.prepare(args.workload)
    common.use_program()
    common.device_info(int(parts["cell"]["chips"]))
    common.enable_compile_cache()
    for line in readings(parts, args.seeds, args.control_seeds,
                         args.seconds, args.answers):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
