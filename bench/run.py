"""Run one benchmark cell once, in this process, on the chip.

    python3 bench/run.py --workload table6.replan --seed 7 --seconds 10 --trace 0

Finds the cell in BENCHMARK.json and everything else by name (see
bench/common.py), refuses any device that is not a TPU, enables JAX's
persistent compilation cache inside the checkout, and then:

1. set-up: builds the cell's inputs from the seed and warms every program
   the window uses (`setup_s` runs from process start to the window);
2. window: drives the traffic for `--seconds` seconds, to the end of the
   replay in flight then; with `--trace 1` under the JAX profiler, with
   the benchmark's host spans;
3. reads `memory_peak_bytes`, then checks what the window produced
   against the plain reference, and prints each number compared beside
   its limit as the last lines on standard error;
4. prints one JSON line: `correct`, `attempted`, `failed`, the cell's
   end-to-end metrics (`--trace 0`) or per-layer metrics (`--trace 1`,
   with the device's busy time and a breakdown), the device, and the
   checks last.

Exits non-zero, printing no result, when something fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402

# libtpu would log under /tmp; the benchmark writes only in its checkout
# and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def prepare(workload: str) -> dict:
    """What a run of `workload` is made from, found by name: the
    BENCHMARK.json entry, the configuration, the mix and the limits."""
    spec = common.load_spec()
    cell = common.cell_entry(spec, workload)
    from bench.traffic import gen
    mix = gen.load_mix(cell["traffic"])
    return {"spec": spec, "workload": workload, "cell": cell,
            "config": common.load_config(spec, cell["config"]), "mix": mix,
            "limits": common.load_limits(workload)}


def measure(parts: dict, seed: int, seconds: float, trace: bool,
            device: dict, t_start: float) -> dict:
    """Set-up, window and check of one run on the device JAX holds;
    returns the result line as a dict."""
    import jax
    runner = common.runner(parts["mix"]["runner"]).Cell(
        parts["config"], parts["mix"], seed)
    runner.setup()
    compiles: list = []                 # when each backend compile ended

    def on_event(event: str, secs: float, **kw) -> None:
        if event == common.BACKEND_COMPILE_EVENT:
            compiles.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_event)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            # device ops and the benchmark's own spans; no Python tracer,
            # which would record every call and slow the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        w0 = time.perf_counter()
        record = runner.window(seconds, annotate=trace)
        w1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
            from bench import trace as tr
            record["trace"] = tr.summarize(tr.load(trace_dir),
                                           runner.idle_label)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    record["setup_s"] = setup_s
    record["compiles_in_window"] = sum(w0 <= t <= w1 for t in compiles)
    device = dict(device)
    peak = common.memory_peak_bytes()
    if peak is not None:
        device["memory_peak_bytes"] = peak

    limits = parts["limits"]
    readings = runner.check()
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in readings.items()}
    correct = all(v <= limits[k] for k, v in readings.items())

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in common.metrics_of(parts["spec"], parts["workload"], kind):
        value = common.metric_reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": correct, "attempted": record["decisions"],
           "failed": 0, "metrics": metrics, "device": device}
    t = record.get("trace")
    if trace and t:
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["top_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = checks
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of one cell on the chip; returns the result line."""
    parts = prepare(workload)
    common.use_program()
    device = common.device_info(int(parts["cell"]["chips"]))
    common.enable_compile_cache()
    return measure(parts, seed, seconds, trace, device, T_START)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
