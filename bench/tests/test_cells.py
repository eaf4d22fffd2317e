"""Each cell runs end to end on the CPU through the same `prepare` and
`measure` that bench/run.py calls; only the look for a chip is skipped.
`SMALL` shortens a cell's trace where a test needs it."""
import json
import subprocess
import sys
import time

import pytest

from bench import common, run

CELLS = [c["name"] for c in common.load_spec()["workloads"]]

# shorter traces for the tests that drive many runs
SMALL = {"table6.replan": {"mix": {"periods": 8}}}


def cpu_run(workload, seed=3, seconds=0.5, trace=False, small=True):
    """A run as bench/run.py makes it, on the CPU."""
    import jax
    parts = run.prepare(workload)
    for key, extra in (SMALL.get(workload, {}) if small else {}).items():
        parts[key].update(extra)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    return run.measure(parts, seed, seconds, trace, device,
                       time.perf_counter())


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    out = cpu_run(workload, seed=2**31 + 11, small=False)
    spec = common.load_spec()
    want = {m["name"] for m in common.metrics_of(spec, workload,
                                                 "end_to_end")}
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(common.load_limits(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_host_metrics(workload):
    # the CPU has no device plane: device metrics are left out, never 0
    out = cpu_run(workload, trace=True)
    names = set(out["metrics"])
    assert "compiles_in_window" in names
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert not any(n.startswith(("device_", "decide_host"))
                   for n in names)
    assert "busy_s" not in out["device"]


def test_cli_refuses_a_machine_without_the_chip():
    proc = subprocess.run(
        [sys.executable, str(common.BENCH_DIR / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_benchmark_json_names_files_that_exist():
    spec = common.load_spec()
    for cell in spec["workloads"]:
        common.load_config(spec, cell["config"])
        common.load_limits(cell["name"])
        json.loads((common.BENCH_DIR / "traffic" /
                    f"{cell['traffic']}.json").read_text())
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert callable(common.metric_reader(m["name"]).read)
