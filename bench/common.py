"""What every part of the benchmark shares: where things are, how a cell
is found from its name, the compile cache, and the device.

A cell (`workloads` in BENCHMARK.json) names a configuration and a
traffic mix. Everything else is found by name:

  bench/configs/<config>.json    the deployment (the entry's `file`)
  bench/traffic/<traffic>.json   the mix; its `runner` names
  bench/runners/<runner>.py      the code that builds, warms and drives it
  bench/limits/<cell>.json       the limit of each number `correct` compares
  bench/metrics/<metric>.py      one reader per metric, `read(record)`;
                                 `<quantity>.<part>` (one quantity split
                                 by the cells that report it) falls back
                                 to `<quantity>.py`

so a new cell, configuration, mix or metric is new files plus entries.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_spec() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json at {ROOT}")
    return json.loads(path.read_text())


def cell_entry(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in spec['workloads']]}")


def load_config(spec: dict, name: str) -> dict:
    for cfg in spec["configs"]:
        if cfg["name"] == name:
            return json.loads((ROOT / cfg["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_limits(workload: str) -> dict:
    return json.loads((BENCH_DIR / "limits" / f"{workload}.json")
                      .read_text())


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(name: str) -> ModuleType:
    return _module(BENCH_DIR / "runners" / f"{name}.py")


def metric_reader(name: str) -> ModuleType:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        path = BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py"
    return _module(path)


def metrics_of(spec: dict, workload: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries that apply to a cell."""
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


def span(name: str, on: bool):
    """A host span `bench.<name>` in the profiler's trace, when tracing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def use_program() -> None:
    """Make the program under test importable from the checkout."""
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"no program under test at {ROOT / 'src'}")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> Path:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where `JAX_COMPILATION_CACHE_DIR` says), keeping every
    program however fast it compiled, so only a checkout's first run of
    a cell compiles."""
    import jax
    env = os.environ.get(CACHE_ENV)
    path = Path(env) if env else ROOT / ".jax_cache"
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    """The accelerator as JAX reports it; raises unless JAX sees at least
    `chips` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise RuntimeError(
            f"this cell needs {chips} TPU chip(s); JAX sees "
            f"{len(devs)} {devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
