"""The reduction from a profiler trace to the per-layer metrics gives
known numbers on a small recorded trace, and reads the profiler's own
files."""
import json
from pathlib import Path

import pytest

from bench import common, trace

DATA = Path(__file__).with_name("data")


@pytest.fixture
def summary():
    rec = json.loads((DATA / "trace_small.json").read_text())
    return trace.summarize(rec, idle_label="engine")


def test_busy_and_spans_inside_the_window(summary):
    # device busy [100, 300] and [500, 600] inside the [0, 1000] window;
    # the op at 1200 lies outside it
    assert summary["window_s"] == pytest.approx(1000e-9)
    assert summary["busy_s"] == pytest.approx(300e-9)
    assert summary["span_s"]["decide"] == pytest.approx(560e-9)
    assert summary["busy_in"]["decide"] == pytest.approx(300e-9)


def test_breakdown(summary):
    assert summary["top_ops"] == [["jit_a:fusion.2", pytest.approx(150e-9)],
                                  ["jit_a:fusion.1", pytest.approx(100e-9)],
                                  ["jit_b:copy.3", pytest.approx(100e-9)]]
    assert summary["idle_gaps"] == [["engine", pytest.approx(400e-9)],
                                    ["decide", pytest.approx(200e-9)],
                                    ["engine", pytest.approx(100e-9)]]


def test_readers(summary):
    record = {"trace": summary, "decisions": 2}
    read = {n: common.metric_reader(n).read(record) for n in (
        "device_ms_per_decision", "decide_host_ms", "device_idle_share",
        "device_idle_share.any_cell")}
    assert read["device_ms_per_decision"] == pytest.approx(150e-6)
    assert read["decide_host_ms"] == pytest.approx(130e-6)
    assert read["device_idle_share"] == pytest.approx(70.0)
    # a quantity split by cell falls back to the quantity's own reader
    assert read["device_idle_share.any_cell"] == pytest.approx(70.0)


def test_readers_find_nothing_without_a_device():
    for n in ("device_ms_per_decision", "decide_host_ms",
              "device_idle_share"):
        assert common.metric_reader(n).read({"decisions": 3}) is None
    assert trace.summarize({"devices": 0, "ops": [],
                            "spans": [["window", 0, 10]]}) == {}


def test_host_clock_readers():
    rec = {"seconds": 2.0, "decide_s": [1e-4 * k for k in range(1, 101)],
           "decisions": 100, "setup_s": 12.5, "compiles_in_window": 0}
    assert common.metric_reader("decide_p95_ms").read(rec) == \
        pytest.approx(9.5)
    assert common.metric_reader("decisions_per_s").read(rec) == 50.0
    assert common.metric_reader("engine_ms_per_decision").read(rec) == \
        pytest.approx(14.95)
    assert common.metric_reader("compiles_in_window").read(rec) == 0
    assert common.metric_reader("setup_s").read(rec) == 12.5


def test_load_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.decide"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    rec = trace.load(str(tmp_path))
    names = [s[0] for s in rec["spans"]]
    assert "window" in names and "decide" in names
    # the CPU backend has no device plane: nothing to call device time
    assert rec["devices"] == 0
