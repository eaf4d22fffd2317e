"""Reprolint + metro-sanitizer tests (DESIGN.md §14).

Three layers:

  * rule fixtures — a positive and a negative snippet per rule
    (R001–R006), linted from tmp files so path-scoped rules (R002) see
    realistic repo-relative paths;
  * the linter contract — suppression comments, the CLI's exit codes
    and JSON report, and the acceptance bar that the repo's own `src`
    tree lints clean;
  * the sanitizer — direct violation injections (double-booking, FIFO
    inversion, mutated started job, double hedge, double terminal,
    missing terminal, capacity overdraw) plus the zero-perturbation
    contract: sanitize=True runs produce bit-identical event-log CRCs.
"""
import copy
import json
import os
import subprocess
import sys
import textwrap
import zlib
from pathlib import Path

import pytest

from repro.analysis import ALL_RULES, RULES_BY_ID, lint_paths
from repro.core.simulator import JobSpec
from repro.core.tiers import CC, ED, ES
from repro.metro import traces
from repro.metro.engine import MetroEngine, _Commit, simulate_metro
from repro.metro.policies import GreedyPolicy, HedgingPolicy, TabuPolicy
from repro.metro.sanitizer import MetroSanitizer, SanitizerViolation

from prop import random_fleet_events, sweep

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"
MPT = {CC: 2, ES: 2}


# ===================================================================
# linter fixtures
# ===================================================================

def lint_snippet(tmp_path, code, name="mod.py"):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return lint_paths([f], ALL_RULES, root=tmp_path)


def rule_ids(findings):
    return [f.rule for f in findings]


class TestRuleFixtures:
    def test_r001_flags_bare_assert(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            def f(x):
                assert x > 0, "positive"
                return x
        """)
        assert rule_ids(fs) == ["R001"] and fs[0].line == 3

    def test_r001_negative_raise_guard(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            def f(x):
                if not x > 0:
                    raise ValueError(f"need positive, got {x}")
                return x
        """)
        assert fs == []

    def test_r002_flags_wall_clock_in_metro(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import time
            def step(now):
                t0 = time.time()
                return now + time.perf_counter() - t0
        """, name="metro/engine.py")
        assert rule_ids(fs) == ["R002", "R002"]

    def test_r002_resolves_import_aliases(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            from time import monotonic
            from datetime import datetime as dt
            def step():
                return monotonic(), dt.now()
        """, name="core/sim.py")
        assert rule_ids(fs) == ["R002", "R002"]

    def test_r002_scoped_to_simulation_dirs(self, tmp_path):
        # same wall-clock read outside metro/ / core/ is allowed —
        # launchers and benchmarks legitimately measure wall time
        fs = lint_snippet(tmp_path, """
            import time
            def bench():
                return time.perf_counter()
        """, name="launch/bench.py")
        assert fs == []

    def test_r003_flags_global_state_rng(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import random
            import numpy as np
            def draw():
                a = np.random.rand(3)
                b = random.choice([1, 2])
                rng = np.random.default_rng()
                return a, b, rng
        """)
        assert rule_ids(fs) == ["R003", "R003", "R003"]

    def test_r003_negative_seeded_generator(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import numpy as np
            def draw(seed):
                rng = np.random.default_rng(seed)
                return rng.uniform(), np.random.SeedSequence(seed)
        """)
        assert fs == []

    def test_r004_flags_order_revealing_set_iteration(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            def emit(names, heap):
                for n in set(names):
                    heap.append(n)
                order = list({"a", "b"} | set(names))
                pairs = [(n, 1) for n in frozenset(names)]
                return order, pairs
        """)
        assert rule_ids(fs) == ["R004", "R004", "R004"]

    def test_r004_negative_sorted_and_reductions(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            def emit(names, heap):
                for n in sorted(set(names)):
                    heap.append(n)
                return len(set(names)), max({1, 2}), "a" in set(names)
        """)
        assert fs == []

    def test_r005_flags_python_branch_on_traced_arg(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return float(x)
        """)
        assert rule_ids(fs) == ["R005", "R005"]

    def test_r005_negative_static_argnames_and_metadata(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("mode",))
            def f(x, mode):
                if mode == "fast":
                    return x
                if x.ndim > 2 or len(x) == 0:
                    return x
                return x * x.shape[0]
        """)
        assert fs == []

    def test_r005_sees_pallas_kernel_bodies(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import functools
            from jax.experimental import pallas as pl

            def kern(x_ref, o_ref, scale):
                if scale > 1.0:
                    o_ref[...] = x_ref[...] * scale
                v = x_ref[...].item()

            def call(x):
                return pl.pallas_call(functools.partial(kern, scale=2.0),
                                      out_shape=x)(x)
        """)
        # partial-bound `scale` is static (the If is fine); `.item()`
        # on a traced Ref value is not
        assert rule_ids(fs) == ["R005"]
        assert ".item()" in fs[0].message

    def test_r006_flags_immediate_jit_invocation(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax
            def step(f, x):
                return jax.jit(f)(x)
        """)
        assert rule_ids(fs) == ["R006"]

    def test_r006_negative_aot_lower_and_hoisted_jit(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import jax
            def compile_once(f, spec):
                return jax.jit(f).lower(spec)
            _step = None
            def step(f, x):
                global _step
                if _step is None:
                    _step = jax.jit(f)
                return _step(x)
        """)
        assert fs == []

    def test_r006_flags_raw_kernel_call_outside_dispatcher(self, tmp_path):
        code = """
            from repro.core.scheduler_jax import tabu_search_batched
            def plan(jobs):
                return tabu_search_batched([jobs])
        """
        assert rule_ids(lint_snippet(
            tmp_path, code, name="metro/policies.py")) == ["R006"]
        # ... but the dispatcher module itself owns those calls
        assert lint_snippet(tmp_path, code,
                            name="core/scheduler.py") == []

    def test_syntax_error_reports_e000(self, tmp_path):
        fs = lint_snippet(tmp_path, "def f(:\n    pass\n")
        assert rule_ids(fs) == ["E000"]


class TestSuppression:
    def test_trailing_comment_suppresses_one_rule(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            def f(x):
                assert x  # reprolint: disable=R001
                assert x
        """)
        assert [(f.rule, f.line) for f in fs] == [("R001", 4)]

    def test_comment_line_covers_line_below(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import time
            def step():
                # reprolint: disable=R002
                return time.time()
        """, name="metro/x.py")
        assert fs == []

    def test_bare_disable_suppresses_all_rules(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            import time
            def step(x):
                assert x and time.time()  # reprolint: disable
        """, name="metro/x.py")
        assert fs == []

    def test_mismatched_rule_id_does_not_suppress(self, tmp_path):
        fs = lint_snippet(tmp_path, """
            def f(x):
                assert x  # reprolint: disable=R002
        """)
        assert rule_ids(fs) == ["R001"]


class TestCLI:
    def _run(self, *argv, cwd=None):
        env = dict(os.environ, PYTHONPATH=str(SRC))
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis", *argv],
            capture_output=True, text=True, env=env, cwd=cwd or REPO)

    def test_exit_1_and_json_report_on_findings(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def f(x):\n    assert x\n")
        out = self._run(str(bad), "--format", "json")
        assert out.returncode == 1, out.stderr
        report = json.loads(out.stdout)
        assert report["counts"] == {"R001": 1}
        (f,) = report["findings"]
        assert f["rule"] == "R001" and f["line"] == 2

    def test_exit_0_on_clean_tree(self, tmp_path):
        (tmp_path / "ok.py").write_text(
            "def f(x):\n"
            "    if not x:\n"
            "        raise ValueError('x')\n"
            "    return x\n")
        out = self._run(str(tmp_path))
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 finding(s)" in out.stdout

    def test_rule_subset_and_output_file(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import time\nassert time.time()\n")
        rpt = tmp_path / "report.json"
        out = self._run(str(bad), "--rules", "R001",
                        "--output", str(rpt))
        assert out.returncode == 1
        report = json.loads(rpt.read_text())
        assert report["rules"] == ["R001"]
        assert [f["rule"] for f in report["findings"]] == ["R001"]

    def test_unknown_rule_exits_2(self, tmp_path):
        out = self._run(str(tmp_path), "--rules", "R999")
        assert out.returncode == 2
        assert "R999" in out.stderr

    def test_list_rules(self):
        out = self._run("--list-rules")
        assert out.returncode == 0
        for rid in RULES_BY_ID:
            assert rid in out.stdout


def test_repo_src_tree_lints_clean():
    """The acceptance bar: `python -m repro.analysis src` exits 0."""
    findings = lint_paths([SRC], ALL_RULES, root=REPO)
    assert findings == [], "\n".join(f.human() for f in findings)


# ===================================================================
# sanitizer: violation injections
# ===================================================================

def _cloud_job(name, release, proc_c, deadline=float("inf")):
    return JobSpec(name=name, release=release, weight=1.0,
                   proc={CC: proc_c, ES: 500.0, ED: 500.0},
                   trans={CC: 0.0, ES: 0.0, ED: 0.0}, deadline=deadline)


def _engine_with_commits(*commit_specs):
    """An un-run engine with hand-planted cloud commitments, plus its
    sanitizer. commit_specs: (arrival, start, end, slot)."""
    jobs = [_cloud_job(f"J{i}", 0.0, 1.0)
            for i in range(len(commit_specs))]
    eng = MetroEngine([jobs], GreedyPolicy(), machines_per_tier=MPT)
    for i, (arr, start, end, slot) in enumerate(commit_specs):
        eng.commits[0][i] = _Commit(job=jobs[i], machine=CC, arrival=arr,
                                    start=start, end=end, slot=slot,
                                    planned_at=0.0)
    return eng, MetroSanitizer(eng)


class TestSanitizerInjections:
    def test_double_booking_detected(self):
        # two started attempts overlap on cloud slot 0
        eng, san = _engine_with_commits(
            (0.0, 0.0, 10.0, 0), (0.0, 5.0, 15.0, 0))
        with pytest.raises(SanitizerViolation, match="I2-overlap"):
            san.check_pool(eng.cloud, 100.0)

    def test_clean_pool_passes(self):
        eng, san = _engine_with_commits(
            (0.0, 0.0, 10.0, 0), (0.0, 10.0, 20.0, 0))
        san.check_pool(eng.cloud, 100.0)
        assert san.checks == 1

    def test_fifo_inversion_detected(self):
        # job 0 arrived first (t=1) yet starts AFTER job 1 (arrived t=2)
        eng, san = _engine_with_commits(
            (1.0, 20.0, 21.0, 0), (2.0, 15.0, 16.0, 1))
        with pytest.raises(SanitizerViolation, match="I1-fifo"):
            san.check_pool(eng.cloud, 0.0)

    def test_mutated_started_job_detected(self):
        # C2: a started attempt's (machine, slot, start) may never move
        eng, san = _engine_with_commits((0.0, 0.0, 10.0, 0))
        san.check_pool(eng.cloud, 5.0)           # snapshot
        eng.commits[0][0].start = 2.0            # illegal re-timing
        with pytest.raises(SanitizerViolation, match="I3-immutable"):
            san.check_pool(eng.cloud, 5.0)

    def test_end_stretch_is_legal(self):
        # fail-slow re-timing stretches END only — not a C2 violation
        eng, san = _engine_with_commits((0.0, 0.0, 10.0, 0))
        san.check_pool(eng.cloud, 5.0)
        eng.commits[0][0].end = 14.0
        san.check_pool(eng.cloud, 5.0)

    def test_inverted_interval_detected(self):
        eng, san = _engine_with_commits((0.0, 10.0, 4.0, 0))
        with pytest.raises(SanitizerViolation, match="I2-interval"):
            san.check_pool(eng.cloud, 100.0)

    def test_slot_out_of_range_detected(self):
        eng, san = _engine_with_commits((0.0, 0.0, 10.0, 7))
        with pytest.raises(SanitizerViolation, match="I2-slot"):
            san.check_pool(eng.cloud, 100.0)

    def test_event_time_regression_detected(self):
        eng, san = _engine_with_commits((0.0, 0.0, 1.0, 0))
        san.on_event(5.0, ("arrive", 0, 0))
        with pytest.raises(SanitizerViolation, match="I4-monotonic"):
            san.on_event(3.0, ("arrive", 0, 1))

    def test_double_hedge_detected(self):
        eng, san = _engine_with_commits((0.0, 0.0, 1.0, 0))
        san.on_hedge(0, 0)
        with pytest.raises(SanitizerViolation, match="I5-single-hedge"):
            san.on_hedge(0, 0)

    def test_double_terminal_detected(self):
        eng, san = _engine_with_commits((0.0, 0.0, 1.0, 0))
        san.on_terminal(0, 0, "complete")
        with pytest.raises(SanitizerViolation, match="I6-terminal"):
            san.on_terminal(0, 0, "shed")

    def test_missing_terminal_detected_at_exit(self):
        eng, san = _engine_with_commits((0.0, 0.0, 1.0, 0))
        with pytest.raises(SanitizerViolation, match="I6-terminal"):
            san.at_exit(10.0)

    def test_capacity_overdraw_detected_at_exit(self):
        eng, san = _engine_with_commits((0.0, 0.0, 1.0, 0))
        san.on_terminal(0, 0, "complete")
        eng._t_end = 10.0
        eng.metrics.busy_time[CC] = 1e9   # more service than exists
        with pytest.raises(SanitizerViolation, match="I7-capacity"):
            san.at_exit(10.0)


# ===================================================================
# sanitizer: zero-perturbation CRC contract
# ===================================================================

def _crc(res):
    return zlib.crc32(repr(res.event_log).encode())


def _pack_kwargs(sc):
    return dict(machines_per_tier=MPT, failures=sc.failures,
                scale_events=sc.scales, network_events=sc.network,
                slowdowns=sc.slowdowns)


def test_sanitized_run_is_bit_identical_fast():
    sc = traces.make_scenario("default", seed=3, wards=2, horizon=12.0)
    base = simulate_metro(sc.traces, GreedyPolicy(), **_pack_kwargs(sc))
    san = simulate_metro(sc.traces, GreedyPolicy(), **_pack_kwargs(sc),
                         sanitize=True)
    assert san.event_log == base.event_log
    assert _crc(san) == _crc(base)


@pytest.mark.slow
@pytest.mark.parametrize("pack", sorted(traces.SCENARIO_PACKS))
def test_all_packs_sanitize_clean_with_identical_crc(pack):
    """Acceptance: every chaos pack runs sanitize=True without a
    violation and with a bit-identical event-log CRC — hedged execution
    included for the fail-slow pack (DESIGN.md §14)."""
    sc = traces.make_scenario(pack, seed=0)

    def run(sanitize):
        kw = _pack_kwargs(sc)
        if pack == "fail_slow_tail":
            pol = HedgingPolicy(inner=TabuPolicy(jax_threshold=10 ** 9),
                                min_gain=1.0)
            kw.update(hedge_factor=1.3, retry_backoff=1.0,
                      max_attempts=3)
        else:
            pol = TabuPolicy(jax_threshold=10 ** 9)
        return simulate_metro(sc.traces, pol, **kw, sanitize=sanitize)

    base, san = run(False), run(True)
    assert san.event_log == base.event_log, pack
    assert _crc(san) == _crc(base)
    assert san.metrics.finished == sc.jobs


@pytest.mark.slow
def test_fuzzed_fleet_events_run_clean_under_sanitizer():
    """Random crash/slowdown/scale/network interleavings never trip an
    invariant, and sanitized runs replay bit-identically."""
    def policies():
        return (GreedyPolicy(),
                TabuPolicy(jax_threshold=10 ** 9),
                HedgingPolicy(inner=TabuPolicy(jax_threshold=10 ** 9),
                              min_gain=1.0))

    def check(rng):
        horizon, wards = 30.0, 2
        tr = traces.metro_traces(rng, wards, horizon, base_rate=0.15)
        if not any(tr):
            return
        events = random_fleet_events(rng, horizon, wards)
        for make in policies():
            runs = []
            for sanitize in (False, True):
                pol = copy.deepcopy(make)
                kw = {"hedge_factor": 1.3} \
                    if hasattr(pol, "hedge") else {}
                eng = MetroEngine(tr, pol, machines_per_tier=MPT,
                                  max_attempts=3, retry_backoff=1.0,
                                  **events, **kw)
                runs.append(eng.run(sanitize=sanitize))
            base, san = runs
            assert base.event_log == san.event_log, make.name

    sweep(check, n_cases=6, seed=11)
