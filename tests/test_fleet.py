"""Cross-ward shared-cloud contention (DESIGN.md §9): the fleet-true
evaluator `simulate_fleet`, frozen background jobs in both search
backends, the fixed-point `scheduler.search_fleet`, the ward-aware online
hook, the `--wards` CLI path, and the ``python -O`` guard survival of the
ValueError conversions."""
import itertools
import os
import subprocess
import sys

import numpy as np
import pytest

from prop import sweep
from repro.core import online, scheduler, scheduler_jax
from repro.core.problems import metro_jobs
from repro.core.simulator import (MACHINES, JobSpec, ScheduleState,
                                  simulate, simulate_fleet)
from repro.core.tiers import CC, ED, ES


def _random_jobs(rng, n):
    return [JobSpec(name=f"J{i}", release=float(rng.integers(0, 30)),
                    weight=float(rng.integers(1, 4)),
                    proc={t: float(rng.integers(1, 30)) for t in MACHINES},
                    trans={CC: float(rng.integers(0, 60)),
                           ES: float(rng.integers(0, 15)), ED: 0.0})
            for i in range(n)]


def _random_plan(rng, wards):
    return [[MACHINES[int(rng.integers(3))] for _ in jobs]
            for jobs in wards]


# ------------------------------------------------------ fleet-true evaluator
class TestSimulateFleet:
    def test_fully_shared_fleet_equals_merged_instance(self):
        """With every shared tier pooled, the fleet evaluator IS the
        wards-concatenated single instance — same merged FIFO queues,
        same (arrival, release, ward, index) order, bit-identical sums."""
        def check(rng):
            B = int(rng.integers(1, 5))
            wards = [_random_jobs(rng, int(rng.integers(1, 10)))
                     for _ in range(B)]
            plan = _random_plan(rng, wards)
            mpt = {CC: int(rng.integers(1, 4)), ES: int(rng.integers(1, 4))}
            busy = ({CC: [float(rng.integers(0, 20))]}
                    if rng.integers(2) else None)
            fs = simulate_fleet(wards, plan, machines_per_tier=mpt,
                                busy_until=busy, shared_tiers=(CC, ES))
            merged = simulate([j for ws in wards for j in ws],
                              [a for ps in plan for a in ps],
                              machines_per_tier=mpt, busy_until=busy)
            assert fs.weighted_sum == merged.weighted_sum
            assert fs.unweighted_sum == merged.unweighted_sum
            assert fs.last_end == merged.last_end
        sweep(check, n_cases=15, seed=0)

    def test_single_ward_equals_simulate(self):
        """B = 1: shared-cloud pooling degenerates to plain simulate."""
        def check(rng):
            jobs = _random_jobs(rng, int(rng.integers(1, 12)))
            assign = [MACHINES[int(rng.integers(3))] for _ in jobs]
            mpt = {CC: 2, ES: 3}
            fs = simulate_fleet([jobs], [assign], machines_per_tier=mpt)
            ref = simulate(jobs, assign, machines_per_tier=mpt)
            assert fs.weighted_sum == ref.weighted_sum
            assert fs.wards[0].last_end == ref.last_end
        sweep(check, n_cases=10, seed=50)

    def test_per_ward_edge_pools_are_private(self):
        """Two wards all-edge: each queues only on its OWN edge pool, so
        per-ward results equal B independent simulations — while the same
        plan all-cloud shares one pool and must be slower than any single
        ward alone whenever queues overlap."""
        rng = np.random.default_rng(3)
        wards = [_random_jobs(rng, 6), _random_jobs(rng, 6)]
        edge_plan = [[ES] * 6, [ES] * 6]
        fs = simulate_fleet(wards, edge_plan,
                            machines_per_tier={CC: 1, ES: 1})
        for jobs, s in zip(wards, fs.wards):
            ref = simulate(jobs, [ES] * 6)
            assert s.weighted_sum == ref.weighted_sum
        cloud_plan = [[CC] * 6, [CC] * 6]
        fc = simulate_fleet(wards, cloud_plan,
                            machines_per_tier={CC: 1, ES: 1})
        solo = [simulate(jobs, [CC] * 6) for jobs in wards]
        assert fc.weighted_sum >= max(s.weighted_sum for s in solo)

    def test_contention_shows_double_booking(self):
        """The PR's headline: B independent per-ward evaluations claim
        objectives the shared cloud cannot deliver — the fleet-true score
        of the same plans is strictly worse."""
        rng = np.random.default_rng(7)
        wards = [metro_jobs(rng, n=10) for _ in range(4)]
        plan = [[CC] * 10 for _ in range(4)]
        claimed = sum(
            simulate(jobs, p, machines_per_tier={CC: 2, ES: 1}).weighted_sum
            for jobs, p in zip(wards, plan))
        fleet = simulate_fleet(wards, plan,
                               machines_per_tier={CC: 2, ES: 1})
        assert fleet.weighted_sum > claimed

    def test_input_validation(self):
        jobs = _random_jobs(np.random.default_rng(0), 3)
        with pytest.raises(ValueError):
            simulate_fleet([jobs], [])                  # ward count
        with pytest.raises(ValueError):
            simulate_fleet([jobs], [[CC, ES]])          # length mismatch
        with pytest.raises(ValueError):
            simulate_fleet([jobs], [[CC] * 3], shared_tiers=(ED,))
        with pytest.raises(ValueError):                 # pool size dispute
            simulate_fleet([jobs, jobs], [[CC] * 3] * 2,
                           machines_per_tier=[{CC: 1}, {CC: 2}])

    def test_exact_joint_optimum_two_wards(self):
        """2 wards x 3 jobs on a fully shared fleet: brute-forcing joint
        assignments through simulate_fleet reaches exactly the
        exact_optimum of the merged instance — and search_fleet lands
        between that optimum and the naive fleet-true score."""
        rng = np.random.default_rng(11)
        wards = [metro_jobs(rng, n=3), metro_jobs(rng, n=3)]
        mpt = {CC: 1, ES: 1}
        best = float("inf")
        for combo in itertools.product(MACHINES, repeat=6):
            fs = simulate_fleet(wards, [combo[:3], combo[3:]],
                                machines_per_tier=mpt,
                                shared_tiers=(CC, ES))
            best = min(best, fs.weighted_sum)
        merged_opt = scheduler.exact_optimum(
            [j for ws in wards for j in ws], machines_per_tier=mpt)
        assert best == merged_opt.weighted_sum
        plan = scheduler.search_fleet(wards, machines_per_tier=mpt,
                                      shared_tiers=(CC, ES),
                                      sweep_backend="python")
        assert plan.fleet.weighted_sum >= best - 1e-9
        assert plan.fleet.weighted_sum <= \
            plan.naive_fleet.weighted_sum + 1e-9


# ------------------------------------------------------- frozen background
class TestFrozenJobs:
    def test_frozen_never_move_and_score_exactly(self):
        """Both backends: frozen jobs stay pinned, and the reported value
        is the exact simulator's on the full (frozen-included) instance."""
        def check(rng):
            jobs = _random_jobs(rng, 9)
            frozen = [bool(rng.integers(2)) for _ in jobs]
            init = [int(rng.integers(3)) if f else 2
                    for f, _ in zip(frozen, jobs)]
            (v,), (a,) = scheduler_jax.tabu_search_batched(
                [jobs], [init], frozen=[frozen])
            for i, f in enumerate(frozen):
                if f:
                    assert int(a[i]) == init[i]
            exact = simulate(jobs, [MACHINES[int(i)] for i in a])
            assert abs(v - exact.weighted_sum) < 1e-3
            # python path: same pinning contract
            sched = scheduler.neighborhood_search(
                jobs, initial=[MACHINES[i] for i in init], frozen=frozen)
            for i, f in enumerate(frozen):
                if f:
                    assert sched.assignment()[i] == MACHINES[init[i]]
        sweep(check, n_cases=8, seed=100)

    def test_frozen_requires_initial(self):
        jobs = _random_jobs(np.random.default_rng(1), 4)
        with pytest.raises(ValueError):
            scheduler_jax.tabu_search_batched([jobs], frozen=[[True] * 4])
        with pytest.raises(ValueError):
            scheduler.neighborhood_search(jobs, frozen=[True] * 4)
        with pytest.raises(ValueError):
            scheduler.search(jobs, frozen=[True] * 4, jax_threshold=1)

    def test_frozen_background_occupies_the_queue(self):
        """A frozen cloud job ahead in the FIFO queue must delay the
        movable job's cloud option — the search sees the contention."""
        mk = lambda name, rel: JobSpec(
            name=name, release=rel, weight=1.0,
            proc={CC: 10.0, ES: 50.0, ED: 50.0},
            trans={CC: 0.0, ES: 0.0, ED: 0.0})
        jobs = [mk("movable", 1.0), mk("bg", 0.0)]
        sched = scheduler.neighborhood_search(
            jobs, initial=[CC, CC], frozen=[False, True])
        entry = sched.entries[0]
        # bg holds the single cloud machine 0-10, so cloud would finish at
        # 20 (response 19); the search must route the movable job away
        assert entry.machine != CC or entry.start >= 10.0

    def test_pad_to_is_inert(self):
        jobs = _random_jobs(np.random.default_rng(5), 7)
        v1, a1 = scheduler_jax.tabu_search_batched([jobs])
        v2, a2 = scheduler_jax.tabu_search_batched([jobs], pad_to=32)
        assert v1[0] == v2[0] and list(a1[0]) == list(a2[0])


# ------------------------------------------------- fixed-point fleet search
class TestSearchFleet:
    MPT = {CC: 2, ES: 1}

    def _wards(self, seed, B=4, n=8):
        rng = np.random.default_rng(seed)
        return [metro_jobs(rng, n=n) for _ in range(B)]

    @pytest.mark.parametrize("backend", ["python", "batched"])
    def test_monotone_and_gap(self, backend):
        """The fixed-point search never worsens the fleet-true objective,
        and on a cloud-attractive fleet it strictly improves it."""
        wards = self._wards(21, B=4, n=8)
        plan = scheduler.search_fleet(
            wards, machines_per_tier=self.MPT, sweep_backend=backend,
            pad_bucket=16)
        assert plan.fleet.weighted_sum <= \
            plan.naive_fleet.weighted_sum + 1e-9
        # naive fleet-true can never beat what the wards claimed
        assert plan.naive_fleet.weighted_sum >= plan.naive_reported - 1e-6
        assert plan.sweeps >= 1
        # the returned evaluation matches a fresh fleet-true rescore
        fresh = simulate_fleet(wards, plan.assignments,
                               machines_per_tier=self.MPT)
        assert fresh.weighted_sum == plan.fleet.weighted_sum

    def test_contention_gap_closes_on_overcommitted_fleet(self):
        """B wards of cloud-heavy jobs on a small shared pool: the naive
        plans must overcommit (gap > 1) and the sweeps must recover a
        strictly better fleet-true plan."""
        wards = self._wards(33, B=5, n=10)
        plan = scheduler.search_fleet(wards, machines_per_tier=self.MPT,
                                      sweep_backend="python")
        assert plan.contention_gap > 1.0
        assert plan.fleet.weighted_sum < plan.naive_fleet.weighted_sum
        assert 0.0 < plan.gap_closed <= 1.0

    def test_independent_mode_untouched(self):
        """search_fleet's naive stage IS search_batched — per-ward
        assignments identical to calling it directly (the PR-3 batched
        path stays bit-identical)."""
        wards = self._wards(8, B=4, n=8)
        plan = scheduler.search_fleet(wards, machines_per_tier=self.MPT,
                                      max_sweeps=0)
        direct = scheduler.search_batched(
            wards, machines_per_tier=self.MPT)
        assert plan.naive_assignments == [s.assignment() for s in direct]
        assert plan.sweeps == 0

    def test_empty_fleet(self):
        plan = scheduler.search_fleet([], machines_per_tier=self.MPT)
        assert plan.assignments == [] and plan.sweeps == 0


# -------------------------------------------------- ward-aware online hook
class TestOnlineFleet:
    def test_single_ward_is_plain_tabu_online(self):
        """B = 1 has an empty background at every event, so the hook IS
        online_schedule(replan='tabu') — identical commits."""
        def check(rng):
            jobs = metro_jobs(rng, n=8)
            mpt = {CC: 2, ES: 1}
            solo = online.online_schedule(jobs, replan="tabu",
                                          machines_per_tier=mpt)
            fleet = online.online_schedule_fleet(
                [jobs], machines_per_tier=mpt)[0]
            assert solo.weighted_sum == fleet.weighted_sum
            assert solo.last_end == fleet.last_end
        sweep(check, n_cases=6, seed=200)

    def test_no_cloud_double_booking(self):
        """At no instant do more cloud jobs run than the shared pool has
        machines — the property the per-ward-independent online mode
        cannot guarantee."""
        rng = np.random.default_rng(9)
        wards = [metro_jobs(rng, n=8) for _ in range(4)]
        mpt = {CC: 2, ES: 1}
        scheds = online.online_schedule_fleet(wards,
                                              machines_per_tier=mpt)
        assert len(scheds) == 4
        cloud = [(e.start, e.end) for s in scheds for e in s.entries
                 if e.machine == CC]
        for t in sorted({t for se in cloud for t in se}):
            running = sum(1 for s, e in cloud if s <= t < e)
            assert running <= mpt[CC], (t, running)
        for jobs, s in zip(wards, scheds):
            assert len(s.entries) == len(jobs)
            assert all(e.start >= e.job.release for e in s.entries)


# --------------------------------------------------------- CLI / serve path
@pytest.mark.slow
class TestRunWards:
    def test_run_wards_smoke(self):
        from repro.launch import serve
        schedules, seconds = serve.run_wards(
            wards=2, patients=3, horizon=10.0, seed=1, verbose=False)
        assert len(schedules) == 2
        for s in schedules:
            assert len(s.entries) == 3
            assert all(e.machine in (CC, ES, ED) for e in s.entries)
        assert seconds > 0

    def test_run_wards_contention_smoke(self):
        from repro.launch import serve
        schedules, seconds, plan = serve.run_wards(
            wards=2, patients=3, horizon=10.0, seed=1, verbose=False,
            contention=True)
        assert len(schedules) == 2
        assert plan.fleet.weighted_sum <= \
            plan.naive_fleet.weighted_sum + 1e-9
        assert plan.contention_gap >= 1.0 - 1e-9

    def test_explicit_zero_quantum_rejected(self):
        from repro.launch import serve
        with pytest.raises(ValueError):
            serve.run_wards(wards=2, patients=2, horizon=5.0,
                            quantum=0.0, verbose=False)
        with pytest.raises(ValueError):
            serve.run(patients=2, horizon=5.0, quantum=0.0,
                      verbose=False, execute=False)


# ------------------------------------------------------- python -O survival
@pytest.mark.slow
def test_guards_survive_python_O():
    """The length/size guards converted from assert must still raise
    under ``python -O`` (which strips asserts) — core scheduling plus
    every module the R001 reprolint sweep converted (kernels, models,
    configs, sharding, launch; DESIGN.md §14)."""
    code = """
import dataclasses
import sys
sys.path.insert(0, sys.argv[1])
import jax.numpy as jnp
from repro.configs import get_config
from repro.core import scheduler
from repro.core.simulator import JobSpec, ScheduleState, simulate
from repro.core.tiers import CC, ED, ES
from repro.kernels import (flash_attention, lstm_cell, mlstm_chunk, ref,
                           ssm_scan)
from repro.launch import dryrun
from repro.models.encdec import EncDecModel
from repro.sharding import ep_moe, policy
assert not __debug__, "run me with -O"
job = JobSpec(name="J", release=0.0, weight=1.0,
              proc={CC: 1.0, ES: 1.0, ED: 1.0},
              trans={CC: 0.0, ES: 0.0, ED: 0.0})
cfg = get_config("qwen2-1.5b")
z = jnp.zeros
for fn in (lambda: simulate([job], []),
           lambda: ScheduleState([job], []),
           lambda: simulate([job], ["moon"]),
           lambda: scheduler.exact_optimum([job] * 13),
           # converted R001 guards (group pattern / enc-dec / wx shape)
           lambda: dataclasses.replace(cfg, num_groups=cfg.num_layers + 1),
           lambda: EncDecModel(cfg),
           lambda: lstm_cell.lstm_cell(z((4, 8)), z((4, 8)), z((4, 8)),
                                       z((8, 3, 8)), z((8, 4, 8)),
                                       z((4, 8)))):
    try:
        fn()
    except ValueError:
        pass
    else:
        raise SystemExit(f"guard vanished under -O: {fn}")
print("guards ok")
"""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    out = subprocess.run([sys.executable, "-O", "-c", code, src],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "guards ok" in out.stdout


# --------------------------------------------- contention regression gate
class TestContentionGate:
    """check_regression.py contention logic (no bench run)."""

    def _compare(self):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks"))
        try:
            from check_regression import compare
        finally:
            sys.path.pop(0)
        return compare

    def _reports(self):
        base = {"contention": {
            "contention_gap": 1.5, "gap_closed": 0.9,
            "improvement_vs_naive": 1.4, "wards_per_s": 2.0,
            "naive_fleet_true": 3000.0, "fleet_true": 2100.0}}
        import copy
        return base, copy.deepcopy(base)

    def test_identical_passes(self):
        compare = self._compare()
        committed, fresh = self._reports()
        assert compare(committed, fresh) == []

    def test_throughput_regression_fails(self):
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention"]["wards_per_s"] = 0.5          # -75%
        assert any("wards_per_s" in p for p in compare(committed, fresh))

    def test_gap_closed_regression_fails(self):
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention"]["gap_closed"] = 0.3           # -66%
        assert any("gap_closed" in p for p in compare(committed, fresh))

    def test_vanished_gap_fails(self):
        """If the benchmark fleet stops double-booking, the bench no
        longer measures contention — hard failure, not a perf floor."""
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention"]["contention_gap"] = 1.0
        assert any("contention_gap" in p for p in compare(committed, fresh))

    def test_no_strict_improvement_fails(self):
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention"]["fleet_true"] = 3000.0        # == naive
        assert any("strictly beat" in p for p in compare(committed, fresh))

    def test_missing_section_is_not_gated(self):
        """Old reports without a contention section still pass (the gate
        tightens with the baseline, never blocks on new sections)."""
        compare = self._compare()
        committed, _ = self._reports()
        assert compare(committed, {"contention": {}}) == []


# ------------------------------------ interval contention regression gate
class TestContentionIntervalGate:
    """check_regression.py §12 interval-path logic (no bench run)."""

    def _compare(self):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks"))
        try:
            from check_regression import compare
        finally:
            sys.path.pop(0)
        return compare

    def _reports(self):
        base = {"contention_interval": {
            "gap_closed": 0.95, "improvement_vs_naive": 10.0,
            "wards_per_s": 5.0, "fraction_of_batched": 0.03,
            "parity_with_phantom": True,
            "compiled_programs": 0}}
        import copy
        return base, copy.deepcopy(base)

    def test_identical_passes(self):
        compare = self._compare()
        committed, fresh = self._reports()
        assert compare(committed, fresh) == []

    def test_throughput_regression_fails(self):
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention_interval"]["wards_per_s"] = 1.0  # -80%
        assert any("contention_interval/wards_per_s" in p
                   for p in compare(committed, fresh))

    def test_batched_ratio_regression_fails(self):
        """fraction_of_batched is the committed "fleet sweeps at §8
        batched speeds" claim — falling far behind the independent
        batched floor fails even if absolute wards/s still passes."""
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention_interval"]["fraction_of_batched"] = 0.001
        assert any("fraction_of_batched" in p
                   for p in compare(committed, fresh))

    def test_parity_break_fails(self):
        """parity_with_phantom is a hard invariant: tolerance never
        excuses the interval background diverging from the oracle."""
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention_interval"]["parity_with_phantom"] = False
        assert any("parity_with_phantom" in p
                   for p in compare(committed, fresh, tolerance=0.99))

    def test_compile_growth_fails(self):
        """More programs compiled than the committed run is a hard
        failure: tolerance never excuses shape churn."""
        compare = self._compare()
        committed, fresh = self._reports()
        fresh["contention_interval"]["compiled_programs"] = 1
        assert any("compiled_programs" in p
                   for p in compare(committed, fresh, tolerance=0.99))

    def test_missing_section_is_not_gated(self):
        compare = self._compare()
        committed, _ = self._reports()
        assert compare(committed, {}) == []
