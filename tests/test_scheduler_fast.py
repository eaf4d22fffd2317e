"""Fast-path scheduler tests: incremental ScheduleState invariants, the
rescore-after-move (float drift) regression, the randomized Python/JAX
parity suite (>= 200 instances), and the size-dispatched search."""
import zlib
from dataclasses import replace

import numpy as np
import pytest

from repro.core import scheduler, scheduler_jax
from repro.core.problems import table6_jobs
from repro.core.simulator import (MACHINES, JobSpec, ScheduleState, simulate)
from repro.core.tiers import CC, ED, ES


def _random_jobs(rng, n, *, tie_heavy=False):
    """tie_heavy: tiny release/transmission ranges force many simultaneous
    arrivals, exercising the (arrival, release, index) FIFO tiebreak."""
    rel_hi, tc_hi, te_hi = (3, 2, 2) if tie_heavy else (30, 60, 15)
    return [JobSpec(name=f"J{i}", release=float(rng.integers(0, rel_hi)),
                    weight=float(rng.integers(1, 4)),
                    proc={t: float(rng.integers(1, 30)) for t in MACHINES},
                    trans={CC: float(rng.integers(0, tc_hi)),
                           ES: float(rng.integers(0, te_hi)), ED: 0.0})
            for i in range(n)]


# --------------------------------------------- incremental state invariants
class TestScheduleState:
    def test_matches_simulate_under_random_move_sequences(self):
        for seed in range(40):
            rng = np.random.default_rng(seed)
            n = int(rng.integers(3, 12))
            jobs = _random_jobs(rng, n)
            mpt = {CC: int(rng.integers(1, 3)), ES: int(rng.integers(1, 3))}
            assign = [MACHINES[j] for j in rng.integers(0, 3, n)]
            st = ScheduleState(jobs, assign, machines_per_tier=mpt)
            for _ in range(15):
                k = int(rng.integers(0, n))
                dst = MACHINES[int(rng.integers(0, 3))]
                pred = {o: st.try_move(k, dst, o)
                        for o in ("weighted", "unweighted", "last")}
                st.apply_move(k, dst)
                ref = simulate(jobs, st.assign, machines_per_tier=mpt)
                assert abs(pred["weighted"] - ref.weighted_sum) < 1e-6
                assert abs(pred["unweighted"] - ref.unweighted_sum) < 1e-6
                assert abs(pred["last"] - ref.last_end) < 1e-6
                assert abs(st.score("weighted") - ref.weighted_sum) < 1e-9
                for e in ref.entries:
                    i = jobs.index(e.job)
                    assert abs(st.end[i] - e.end) < 1e-9

    def test_noop_move_is_identity(self):
        jobs = table6_jobs()
        st = ScheduleState(jobs, ["cloud"] * len(jobs))
        before = st.score()
        assert st.try_move(0, st.assign[0]) == before
        st.apply_move(0, st.assign[0])
        assert st.score() == before


# ------------------------------------------------- rescore-after-move fix
class TestDriftRegression:
    def test_pinned_objective_on_paper_instance(self):
        s = scheduler.neighborhood_search(table6_jobs())
        assert s.weighted_sum == 228.0
        assert s.unweighted_sum == 150.0
        assert s.last_end == 43.0

    def test_pinned_objective_on_fractional_instance(self):
        """Fixed instance with 0.1-step times (not exactly representable in
        binary): the seed `best -= v_max` accumulator drifts on these; the
        rescore-after-move search must report the exact re-simulated
        objective, pinned here."""
        rng = np.random.default_rng(123)
        jobs = [JobSpec(
            name=f"F{i}", release=float(rng.integers(0, 30)) * 0.1,
            weight=float(rng.integers(1, 4)) * 0.3,
            proc={t: float(rng.integers(1, 30)) * 0.1 for t in MACHINES},
            trans={CC: float(rng.integers(0, 60)) * 0.1,
                   ES: float(rng.integers(0, 15)) * 0.1, ED: 0.0})
            for i in range(12)]
        s = scheduler.neighborhood_search(jobs)
        assert s.weighted_sum == 8.25
        # the reported objective IS the exact re-simulation of the final
        # assignment — bit-for-bit, no accumulated error
        assert s.weighted_sum == simulate(jobs, s.assignment()).weighted_sum

    def test_incremental_matches_reference_on_integer_instances(self):
        """On integer instances float arithmetic is exact, so the seed
        reference and the incremental search must agree exactly."""
        for seed in range(60):
            rng = np.random.default_rng(seed)
            jobs = _random_jobs(rng, int(rng.integers(3, 12)))
            a = scheduler.neighborhood_search(jobs)
            b = scheduler.neighborhood_search_reference(jobs)
            assert a.weighted_sum == b.weighted_sum, seed


# --------------------------------------------------- Python vs JAX parity
class TestEvaluatorParity:
    """simulate == evaluate_assignments over >= 200 random instances,
    including multi-machine tiers and simultaneous-arrival ties. Instance
    shapes are drawn from a fixed grid so jit caches stay warm."""

    GRID = [  # (n, (cloud_machines, edge_machines), tie_heavy, cases)
        (6, (1, 1), False, 40),
        (6, (2, 1), False, 30),
        (6, (1, 3), True, 30),
        (10, (1, 1), True, 40),
        (10, (2, 2), False, 30),
        (10, (3, 2), True, 40),
    ]

    @pytest.mark.parametrize("n,mpt,tie_heavy,cases", GRID)
    def test_parity(self, n, mpt, tie_heavy, cases):
        for case in range(cases):
            rng = np.random.default_rng(hash((n, mpt, tie_heavy)) %
                                        (2 ** 31) + case)
            jobs = _random_jobs(rng, n, tie_heavy=tie_heavy)
            assigns = rng.integers(0, 3, size=(8, n)).astype(np.int32)
            rel, w, proc, trans = scheduler_jax.specs_to_arrays(jobs)
            m = scheduler_jax.evaluate_assignments(
                assigns, rel, w, proc, trans, machines_per_tier=mpt)
            for ai in range(8):
                s = simulate(jobs, [MACHINES[j] for j in assigns[ai]],
                             machines_per_tier={CC: mpt[0], ES: mpt[1]})
                assert abs(float(m["weighted"][ai]) - s.weighted_sum) < 1e-3
                assert abs(float(m["unweighted"][ai])
                           - s.unweighted_sum) < 1e-3
                assert abs(float(m["last"][ai]) - s.last_end) < 1e-3

    def test_deterministic_tie_break(self):
        """Three jobs arriving at the same instant on the same machine run
        in (release, index) order in both evaluators."""
        jobs = [
            JobSpec(name="A", release=2.0, weight=1.0,
                    proc={CC: 5.0, ES: 5.0, ED: 50.0},
                    trans={CC: 0.0, ES: 0.0, ED: 0.0}),
            JobSpec(name="B", release=0.0, weight=1.0,
                    proc={CC: 3.0, ES: 3.0, ED: 50.0},
                    trans={CC: 2.0, ES: 2.0, ED: 0.0}),
            JobSpec(name="C", release=0.0, weight=1.0,
                    proc={CC: 7.0, ES: 7.0, ED: 50.0},
                    trans={CC: 2.0, ES: 2.0, ED: 0.0}),
        ]
        for assign in ([CC, CC, CC], [ES, ES, ES]):
            s = simulate(jobs, assign)
            by_name = {e.job.name: e for e in s.entries}
            # all arrive at t=2; order must be B (release 0, idx 1),
            # C (release 0, idx 2), A (release 2, idx 0)
            assert by_name["B"].start == 2.0
            assert by_name["C"].start == 5.0
            assert by_name["A"].start == 12.0
            rel, w, proc, trans = scheduler_jax.specs_to_arrays(jobs)
            enc = np.asarray([[MACHINES.index(t) for t in assign]], np.int32)
            m = scheduler_jax.evaluate_assignments(enc, rel, w, proc, trans)
            assert abs(float(m["weighted"][0]) - s.weighted_sum) < 1e-6
            assert abs(float(m["last"][0]) - s.last_end) < 1e-6


# ------------------------------------------------------ jitted tabu search
class TestTabuSearchJax:
    def test_reaches_exact_optimum_on_small_instances(self):
        for seed in range(5):
            jobs = _random_jobs(np.random.default_rng(seed), 7)
            (v,), (a,) = scheduler_jax.tabu_search_batched([jobs])
            opt, _ = scheduler_jax.exact_optimum_jax(jobs)
            assert v <= opt * 1.05 + 1e-6
            # the returned value is the exact simulation of the returned
            # assignment
            s = simulate(jobs, [MACHINES[int(i)] for i in a])
            assert abs(v - s.weighted_sum) < 1e-3

    def test_improves_on_greedy_start(self):
        jobs = table6_jobs()
        (v,), _ = scheduler_jax.tabu_search_batched([jobs])
        greedy = simulate(jobs, scheduler.greedy_schedule(jobs))
        assert v <= greedy.weighted_sum + 1e-6


# -------------------------------------------------------- dispatched search
class TestSearchDispatch:
    def test_python_path_below_threshold(self):
        jobs = table6_jobs()
        a = scheduler.search(jobs, jax_threshold=100)
        b = scheduler.neighborhood_search(jobs)
        assert a.weighted_sum == b.weighted_sum

    def test_jax_path_above_threshold(self):
        jobs = _random_jobs(np.random.default_rng(0), 30)
        s = scheduler.search(jobs, jax_threshold=10)
        # valid exact schedule, at least as good as every baseline
        assert len(s.entries) == 30
        for t in MACHINES:
            assert s.weighted_sum <= \
                scheduler.all_on_tier(jobs, t).weighted_sum + 1e-6

    def test_sizes_in_one_bucket_share_a_program(self):
        """`search` pads the device search's rows to a 16-row bucket
        (DESIGN.md §12): n=9 and n=10 ride one compiled program, and
        n=20 needs the next bucket's."""
        mpt = {CC: 2, ES: 1}
        kernel = scheduler_jax._tabu_run_packed
        kernel.clear_cache()
        for n, grows in ((9, True), (10, False), (20, True)):
            before = kernel._cache_size()
            jobs = _random_jobs(np.random.default_rng(n), n)
            scheduler.search(jobs, machines_per_tier=mpt, jax_threshold=0)
            assert (kernel._cache_size() > before) == grows, n

    def test_online_replan_through_dispatcher(self):
        from repro.core import online
        jobs = _random_jobs(np.random.default_rng(3), 12)
        on_py = online.online_schedule(jobs, replan="tabu")
        on_jax = online.online_schedule(jobs, replan="tabu",
                                        jax_threshold=4)
        for s in (on_py, on_jax):
            assert len(s.entries) == 12
            for e in s.entries:
                assert e.start >= e.job.release + e.job.trans[e.machine] \
                    - 1e-9


# ------------------------------------------------ backend follows the input
def _search(jax_threshold):
    jobs = _random_jobs(np.random.default_rng(0), 9)
    return scheduler.search(jobs, machines_per_tier={CC: 2, ES: 1},
                            jax_threshold=jax_threshold).assignment()


def _search_batched(jax_threshold):
    probs = [_random_jobs(np.random.default_rng(s), 9) for s in range(3)]
    return [s.assignment() for s in scheduler.search_batched(
        probs, min_batch=99, jax_threshold=jax_threshold)]


def _metro_log_crc(jax_threshold):
    from repro.metro import TabuPolicy, simulate_metro
    traces = [[replace(j, name=f"{j.name}.{p}", release=j.release + 25.0 * p)
               for p in range(2) for j in table6_jobs()]]
    res = simulate_metro(traces, TabuPolicy(jax_threshold=jax_threshold),
                         machines_per_tier={CC: 1, ES: 1})
    return zlib.crc32(repr(res.event_log).encode())


@pytest.mark.parametrize("run", [_search, _search_batched, _metro_log_crc],
                         ids=["search", "search_batched", "metro"])
def test_backend_is_a_function_of_the_input(run, monkeypatch):
    """The search backend follows the call's own arguments and the
    platform (DESIGN.md §3.3): below the threshold on the CPU the Python
    path runs, and returns the same answer, before and after a forced
    device search of the same bucketed shape."""
    calls = []
    real = scheduler_jax.tabu_search_batched

    def spy(*args, **kw):
        calls.append(len(args[0]))
        return real(*args, **kw)

    monkeypatch.setattr(scheduler_jax, "tabu_search_batched", spy)
    fresh = run(None)
    assert calls == []                          # CPU default: Python
    run(0)
    forced = len(calls)
    assert forced > 0                           # explicit: device search
    assert run(None) == fresh
    assert len(calls) == forced                 # still Python


# ----------------------------------------- batched initial/frozen threading
class TestBatchedInitialFrozen:
    def test_mixed_initials_agree_across_dispatch_paths(self):
        """A per-ward `initial` with gaps works on BOTH search_batched
        paths: the sequential fallback and the batched backend (which
        fills the gaps with the same greedy initial the solo path
        uses)."""
        probs = [_random_jobs(np.random.default_rng(s), 6)
                 for s in range(4)]
        initial = [["cloud"] * 6, None, ["device"] * 6, None]
        seq = scheduler.search_batched(probs, max_count=3,
                                       initial=initial, min_batch=99)
        bat = scheduler.search_batched(probs, max_count=3,
                                       initial=initial, min_batch=1)
        for s, b in zip(seq, bat):
            assert len(s.entries) == len(b.entries) == 6
            assert s.weighted_sum > 0 and b.weighted_sum > 0

    def test_frozen_background_via_search_batched(self):
        """frozen masks ride through search_batched to both backends and
        pin the background jobs' tiers."""
        probs = [_random_jobs(np.random.default_rng(s), 5)
                 for s in range(2)]
        initial = [["cloud", "cloud", "device", "device", "device"]] * 2
        frozen = [[True, True, False, False, False]] * 2
        for min_batch in (99, 1):
            plans = scheduler.search_batched(
                probs, max_count=3, initial=initial, frozen=frozen,
                min_batch=min_batch)
            for p in plans:
                assert p.assignment()[:2] == ["cloud", "cloud"]
        with pytest.raises(ValueError):
            scheduler.search_batched(probs, frozen=frozen, min_batch=1)
