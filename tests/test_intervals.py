"""Interval-reservation parity (DESIGN.md §12): the reserved-interval
representation of committed background occupancy must be bit-identical —
objectives AND trajectories — to the frozen-phantom construction it
replaces, at every layer: `simulate`/`ScheduleState`, the Python
`neighborhood_search`, the jitted `tabu_search_batched`, the dispatching
`search`/`search_batched`, the fixed-point `search_fleet` (both sweep
backends, all three objectives, (2,3)-ward fleets), the `_FleetEval`
trial evaluator, and the metro `TabuPolicy` replan path (B = 1 solo and
batched)."""
import numpy as np
import pytest

from prop import sweep
from repro.core import scheduler, scheduler_jax
from repro.core.problems import metro_jobs
from repro.core.simulator import (MACHINES, JobSpec, Reservation,
                                  ScheduleState, simulate, simulate_fleet,
                                  _fleet_mpts)
from repro.core.tiers import CC, ED, ES


def _random_jobs(rng, n):
    return [JobSpec(name=f"J{i}", release=float(rng.integers(0, 30)),
                    weight=float(rng.integers(1, 4)),
                    proc={t: float(rng.integers(1, 30)) for t in MACHINES},
                    trans={CC: float(rng.integers(0, 60)),
                           ES: float(rng.integers(0, 15)), ED: 0.0})
            for i in range(n)]


def _random_reservations(rng, max_per_tier=3):
    resv = {}
    for tier in (CC, ES):
        k = int(rng.integers(0, max_per_tier + 1))
        if k:
            rs = []
            for _ in range(k):
                rel = float(rng.integers(0, 30))
                rs.append(Reservation(
                    arrival=rel + float(rng.integers(0, 40)),
                    proc=float(rng.integers(1, 30)), release=rel,
                    weight=float(rng.integers(0, 4))))
            resv[tier] = rs
    return resv


def _phantoms(reserved):
    """The legacy frozen-phantom construction for a reservation map:
    background JobSpecs (appended after the instance's jobs, cloud list
    then edge list) plus their pinned tiers — the §12 oracle."""
    jobs, tiers = [], []
    for tier in (CC, ES):
        for k, r in enumerate((reserved or {}).get(tier) or ()):
            d = r.arrival - r.release
            jobs.append(JobSpec(
                name=f"bg-{tier}-{k}", release=r.release, weight=r.weight,
                proc={CC: r.proc, ES: r.proc, ED: r.proc},
                trans={CC: d, ES: d, ED: 0.0}))
            tiers.append(tier)
    return jobs, tiers


def _objectives(s):
    return (s.weighted_sum, s.unweighted_sum, s.last_end)


# --------------------------------------------------------- simulator layer
class TestSimulateParity:
    def test_reservations_equal_phantoms(self):
        """simulate(jobs, a, reserved=R) is bit-identical — all three
        sums AND per-reservation (arrival, start, end) — to simulating
        the phantom-augmented instance."""
        def check(rng):
            jobs = _random_jobs(rng, int(rng.integers(1, 10)))
            assign = [MACHINES[int(rng.integers(3))] for _ in jobs]
            resv = _random_reservations(rng)
            mpt = {CC: int(rng.integers(1, 3)), ES: int(rng.integers(1, 3))}
            busy = ({CC: [float(rng.integers(0, 20))]}
                    if rng.integers(2) else None)
            ph_jobs, ph_tiers = _phantoms(resv)
            ref = simulate(jobs + ph_jobs, assign + ph_tiers,
                           machines_per_tier=mpt, busy_until=busy)
            got = simulate(jobs, assign, machines_per_tier=mpt,
                           busy_until=busy, reserved=resv)
            assert _objectives(got) == _objectives(ref)
            # reservation timings == the phantom entries they replace
            ph = ref.entries[len(jobs):]
            k = 0
            for tier in (CC, ES):
                for t in (got.reserved_times or {}).get(tier, ()):
                    assert t == (ph[k].arrival, ph[k].start, ph[k].end)
                    k += 1
            assert k == len(ph_jobs)
        sweep(check, n_cases=25, seed=0)

    def test_tie_breaks_job_first_then_list_order(self):
        """At equal (arrival, release) a real job dispatches before a
        reservation, and reservations keep input-list order — exactly
        the phantom append order."""
        job = JobSpec(name="J", release=0.0, weight=1.0,
                      proc={CC: 5.0, ES: 5.0, ED: 50.0},
                      trans={CC: 0.0, ES: 0.0, ED: 0.0})
        rs = [Reservation(arrival=0.0, proc=3.0, release=0.0, weight=1.0),
              Reservation(arrival=0.0, proc=7.0, release=0.0, weight=1.0)]
        s = simulate([job], [CC], reserved={CC: rs})
        assert s.entries[0].start == 0.0
        (a0, s0, e0), (a1, s1, e1) = s.reserved_times[CC]
        assert (s0, e0) == (5.0, 8.0)       # first listed runs first
        assert (s1, e1) == (8.0, 15.0)

    def test_schedule_state_tracks_simulate(self):
        """ScheduleState with reservations: score / try_move /
        apply_move / to_schedule all agree with fresh `simulate` calls
        on every objective through a random move sequence."""
        def check(rng):
            jobs = _random_jobs(rng, int(rng.integers(2, 8)))
            assign = [MACHINES[int(rng.integers(3))] for _ in jobs]
            resv = _random_reservations(rng)
            mpt = {CC: 2, ES: 1}
            state = ScheduleState(jobs, list(assign),
                                  machines_per_tier=mpt, reserved=resv)
            for _ in range(6):
                k = int(rng.integers(len(jobs)))
                dst = MACHINES[int(rng.integers(3))]
                moved = list(state.assign)
                moved[k] = dst
                ref = simulate(jobs, moved, machines_per_tier=mpt,
                               reserved=resv)
                for obj in ("weighted", "unweighted", "last"):
                    assert state.try_move(k, dst, obj) == ref.objective(obj)
                if dst != state.assign[k]:
                    state.apply_move(k, dst)
                    for obj in ("weighted", "unweighted", "last"):
                        assert state.score(obj) == ref.objective(obj)
            final = state.to_schedule()
            ref = simulate(jobs, state.assign, machines_per_tier=mpt,
                           reserved=resv)
            assert _objectives(final) == _objectives(ref)
        sweep(check, n_cases=12, seed=7)

    def test_reservations_shared_tiers_only(self):
        job = _random_jobs(np.random.default_rng(0), 1)[0]
        bad = {ED: [Reservation(arrival=0.0, proc=1.0, release=0.0)]}
        with pytest.raises(ValueError):
            simulate([job], [CC], reserved=bad)
        with pytest.raises(ValueError):
            ScheduleState([job], [CC], reserved=bad)


# ------------------------------------------------------ python search layer
class TestPythonSearchParity:
    @pytest.mark.parametrize("objective", ["weighted", "unweighted", "last"])
    def test_neighborhood_search_matches_phantom(self, objective):
        """Same trajectory: the interval search's move sequence equals
        the frozen-phantom search's (movable candidates, scores and ties
        all agree), so assignments and objectives are bit-identical."""
        def check(rng):
            jobs = _random_jobs(rng, int(rng.integers(2, 8)))
            resv = _random_reservations(rng)
            init = [MACHINES[int(rng.integers(3))] for _ in jobs]
            mpt = {CC: int(rng.integers(1, 3)), ES: 1}
            ph_jobs, ph_tiers = _phantoms(resv)
            got = scheduler.neighborhood_search(
                jobs, initial=init, max_count=5, objective=objective,
                machines_per_tier=mpt, reserved=resv or None)
            ref = scheduler.neighborhood_search(
                jobs + ph_jobs, initial=init + ph_tiers, max_count=5,
                objective=objective, machines_per_tier=mpt,
                frozen=[False] * len(jobs) + [True] * len(ph_jobs))
            assert got.assignment() == ref.assignment()[:len(jobs)]
            assert _objectives(got) == _objectives(ref)
        sweep(check, n_cases=10, seed=31)

    def test_reservations_require_initial(self):
        jobs = _random_jobs(np.random.default_rng(1), 4)
        resv = {CC: [Reservation(arrival=0.0, proc=5.0, release=0.0)]}
        with pytest.raises(ValueError):
            scheduler.neighborhood_search(jobs, reserved=resv)
        with pytest.raises(ValueError):
            scheduler.search(jobs, reserved=resv, jax_threshold=0)
        with pytest.raises(ValueError):
            scheduler_jax.tabu_search_batched([jobs], reserved=[resv])
        with pytest.raises(ValueError, match="wards"):
            scheduler.search_batched([jobs, jobs], reserved=[None, resv])


# ------------------------------------------------------------ kernel layer
class TestKernelParity:
    MPT = [(2, 1)]

    def _case(self, seed, n=6):
        rng = np.random.default_rng(seed)
        jobs = _random_jobs(rng, n)
        resv = _random_reservations(rng)
        if not resv:
            resv = {CC: [Reservation(arrival=3.0, proc=4.0, release=1.0,
                                     weight=2.0)]}
        init = [int(rng.integers(3)) for _ in jobs]
        return jobs, resv, init

    @pytest.mark.parametrize("objective", ["weighted", "unweighted", "last"])
    def test_batched_reserved_equals_frozen(self, objective):
        """tabu_search_batched: reserved rows vs frozen-phantom rows are
        bit-identical in value and assignment on integer instances."""
        for seed in (0, 1, 2):
            jobs, resv, init = self._case(seed)
            ph_jobs, ph_tiers = _phantoms(resv)
            ph_idx = [MACHINES.index(t) for t in ph_tiers]
            v1, a1 = scheduler_jax.tabu_search_batched(
                [jobs], [init], objective=objective,
                machines_per_tier=self.MPT, reserved=[resv], pad_to=16)
            v2, a2 = scheduler_jax.tabu_search_batched(
                [jobs + ph_jobs], [init + ph_idx], objective=objective,
                machines_per_tier=self.MPT,
                frozen=[[False] * len(jobs) + [True] * len(ph_jobs)],
                pad_to=16)
            assert float(v1[0]) == float(v2[0])
            assert list(a1[0]) == list(a2[0])[:len(jobs)]

    def test_search_jax_equals_python_with_reservations(self):
        """The dispatching `search`: forced-JAX and Python backends land
        on the same objective for a reserved instance, and the JAX value
        is exact (rescored by `simulate`)."""
        jobs, resv, init = self._case(5)
        init_t = [MACHINES[i] for i in init]
        mpt = {CC: 2, ES: 1}
        jaxed = scheduler.search(jobs, initial=init_t, reserved=resv,
                                 jax_threshold=0, machines_per_tier=mpt)
        py = scheduler.search(jobs, initial=init_t, reserved=resv,
                              jax_threshold=10**9, machines_per_tier=mpt)
        ref = simulate(jobs, jaxed.assignment(), machines_per_tier=mpt,
                       reserved=resv)
        assert jaxed.weighted_sum == ref.weighted_sum
        assert jaxed.weighted_sum == py.weighted_sum

    def test_search_batched_reserved_per_ward(self):
        """Per-ward reservation maps ride the batched path and each
        ward's result is exact under its own reservations."""
        cases = [self._case(s) for s in (10, 11, 12)]
        problems = [jobs for jobs, _, _ in cases]
        resvs = [resv for _, resv, _ in cases]
        inits = [[MACHINES[i] for i in init] for _, _, init in cases]
        scheds = scheduler.search_batched(
            problems, machines_per_tier=[{CC: 2, ES: 1}] * 3,
            initial=inits, reserved=resvs, min_batch=1, jax_threshold=0)
        for jobs, resv, s in zip(problems, resvs, scheds):
            ref = simulate(jobs, s.assignment(),
                           machines_per_tier={CC: 2, ES: 1}, reserved=resv)
            assert _objectives(s) == _objectives(ref)


# ------------------------------------------------------------- fleet layer
class TestFleetEvalExact:
    def test_matches_simulate_fleet_bitwise(self):
        """_FleetEval replays `simulate_fleet`'s heap arithmetic — every
        random trial plan scores bit-identically on all objectives."""
        def check(rng):
            B = int(rng.integers(1, 4))
            wards = [_random_jobs(rng, int(rng.integers(1, 8)))
                     for _ in range(B)]
            shared = (CC,) if rng.integers(2) else (CC, ES)
            mpt = {CC: int(rng.integers(1, 3)), ES: int(rng.integers(1, 3))}
            busy = ({CC: [float(rng.integers(0, 15))]}
                    if rng.integers(2) else None)
            wbusy = ([{ES: [float(rng.integers(0, 15))]}
                      for _ in range(B)]
                     if (ES not in shared and rng.integers(2)) else None)
            mpts = _fleet_mpts(mpt, B, shared)
            ev = scheduler._FleetEval(wards, mpts, busy, wbusy, shared)
            for _ in range(5):
                plan = [[MACHINES[int(rng.integers(3))] for _ in jobs]
                        for jobs in wards]
                ref = simulate_fleet(wards, plan, machines_per_tier=mpts,
                                     busy_until=busy,
                                     ward_busy_until=wbusy,
                                     shared_tiers=shared)
                for obj in ("weighted", "unweighted", "last"):
                    assert ev(plan, obj) == ref.objective(obj)
        sweep(check, n_cases=12, seed=90)


class TestSearchFleetParity:
    MPT = {CC: 2, ES: 1}

    def _wards(self, seed, B, n=8):
        rng = np.random.default_rng(seed)
        return [metro_jobs(rng, n=n) for _ in range(B)]

    @pytest.mark.parametrize("objective", ["weighted", "unweighted", "last"])
    @pytest.mark.parametrize("backend", ["python", "batched"])
    def test_interval_equals_phantom(self, objective, backend):
        """The tentpole contract: `search_fleet` with interval
        reservations reproduces the frozen-phantom path's plan —
        identical assignments, sweeps and fleet-true objectives — on
        both sweep backends and all three objectives."""
        wards = self._wards(42, B=3)
        kw = dict(machines_per_tier=self.MPT, objective=objective,
                  max_count=5, max_sweeps=3, sweep_backend=backend,
                  pad_bucket=16)
        pi = scheduler.search_fleet(wards, background="interval", **kw)
        pp = scheduler.search_fleet(wards, background="phantom", **kw)
        assert pi.assignments == pp.assignments
        assert pi.sweeps == pp.sweeps
        assert pi.fleet.objective(objective) == \
            pp.fleet.objective(objective)
        assert _objectives(pi.naive_fleet) == _objectives(pp.naive_fleet)

    def test_two_ward_fleet_parity(self):
        """(2,3) fleets per the issue: the B = 2 case too."""
        wards = self._wards(7, B=2, n=6)
        for backend in ("python", "batched"):
            pi = scheduler.search_fleet(
                wards, machines_per_tier=self.MPT, max_count=4,
                max_sweeps=2, sweep_backend=backend, pad_bucket=16)
            pp = scheduler.search_fleet(
                wards, machines_per_tier=self.MPT, max_count=4,
                max_sweeps=2, sweep_backend=backend, pad_bucket=16,
                background="phantom")
            assert pi.assignments == pp.assignments
            assert pi.fleet.weighted_sum == pp.fleet.weighted_sum

    def test_background_validated(self):
        with pytest.raises(ValueError):
            scheduler.search_fleet(self._wards(0, B=2, n=3),
                                   machines_per_tier=self.MPT,
                                   background="hologram")


# ------------------------------------------------------- metro replan layer
class TestMetroReplanParity:
    def _request(self, seed, n=6, bg=2):
        from repro.metro.policies import ReplanRequest
        rng = np.random.default_rng(seed)
        jobs = _random_jobs(rng, n)
        bg_specs = _random_jobs(rng, bg)
        cur = [MACHINES[int(rng.integers(3))] for _ in jobs]
        return ReplanRequest(
            ward=0, movable=list(range(n)), shifted=jobs,
            current=list(cur), fresh=[], busy={CC: [0.0, 0.0]},
            reserved={CC: [0.0, 0.0]},
            machines_per_tier={CC: 2, ES: 1}, background=bg_specs)

    def test_tabu_policy_background_equals_phantom_search(self):
        """TabuPolicy's reservation replan (metro B = 1 decide) lands on
        the frozen-phantom reference search bit-identically."""
        from repro.metro.policies import TabuPolicy
        for seed in (3, 4, 5):
            req = self._request(seed)
            got = TabuPolicy(max_count=5).decide([req], now=0.0)[0]
            n = len(req.shifted)
            ph = list(req.background)
            ref = scheduler.search(
                req.shifted + ph,
                initial=req.current + [CC] * len(ph),
                frozen=[False] * n + [True] * len(ph), max_count=5,
                machines_per_tier=req.machines_per_tier,
                busy_until=req.busy)
            assert got == ref.assignment()[:n]

    def test_tabu_policy_solo_equals_batched(self):
        """One request through the solo path == the same request forced
        through the batched path (min_batch=1) — decisions identical."""
        from repro.metro.policies import TabuPolicy
        req = self._request(13)
        solo = TabuPolicy(max_count=5).decide([req], now=0.0)
        resv, init = TabuPolicy._reservations(req)
        batched = scheduler.search_batched(
            [list(req.shifted)], max_count=5,
            machines_per_tier=[req.machines_per_tier],
            busy_until=[req.busy], initial=[init], reserved=[resv],
            min_batch=10**9)
        assert solo == [batched[0].assignment()]
