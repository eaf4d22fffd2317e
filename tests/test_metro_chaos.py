"""Crash-consistency tests for the metro engine (DESIGN.md §11):
job-killing failures with retry/failover, SHED accounting, degraded-
network pricing, seeded determinism of every chaos scenario pack, and
the per-scenario regression-gate logic."""
import os
import sys

import pytest

from repro.core.tiers import CC, ED, ES
from repro.core.simulator import JobSpec
from repro.metro import traces
from repro.metro.engine import (FailureEvent, MetroEngine, NetworkEvent,
                                ScaleEvent, _Pool, simulate_metro)
from repro.metro.policies import (SHED, GreedyPolicy, SheddingPolicy,
                                  TabuPolicy)

MPT = {CC: 2, ES: 2}


def _cloud_job(name, release, proc_c, trans_c=2.0, deadline=float("inf"),
               weight=1.0, workload=""):
    """A job only the cloud can run sensibly (edge/device prohibitive)."""
    return JobSpec(name=name, release=release, weight=weight,
                   proc={CC: proc_c, ES: 500.0, ED: 500.0},
                   trans={CC: trans_c, ES: 0.0, ED: 0.0},
                   deadline=deadline, workload=workload)


def _run_pack(name, policy, seed=0, wards=None, horizon=None):
    sc = traces.make_scenario(name, seed, wards=wards, horizon=horizon)
    res = simulate_metro(sc.traces, policy,
                         machines_per_tier=MPT, failures=sc.failures,
                         scale_events=sc.scales, network_events=sc.network,
                         slowdowns=sc.slowdowns)
    return sc, res


# ------------------------------------------------------------ crash kills
def test_crash_kills_in_flight_job_and_retries_it():
    # A starts at t=2 (trans 2), would end at 12; the crash at t=5 kills
    # it mid-run: 3 machine-seconds wasted, re-dispatched as a fresh
    # arrival, restarted once the machine repairs at 15
    jobs = [_cloud_job("A", 0.0, proc_c=10.0)]
    crash = FailureEvent(time=5.0, tier=CC, duration=10.0,
                         kill_running=True)
    res = simulate_metro([jobs], GreedyPolicy(),
                         machines_per_tier={CC: 1, ES: 1},
                         failures=[crash])
    (a,) = res.wards[0].entries
    assert (a.machine, a.start, a.end) == (CC, 15.0, 25.0)
    kill = next(ev for ev in res.event_log if ev[0] == "kill")
    assert kill == ("kill", 5.0, 0, 0, CC, 0, 3.0, 1)
    fail = next(ev for ev in res.event_log if ev[0] == "fail")
    assert fail == ("fail", 5.0, CC, -1, 0, 15.0, 1)
    comp = next(ev for ev in res.event_log if ev[0] == "complete")
    assert comp[-1] == 2                              # attempts
    m = res.metrics
    assert (m.retries, m.max_attempts) == (1, 2)
    assert m.wasted_seconds == pytest.approx(3.0)
    assert m.completions == 1 and m.shed == 0
    # the event log's kinds tell the whole story, in order
    assert [ev[0] for ev in res.event_log] == \
        ["arrive", "fail", "kill", "recover", "complete"]


def test_crash_retry_fails_over_to_another_tier():
    # the edge is a viable escape: when the crash takes the only cloud
    # machine down for 50, the tabu replanner re-dispatches the killed
    # job to the edge instead of waiting out the repair
    job = JobSpec(name="A", release=0.0, weight=1.0,
                  proc={CC: 10.0, ES: 12.0, ED: 100.0},
                  trans={CC: 2.0, ES: 1.0, ED: 0.0})
    crash = FailureEvent(time=5.0, tier=CC, duration=50.0,
                         kill_running=True)
    res = simulate_metro([[job]], TabuPolicy(jax_threshold=10 ** 9),
                         machines_per_tier={CC: 1, ES: 1},
                         failures=[crash])
    (a,) = res.wards[0].entries
    assert a.machine == ES                    # failover, not wait-for-repair
    assert a.end == 5.0 + 1.0 + 12.0          # re-shipped at the kill time
    assert res.metrics.retries == 1
    comp = next(ev for ev in res.event_log if ev[0] == "complete")
    assert comp[4] == ES and comp[-1] == 2


def test_crash_strikes_the_busiest_machine():
    # two cloud machines: A (long) on slot 0, B (short) on slot 1; by
    # t=10 B has drained, so the LATEST-free machine is A's — a crash
    # must kill A, not strike the idle slot
    jobs = [_cloud_job("A", 0.0, proc_c=20.0),
            _cloud_job("B", 0.0, proc_c=3.0, trans_c=1.0)]
    crash = FailureEvent(time=10.0, tier=CC, duration=5.0,
                         kill_running=True)
    res = simulate_metro([jobs], GreedyPolicy(),
                         machines_per_tier={CC: 2, ES: 1},
                         failures=[crash])
    kills = [ev for ev in res.event_log if ev[0] == "kill"]
    assert len(kills) == 1 and kills[0][2:4] == (0, 0)   # ward 0, job A
    assert res.metrics.completions == 2                  # B untouched + A retried


def test_drain_failure_still_never_kills():
    jobs = [_cloud_job("A", 0.0, proc_c=10.0)]
    drain = FailureEvent(time=5.0, tier=CC, duration=10.0)
    res = simulate_metro([jobs], GreedyPolicy(),
                         machines_per_tier={CC: 1, ES: 1},
                         failures=[drain])
    assert not any(ev[0] == "kill" for ev in res.event_log)
    (a,) = res.wards[0].entries
    assert (a.start, a.end) == (2.0, 12.0)               # run undisturbed
    assert res.metrics.retries == 0


def test_failure_on_fully_retired_pool_logs_and_skips():
    eng = MetroEngine([[_cloud_job("A", 0.0, proc_c=1.0)]],
                      GreedyPolicy(), machines_per_tier={CC: 1, ES: 1})
    for s in eng.cloud.slots:
        s.retired_at = 0.0
        s.down = float("inf")
    eng._on_fail(3.0, FailureEvent(time=3.0, tier=CC, duration=5.0,
                                   kill_running=True))
    assert ("fail", 3.0, CC, -1, -1, 3.0, 1) in eng.event_log
    # no machine was struck: no outage recorded, no recovery scheduled
    assert all(not s.outages for s in eng.cloud.slots)
    assert not any(p[0] == "recover" for _, _, _, p in eng._heap)


def test_same_timestamp_fail_scale_recover_ordering():
    # at t=10 three fleet events collide; the engine must apply the NEW
    # failure first, then the scale-up, then the recovery of the t=5
    # failure (_P_FAIL < _P_SCALE < _P_RECOVER)
    jobs = [_cloud_job("A", 0.0, proc_c=1.0)]
    res = simulate_metro([jobs], GreedyPolicy(),
                         machines_per_tier={CC: 2, ES: 1},
                         failures=[FailureEvent(time=5.0, duration=5.0),
                                   FailureEvent(time=10.0, duration=3.0)],
                         scale_events=[ScaleEvent(time=10.0, delta=1)])
    at_10 = [ev[0] for ev in res.event_log
             if ev[0] in ("fail", "scale", "recover") and ev[1] == 10.0]
    assert at_10 == ["fail", "scale", "recover"]


def test_capacity_integral_merges_overlaps_and_clips_retirement():
    pool = _Pool(CC, 1)
    slot = pool.slots[0]
    slot.outages = [(2.0, 8.0), (5.0, 12.0),    # overlap -> union [2, 12)
                    (18.0, 25.0)]               # straddles the retirement
    slot.retired_at = 20.0
    # lifetime [0, 20): 20 - union([2,12)) - clip([18,25) -> [18,20))
    assert pool.capacity_integral(30.0) == pytest.approx(20 - 10 - 2)
    # before the retirement the clip is t_end itself
    assert pool.capacity_integral(6.0) == pytest.approx(6 - 4)
    # a double-struck machine never goes negative
    slot.outages.append((0.0, 50.0))
    assert pool.capacity_integral(30.0) == 0.0


# --------------------------------------------------------------- shedding
class _ShedAll:
    """Degenerate policy: sheds every movable job (accounting probe)."""
    name = "shed_all"
    joint = False
    replans_on_fleet_events = False

    def decide(self, requests, now):
        return [[SHED] * len(req.movable) for req in requests]


def test_shed_accounting_and_run_invariant():
    jobs = [_cloud_job("A", 0.0, proc_c=5.0, deadline=30.0,
                       weight=2.0, workload="alert"),
            _cloud_job("B", 1.0, proc_c=5.0, deadline=30.0,
                       weight=1.0, workload="phenotype")]
    res = simulate_metro([jobs], _ShedAll(),
                         machines_per_tier={CC: 1, ES: 1})
    m = res.metrics
    assert (m.completions, m.shed, m.finished) == (0, 2, 2)
    assert m.miss_rate == 1.0 and m.shed_rate == 1.0
    assert m.weighted_miss_rate == 1.0
    assert m.by_class == {"alert": [0, 0, 1], "phenotype": [0, 0, 1]}
    assert res.wards[0].entries == []         # nothing ever ran
    sheds = [ev for ev in res.event_log if ev[0] == "shed"]
    assert sheds == [("shed", 0.0, 0, 0, "A"), ("shed", 1.0, 0, 1, "B")]


def test_bad_policy_decision_rejected_centrally():
    class _Mars:
        name = "mars"
        joint = False
        replans_on_fleet_events = False

        def decide(self, requests, now):
            return [["mars"] * len(req.movable) for req in requests]

    with pytest.raises(ValueError, match="mars"):
        simulate_metro([[_cloud_job("A", 0.0, proc_c=1.0)]], _Mars(),
                       machines_per_tier={CC: 1, ES: 1})


def test_shedding_policy_spares_the_life_critical_class():
    # under the saturation pack the shedder drops work — but never a job
    # of the heaviest weight class (alerts/threats, w=2): it chooses
    # WHICH deadline to miss, and w=1 phenotype reports pay
    _, res = _run_pack("mass_casualty_crash", SheddingPolicy())
    m = res.metrics
    assert m.shed > 0
    w_max = max(m.class_weight.values())
    for cls, (done, missed, shed) in m.by_class.items():
        if m.class_weight[cls] >= w_max:
            assert shed == 0, f"shed a {cls} job (w={m.class_weight[cls]})"
    assert any(shed for _, _, shed in m.by_class.values())
    # and the protection is the point: life-critical misses beat greedy's
    _, greedy = _run_pack("mass_casualty_crash", GreedyPolicy())
    assert m.critical_miss_rate < greedy.metrics.critical_miss_rate


# ------------------------------------------------------- degraded network
def test_network_window_reroutes_decisions():
    # cloud normally wins (arrival 3, end 8 vs edge 21); inside a 10x
    # degraded-uplink window the shipped-to-cloud price is 21 > edge 21?
    # no: trans 2 -> 20, end 1+20+5 = 26 > edge 1+1+20 = 22 -> edge
    job = JobSpec(name="A", release=1.0, weight=1.0,
                  proc={CC: 5.0, ES: 20.0, ED: 200.0},
                  trans={CC: 2.0, ES: 1.0, ED: 0.0})
    base = simulate_metro([[job]], GreedyPolicy(),
                          machines_per_tier={CC: 1, ES: 1})
    assert base.wards[0].entries[0].machine == CC
    net = NetworkEvent(time=0.0, duration=10.0, tier=CC, factor=10.0)
    res = simulate_metro([[job]], GreedyPolicy(),
                         machines_per_tier={CC: 1, ES: 1},
                         network_events=[net])
    (a,) = res.wards[0].entries
    assert a.machine == ES                      # the window re-routed it
    assert a.arrival == 2.0                     # edge trans NOT degraded
    opens = [ev for ev in res.event_log if ev[0] == "net"]
    assert opens == [("net", 0.0, CC, 10.0, 1), ("net", 10.0, CC, 10.0, 0)]


def test_network_factors_compound_and_unwind():
    eng = MetroEngine([[_cloud_job("A", 0.0, proc_c=1.0)]],
                      GreedyPolicy(), machines_per_tier={CC: 1, ES: 1})
    e1 = NetworkEvent(time=0.0, duration=10.0, tier=CC, factor=2.0)
    e2 = NetworkEvent(time=1.0, duration=5.0, tier=CC, factor=3.0)
    eng._on_net(0.0, e1, True)
    eng._on_net(1.0, e2, True)
    assert eng._net_factor(CC) == pytest.approx(6.0)    # windows compound
    assert eng._net_factor(ES) == 1.0
    eng._on_net(6.0, e2, False)
    assert eng._net_factor(CC) == pytest.approx(2.0)
    eng._on_net(10.0, e1, False)
    assert eng._net_factor(CC) == 1.0 and not eng._net


def test_network_event_validation():
    jobs = [[_cloud_job("A", 0.0, proc_c=1.0)]]
    with pytest.raises(ValueError, match="shared tier"):
        MetroEngine(jobs, GreedyPolicy(), machines_per_tier={CC: 1, ES: 1},
                    network_events=[NetworkEvent(time=0.0, tier=ED)])
    with pytest.raises(ValueError, match="factor"):
        MetroEngine(jobs, GreedyPolicy(), machines_per_tier={CC: 1, ES: 1},
                    network_events=[NetworkEvent(time=0.0, factor=0.0)])


# --------------------------------------------------- scenario-pack chaos
@pytest.mark.parametrize("pack", sorted(traces.SCENARIO_PACKS))
def test_every_pack_is_deterministic_and_crash_consistent(pack):
    runs = [_run_pack(pack, GreedyPolicy(), seed=3) for _ in range(2)]
    (sc, a), (_, b) = runs
    assert a.event_log == b.event_log
    assert a.metrics.summary(a.utilization) == \
        b.metrics.summary(b.utilization)
    # crash consistency: every job in the pack ends completed or shed,
    # and retries only appear in the crash packs
    m = a.metrics
    assert m.finished == sc.jobs
    kills = sum(1 for ev in a.event_log if ev[0] == "kill")
    assert kills == m.retries
    if any(f.kill_running for f in sc.failures):
        completes = [ev for ev in a.event_log if ev[0] == "complete"]
        assert max(ev[-1] for ev in completes) == m.max_attempts
    else:
        assert m.retries == 0 and m.wasted_seconds == 0.0
    if sc.network:
        net = [ev for ev in a.event_log if ev[0] == "net"]
        assert len(net) == 2 * len(sc.network)


def test_search_policy_deterministic_on_crash_pack():
    # the replanning path through kills/failovers, pinned to the Python
    # search (jax_threshold) so the run is the same on any platform
    runs = [_run_pack("edge_brownout", TabuPolicy(jax_threshold=10 ** 9),
                      seed=1, wards=2, horizon=40.0) for _ in range(2)]
    (sc, a), (_, b) = runs
    assert a.event_log == b.event_log
    assert a.metrics.finished == sc.jobs
    assert a.metrics.retries == sum(1 for ev in a.event_log
                                    if ev[0] == "kill")


def test_unknown_pack_rejected():
    with pytest.raises(ValueError, match="unknown scenario pack"):
        traces.make_scenario("nope")


# ------------------------------------------------- per-scenario perf gate
class TestScenarioGate:
    """check_regression.py metro_scenarios logic (no bench run)."""

    def _mod(self):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks"))
        try:
            import check_regression
        finally:
            sys.path.pop(0)
        return check_regression

    def _reports(self):
        base = {"metro_scenarios": {
            "edge_brownout": {"events_per_s": 1000.0,
                              "miss_rate_improvement": 3.0,
                              "critical_improvement_shed": 4.0},
            "diurnal_day": {"events_per_s": 5000.0,
                            "miss_rate_improvement": None,
                            "critical_improvement_shed": None}}}
        import copy
        return base, copy.deepcopy(base)

    def test_metric_extraction_skips_vacuous(self):
        cr = self._mod()
        committed, _ = self._reports()
        keys = cr._metro_scenario_metrics(committed)
        assert keys == {
            "metro_scenarios/edge_brownout/events_per_s": 1000.0,
            "metro_scenarios/edge_brownout/miss_rate_improvement": 3.0,
            "metro_scenarios/edge_brownout/critical_improvement_shed": 4.0,
            "metro_scenarios/diurnal_day/events_per_s": 5000.0}

    def test_identical_reports_pass(self):
        cr = self._mod()
        committed, fresh = self._reports()
        assert cr.compare(committed, fresh) == []

    def test_floor_regression_fails(self):
        cr = self._mod()
        committed, fresh = self._reports()
        fresh["metro_scenarios"]["edge_brownout"]["events_per_s"] = 100.0
        problems = cr.compare(committed, fresh, tolerance=0.30)
        assert any("edge_brownout/events_per_s" in p for p in problems)

    def test_ranking_flip_fails_regardless_of_tolerance(self):
        cr = self._mod()
        committed, fresh = self._reports()
        fresh["metro_scenarios"]["edge_brownout"][
            "critical_improvement_shed"] = 0.9
        problems = cr.compare(committed, fresh, tolerance=10.0)
        assert any("no longer wins" in p for p in problems)

    def test_fresh_vacuous_improvement_is_not_a_flip(self):
        cr = self._mod()
        committed, fresh = self._reports()
        fresh["metro_scenarios"]["edge_brownout"][
            "miss_rate_improvement"] = None
        assert cr.compare(committed, fresh, tolerance=0.30) == []

    def test_best_of_n_overlay_rescues_wall_clock_only(self):
        cr = self._mod()
        committed, fresh = self._reports()
        key = "metro_scenarios/edge_brownout/events_per_s"
        fresh["metro_scenarios"]["edge_brownout"]["events_per_s"] = 100.0
        assert cr.compare(committed, fresh) != []
        assert cr.compare(committed, fresh, best={key: 950.0}) == []
        # the overlay never rescues a ranking invariant
        fresh["metro_scenarios"]["edge_brownout"][
            "critical_improvement_shed"] = 0.5
        problems = cr.compare(
            committed, fresh,
            best={key: 950.0,
                  "metro_scenarios/edge_brownout/critical_improvement_shed":
                  9.0})
        assert any("no longer wins" in p for p in problems)

    def test_wall_clock_key_classifier(self):
        cr = self._mod()
        assert cr._is_wall_clock("metro_scenarios/edge_brownout/"
                                 "events_per_s")
        assert cr._is_wall_clock("batched/wards_per_s_batched")
        assert not cr._is_wall_clock(
            "metro_scenarios/edge_brownout/critical_improvement_shed")
