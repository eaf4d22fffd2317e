"""Several wards on one pooled cloud (DESIGN.md §12, "Metro integration"):
every replan carries the other wards' queued cloud work as reservation
rows, counted apart from its own jobs on `scheduler.dispatch`
(`reserved_rows`), with the request build's `wards` and `background`
counts on `engine.requests`. Once reservations dominate a search it runs
in the `pass` regime, and every answer is still a 1-move local
optimum of its subproblem under `simulator.simulate` with the same
reservations, and every committed schedule is feasible."""
from dataclasses import replace

import pytest

from repro.core import problems
from repro.core.scheduler_jax import tabu_search_batched
from repro.core.simulator import JobSpec, Reservation, simulate
from repro.core.tiers import CC, ED, ES
from repro.metro import TabuPolicy, simulate_metro
from repro.utils import spans

from test_metro import _check_schedule_invariants

TIERS = (CC, ES, ED)
MPT = {CC: 4, ES: 1}


def _burst_job(name):
    """A job the cloud serves best, whose data reaches it 20 units after
    release: it waits in the cloud's queue, unstarted, all that time."""
    return JobSpec(name=name, release=0.0, weight=1.0,
                   proc={CC: 2.0, ES: 30.0, ED: 60.0},
                   trans={CC: 20.0, ES: 0.0, ED: 0.0})


def pooled_traces(burst_wards=3, burst=12):
    """`burst_wards` wards that each commit a burst of cloud work at t=0,
    then a ward sending Table VI's ten jobs from t=1 against that queue."""
    traces = [[_burst_job(f"w{b}-{k}") for k in range(burst)]
              for b in range(burst_wards)]
    return traces + [[replace(j, name=f"icu-{j.name}")
                      for j in problems.table6_jobs()]]


class Recording:
    """A policy that records every request and the answer given to it."""

    def __init__(self, inner):
        self.inner = inner
        self.answers = []

    def __getattr__(self, key):
        return getattr(self.inner, key)

    def decide(self, requests, now):
        out = self.inner.decide(requests, now)
        self.answers.extend(zip(requests, out))
        return out


@pytest.fixture(scope="module")
def pooled():
    traces = pooled_traces()
    policy = Recording(TabuPolicy(jax_threshold=0))
    with spans.recording() as rec:
        res = simulate_metro(traces, policy, machines_per_tier=MPT)
    return traces, res, policy.answers, rec


def _cloud_reservations(k):
    """k reservations on the cloud, or None for none."""
    return {CC: [Reservation(arrival=float(a), proc=2.0, release=0.0,
                             weight=1.0) for a in range(k)]} if k else None


@pytest.mark.parametrize("reserved", [[0], [5], [3, 0], [20, 7]],
                         ids=["none", "one-ward", "one-of-two", "two-wards"])
def test_dispatch_counts_reservation_rows_apart(reserved):
    jobs = problems.table6_jobs()
    batch = [jobs[:4 + b] for b in range(len(reserved))]
    resv = [_cloud_reservations(k) for k in reserved]
    with spans.recording() as rec:
        tabu_search_batched(batch, [[2] * len(j) for j in batch],
                            machines_per_tier=(2, 1), reserved=resv)
    (dispatch,) = [s for s in rec.spans if s.name == "scheduler.dispatch"]
    assert dispatch.attrs["reserved_rows"] == sum(reserved)
    assert dispatch.attrs["rows_real"] - dispatch.attrs["reserved_rows"] \
        == sum(len(j) for j in batch)


def test_request_build_counts_wards_and_background(pooled):
    _, _, answers, rec = pooled
    built = [s for s in rec.spans if s.name == "engine.requests"]
    assert built and all({"wards", "background"} <= set(s.attrs)
                         for s in built)
    # one request per arrival: the requests the policy saw, span by span
    with_req = [s for s in built if s.attrs["wards"]]
    assert sum(s.attrs["wards"] for s in built) == len(answers) == \
        len(with_req)
    # a request's background is the gathered queue less its own movable
    # cloud jobs
    for s, (req, _) in zip(with_req, answers):
        own = sum(t == CC for t in req.current)
        assert s.attrs["background"] == len(req.background) + own
    # enough queued work to push a search past the 32-row bucket
    assert max(s.attrs["background"] for s in built) > 32


def test_reservations_dominate_and_take_the_pass_regime(pooled):
    _, _, _, rec = pooled
    dispatch = [s.attrs for s in rec.spans if s.name == "scheduler.dispatch"]
    passes = [d for d in dispatch if d["regime"] == "pass"]
    assert passes
    assert all(d["reserved_rows"] > d["rows_real"] - d["reserved_rows"]
               for d in passes)
    assert {d["rows_padded"] for d in dispatch} >= {16, 48}


def _objective(req, tiers):
    resv, _ = TabuPolicy._reservations(req)
    return simulate(req.shifted, tiers,
                    machines_per_tier=req.machines_per_tier,
                    busy_until=req.busy, reserved=resv).weighted_sum


def test_every_answer_is_a_one_move_optimum(pooled):
    _, _, answers, _ = pooled
    assert any(req.background for req, _ in answers)
    for req, tiers in answers:
        base = _objective(req, tiers)
        for k, cur in enumerate(tiers):
            for t in TIERS:
                if t != cur:
                    moved = list(tiers)
                    moved[k] = t
                    assert _objective(req, moved) >= base - 1e-9, \
                        (req.ward, k, cur, t)


def test_committed_schedules_are_feasible(pooled):
    traces, res, _, _ = pooled
    _check_schedule_invariants(res, MPT)
    for trace, sched in zip(traces, res.wards):
        assert sorted(e.job.name for e in sched.entries) == \
            sorted(j.name for j in trace)


def test_a_kept_request_keeps_its_movable_jobs(pooled):
    # a request is the policy's to keep: later arrivals to its ward do
    # not grow the movable list it was built with
    _, _, answers, _ = pooled
    assert any(len(req.movable) > 1 for req, _ in answers)
    for req, tiers in answers:
        assert len(req.movable) == len(req.shifted) == len(tiers)
