"""Runner for metro replan mixes: closed-loop replays of one seeded trace
through the program's event engine, back to back.

Set-up builds the trace from the seed and replays it once in full, which
compiles (or loads) every program the window uses. The window replays
the same trace again and again through `repro.metro.simulate_metro`,
with the configuration's policy from `repro.metro.make_policy` wrapped
in a timing policy: each `decide` call is one decision, timed from the
request being handed to the policy to its plan coming back (as host
lists, so the device has finished). The window closes with the first
replay that ends after `seconds`, so it holds whole replays only.

`correct` compares two things with the plain reference
(bench/reference.py):

* `plan_gap`: every answer `decide` returned in the window. Each is
  priced on its own replan subproblem (the ward's movable jobs, the busy
  machines, other wards' queued cloud jobs as background), and the
  number is the widest share of the objective that moving one job would
  still save. A converged search leaves none.
* `commit_gap`: the schedules the engine committed in every replay of
  the run. Each must be feasible (every job scheduled once or shed, data
  arrives before it runs, each job runs its full time, no pool runs more
  jobs at once than it has machines), and the engine's weighted response
  per ward must equal the reference's sum over the committed entries. An
  infeasible schedule reads 1.

The runner replays ward traces alone, with no fleet events (failures,
scale steps, fail-slow or network windows).
"""
from __future__ import annotations

import math
import time
from typing import List

from bench import reference as ref
from bench.common import span
from bench.traffic import gen


class TimedPolicy:
    """The configuration's policy as the engine sees it, timing each
    `decide` call and keeping its answers; other attributes pass
    through."""

    def __init__(self, inner, log: list, answers: list, annotate: bool):
        self.inner = inner
        self.log = log
        self.answers = answers
        self.annotate = annotate

    def __getattr__(self, key):
        return getattr(self.inner, key)

    def decide(self, requests, now):
        t0 = time.perf_counter()
        with span("decide", self.annotate):
            out = self.inner.decide(requests, now)
        self.log.append((t0, time.perf_counter()))
        self.answers.extend((req, list(tiers))
                            for req, tiers in zip(requests, out))
        return out


def problem(req) -> ref.Problem:
    """A replan request's subproblem: its movable jobs (shifted to the
    decision instant), the shared machines still busy, and the other
    wards' queued cloud jobs as background."""
    bg = [(s.release + s.trans.get("cloud", 0.0), s.proc["cloud"],
           s.release, s.weight) for s in req.background]
    return ref.Problem(
        jobs=[ref.row(s) for s in req.shifted],
        machines=dict(req.machines_per_tier),
        free0={t: list(req.busy.get(t, ())) for t in ref.SHARED},
        background={"cloud": bg} if bg else {})


class Cell:
    idle_label = "engine"

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg = cfg
        self.traces = gen.stream(cfg, mix, seed)
        self.machines = dict(cfg["machines_per_tier"])
        self.results: list = []          # every replay of the run
        self.answers: list = []          # (request, tiers) in the window

    def _replay(self, log: list, answers: list, annotate: bool):
        from repro.metro import make_policy, simulate_metro
        kw = dict(self.cfg["replan_policy"])
        policy = TimedPolicy(make_policy(kw.pop("name"), **kw), log,
                             answers, annotate)
        self.results.append(simulate_metro(
            self.traces, policy=policy, machines_per_tier=self.machines))

    def setup(self) -> None:
        self._replay([], [], False)

    def window(self, seconds: float, annotate: bool) -> dict:
        log: List[tuple] = []
        t0 = time.perf_counter()
        with span("window", annotate):
            while True:
                self._replay(log, self.answers, annotate)
                t_end = time.perf_counter()
                if t_end - t0 >= seconds:
                    break
        return {"seconds": t_end - t0,
                "decide_s": [b - a for a, b in log],
                "decisions": len(log)}

    # ------------------------------------------------------- correctness
    def _plan_gaps(self, plan_of, answers=None) -> List[float]:
        gaps = []
        for req, tiers in self.answers if answers is None else answers:
            if len(tiers) != len(req.shifted) or \
                    any(t not in ref.TIERS for t in tiers):
                gaps.append(1.0)
                continue
            p = problem(req)
            gaps.append(ref.move_gap(p, plan_of(req, p, tiers)))
        return gaps

    def _commit_gap(self, res, rnd=None) -> float:
        dropped = {rec[4] for rec in res.event_log
                   if rec[0] in ("shed", "giveup")}
        worst, cloud = 0.0, []
        for trace, sched in zip(self.traces, res.wards):
            entries = [(e.job.name, e.machine, e.arrival, e.start, e.end)
                       for e in sched.entries]
            if ref.schedule_violations(trace, entries, dropped) or \
                    ref.over_capacity([(s, e) for _, t, _, s, e in entries
                                       if t == "edge"],
                                      self.machines["edge"]):
                return 1.0
            cloud += [(s, e) for _, t, _, s, e in entries if t == "cloud"]
            jobs = {j.name: j for j in trace}
            want = math.fsum(jobs[n].weight * (e - jobs[n].release)
                             for n, _, _, _, e in entries)
            got = sched.weighted_sum if rnd is None else ref._total(
                [rnd(rnd(jobs[n].weight) * rnd(rnd(e) - rnd(
                    jobs[n].release))) for n, _, _, _, e in entries], rnd)
            worst = max(worst, abs(got - want) / max(abs(want), 1.0))
        if ref.over_capacity(cloud, self.machines["cloud"]):
            return 1.0
        return worst

    def check(self) -> dict:
        return {"plan_gap": max(self._plan_gaps(
                    lambda req, p, tiers: tiers), default=0.0),
                "commit_gap": max(self._commit_gap(r)
                                  for r in self.results)}

    def control(self, answers: int | None = None) -> dict:
        """The same numbers with the reference, computed in bfloat16, in
        the program's place: its Algorithm 2 search, from the program's
        starting tiers, for each of the first `answers` requests of the
        window (all by default), and its accounting of each committed
        schedule."""
        rounds = int(self.cfg["replan_policy"].get("max_count", 5))

        def plan(req, p, tiers):
            init = [t if t is not None else "device" for t in req.current]
            return ref.local_search(p, init, rounds, rnd=ref.to_bf16)
        return {"plan_gap": max(self._plan_gaps(
                    plan, self.answers[:answers]), default=0.0),
                "commit_gap": max(self._commit_gap(r, rnd=ref.to_bf16)
                                  for r in self.results)}
