"""Plain reference for the placement benchmark.

A straightforward implementation of the placement semantics the program
claims (paper Section V, constraints C1-C5), written from the statement
and not from the program's code, and importing nothing of it:

* a job's data reaches tier t at release + trans[t]; the private device
  never queues (one device per patient);
* each shared pool (the cloud, a ward's edge) runs one job per machine
  at a time, non-preemptively, first come first served by arrival (ties:
  release, then the instance's own jobs before background, then input
  order); a job takes the machine that frees earliest and starts at the
  later of its arrival and that machine's free time;
* machines may start busy (free times at the decision instant), and
  background work (other wards' queued cloud jobs) queues like a job
  that cannot be moved;
* the objective is the weighted response, sum of weight x (end -
  release), over the jobs and the background.

On top of the evaluator: the widest improvement that moving one job to
another tier makes to a plan (a converged Algorithm 2 search leaves
none), a plain Algorithm 2 local search (used only as the lower-precision
control), and the checks of an engine's committed schedules.

Every evaluator takes `rnd`, a rounding applied to each stored value and
each arithmetic result: `None` computes in float64 with exactly rounded
sums (the reference), `to_bf16` computes in bfloat16 (the control).
"""
from __future__ import annotations

import heapq
import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

TIERS = ("cloud", "edge", "device")
SHARED = ("cloud", "edge")

Row = Tuple[float, float, Tuple[float, float, float],
            Tuple[float, float, float]]      # release, weight, proc, trans
Rnd = Optional[Callable[[float], float]]


def to_bf16(x: float) -> float:
    """Round a float to the nearest bfloat16, ties to even."""
    import ml_dtypes
    import numpy as np
    return float(np.float32(x).astype(ml_dtypes.bfloat16))


def row(job) -> Row:
    """A job's row from any object with `release`, `weight` and per-tier
    `proc` / `trans` mappings keyed by tier name."""
    return (float(job.release), float(job.weight),
            tuple(float(job.proc[t]) for t in TIERS),
            tuple(float(job.trans.get(t, 0.0)) for t in TIERS))


@dataclass
class Problem:
    """One planning instance: the jobs to place, each shared pool's
    machine count and initial free times, and immovable background work
    per shared pool as (arrival, proc, release, weight)."""
    jobs: List[Row]
    machines: Dict[str, int]
    free0: Dict[str, List[float]] = field(default_factory=dict)
    background: Dict[str, List[Tuple[float, float, float, float]]] = \
        field(default_factory=dict)

    def frees(self, tier: str) -> List[float]:
        """Per-machine free times at the start: the listed busy machines,
        the rest free from time 0."""
        busy = sorted(self.free0.get(tier, ()))
        m = int(self.machines[tier])
        if len(busy) > m:
            raise ValueError(f"{len(busy)} busy {tier} machines of {m}")
        return [0.0] * (m - len(busy)) + busy


def _r(x: float, rnd: Rnd) -> float:
    return x if rnd is None else rnd(x)


def _total(terms: Sequence[float], rnd: Rnd) -> float:
    if rnd is None:
        return math.fsum(terms)
    acc = 0.0
    for x in terms:
        acc = rnd(acc + x)
    return acc


def job_ends(p: Problem, assign: Sequence[str], rnd: Rnd = None
             ) -> Tuple[List[float], Dict[str, List[float]]]:
    """End time of every job under `assign`, and of every background
    item per pool (input order)."""
    if len(assign) != len(p.jobs):
        raise ValueError(f"{len(assign)} tiers for {len(p.jobs)} jobs")
    ends = [0.0] * len(p.jobs)
    bg_ends: Dict[str, List[float]] = {}
    for i, (t, (rel, _, proc, trans)) in enumerate(zip(assign, p.jobs)):
        if t not in TIERS:
            raise ValueError(f"job {i}: unknown tier {t!r}")
        if t == "device":
            arr = _r(_r(rel, rnd) + _r(trans[2], rnd), rnd)
            ends[i] = _r(arr + _r(proc[2], rnd), rnd)
    for m, tier in enumerate(SHARED):
        queue = [(_r(_r(rel, rnd) + _r(trans[m], rnd), rnd), _r(rel, rnd),
                  0, i, _r(proc[m], rnd))
                 for i, (t, (rel, _, proc, trans))
                 in enumerate(zip(assign, p.jobs)) if t == tier]
        bg = p.background.get(tier, ())
        queue += [(_r(a, rnd), _r(rel, rnd), 1, k, _r(pr, rnd))
                  for k, (a, pr, rel, _) in enumerate(bg)]
        queue.sort()
        free = [_r(f, rnd) for f in p.frees(tier)] if queue else []
        heapq.heapify(free)
        out = [0.0] * len(bg)
        for arr, _, kind, i, proc in queue:
            start = max(arr, heapq.heappop(free))
            end = _r(start + proc, rnd)
            heapq.heappush(free, end)
            if kind == 0:
                ends[i] = end
            else:
                out[i] = end
        bg_ends[tier] = out
    return ends, bg_ends


def objective(p: Problem, assign: Sequence[str], rnd: Rnd = None) -> float:
    """Weighted response of the jobs and the background under `assign`."""
    ends, bg_ends = job_ends(p, assign, rnd)
    terms = [_r(_r(w, rnd) * _r(e - _r(rel, rnd), rnd), rnd)
             for (rel, w, _, _), e in zip(p.jobs, ends)]
    for tier in SHARED:
        for (_, _, rel, w), e in zip(p.background.get(tier, ()),
                                     bg_ends.get(tier, ())):
            terms.append(_r(_r(w, rnd) * _r(e - _r(rel, rnd), rnd), rnd))
    return _total(terms, rnd)


def move_gap(p: Problem, assign: Sequence[str]) -> float:
    """The widest share of the objective that moving one job to another
    tier would save, in float64: 0 for a plan no single move improves."""
    base = objective(p, assign)
    best = 0.0
    trial = list(assign)
    for k, cur in enumerate(assign):
        for t in TIERS:
            if t == cur:
                continue
            trial[k] = t
            best = max(best, base - objective(p, trial))
        trial[k] = cur
    return best / base if base > 0 else (1.0 if best > 0 else 0.0)


def local_search(p: Problem, initial: Sequence[str], max_rounds: int,
                 rnd: Rnd = None) -> List[str]:
    """Paper Algorithm 2 over a full re-evaluation per move: each round
    takes the jobs in order of completion, moves each to the tier that
    lowers the objective most if any does, and stops after a round with
    no move or after `max_rounds` rounds."""
    assign = list(initial)
    best = objective(p, assign, rnd)
    for _ in range(max_rounds):
        moved = False
        tabu = [False] * len(assign)
        for _ in range(len(assign)):
            ends, _ = job_ends(p, assign, rnd)
            cand = [i for i in range(len(assign)) if not tabu[i]]
            if not cand:
                break
            k = min(cand, key=lambda i: (ends[i], i))
            tabu[k] = True
            cur, pick = assign[k], None
            for t in TIERS:
                if t == cur:
                    continue
                assign[k] = t
                v = objective(p, assign, rnd)
                if v < best:
                    best, pick = v, t
            assign[k] = pick or cur
            moved |= pick is not None
        if not moved:
            break
    return assign


# --------------------------------------------------- committed schedules
def schedule_violations(trace: Sequence, entries: Sequence[Tuple],
                        dropped: set) -> List[str]:
    """What is wrong with one ward's committed schedule, as text.

    trace: the ward's jobs (objects with name, release, proc); entries:
    (name, tier, arrival, start, end) per scheduled job; dropped: names
    the engine recorded as shed. Every job runs exactly its processing
    time on its tier (no fail-slow window exists). How many jobs a pool
    runs at once is `over_capacity`'s to check."""
    out: List[str] = []
    jobs = {j.name: j for j in trace}
    seen: Dict[str, int] = {}
    for name, tier, arr, start, end in entries:
        seen[name] = seen.get(name, 0) + 1
        job = jobs.get(name)
        if job is None:
            out.append(f"{name}: not in the trace")
            continue
        if tier not in TIERS:
            out.append(f"{name}: unknown tier {tier!r}")
            continue
        proc = float(job.proc[tier])
        if arr < job.release or start < arr:
            out.append(f"{name}: release {job.release} arrival {arr} "
                       f"start {start}")
        served = end - start
        if abs(served - proc) > 1e-9 * max(1.0, proc):
            out.append(f"{name}: ran {served} on {tier}, needs {proc}")
    missing = [n for n in jobs if n not in seen and n not in dropped]
    twice = [n for n, c in seen.items() if c > 1]
    if missing:
        out.append(f"{len(missing)} jobs neither scheduled nor shed, "
                   f"e.g. {missing[:3]}")
    if twice:
        out.append(f"scheduled more than once: {twice[:3]}")
    return out


def over_capacity(intervals: Sequence[Tuple[float, float]],
                  machines: int) -> int:
    """How many job starts find more jobs running on a pool than it has
    machines. Intervals are (start, end) of work on the pool; a job that
    ends at an instant frees its machine for one that starts then."""
    events = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    running, bad = 0, 0
    for _, d in events:
        running += d
        if d > 0 and running > machines:
            bad += 1
    return bad
