"""Scheduler benchmarks beyond the paper's scale.

Head-to-head Algorithm-2 implementations (the repo's single hottest path):

  * reference — the seed full-re-simulation Python tabu search
    (scheduler.neighborhood_search_reference), O(rounds * n^2) simulations;
  * incremental — the ScheduleState-backed tabu search
    (scheduler.neighborhood_search), O(two queues) per candidate move;
  * jax — the fully jitted neighbourhood search
    (scheduler_jax.tabu_search_batched on one instance), one n x 3
    neighbourhood evaluation per lax.while_loop round, no host syncs.

Also: JAX batched-evaluation throughput, heuristic optimality gap,
fleet-scale batched planning throughput in wards/sec (``batched`` section:
scheduler_jax.tabu_search_batched vs the sequential per-instance
`scheduler.search` loop, DESIGN.md §8), cross-ward shared-cloud contention
(``contention`` section: the double-booking gap of independent per-ward
plans on the fleet-true evaluator and how much of it the fixed-point
`scheduler.search_fleet` recovers, DESIGN.md §9), and the online
(non-clairvoyant) competitive ratio — including, behind ``--online``, per-arrival-scenario
ratios (poisson steady-state / ER-surge burst / nightly-quiet,
core.problems.ONLINE_SCENARIOS) on single- and multi-server fleets, whose
clairvoyant baselines are planned by one batched call per sweep. Results
are printed as the harness CSV and written machine-readable to
BENCH_scheduler.json so the perf trajectory is tracked across PRs —
benchmarks/check_regression.py gates on those floors.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core import scheduler, scheduler_jax
from repro.core.simulator import MACHINES, JobSpec, simulate
from repro.core.tiers import CC, ED, ES

BENCH_JSON = os.environ.get("BENCH_SCHEDULER_JSON", "BENCH_scheduler.json")
# the seed path is O(rounds * n^2) full simulations — unusable beyond this
REFERENCE_N_CAP = 100


def _random_jobs(rng, n):
    jobs = []
    for i in range(n):
        jobs.append(JobSpec(
            name=f"J{i}", release=float(rng.integers(0, 50)),
            weight=float(rng.integers(1, 3)),
            proc={t: float(rng.integers(1, 30)) for t in MACHINES},
            trans={CC: float(rng.integers(0, 60)),
                   ES: float(rng.integers(0, 15)), ED: 0.0}))
    return jobs


def _time(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def bench_head_to_head(sizes=(10, 100, 1000), max_count=5):
    """Python tabu (seed) vs incremental tabu vs jitted tabu, fixed seeds.

    Returns a list of per-(n, method) records with seconds, weighted
    objective, and speedup vs the reference path.
    """
    records = []
    for n in sizes:
        jobs = _random_jobs(np.random.default_rng(0), n)
        row = {"n": n, "max_count": max_count, "methods": {}}

        if n <= REFERENCE_N_CAP:
            dt, s = _time(lambda: scheduler.neighborhood_search_reference(
                jobs, max_count=max_count))
            row["methods"]["reference"] = {
                "seconds": dt, "weighted": s.weighted_sum}
        else:
            row["methods"]["reference"] = {
                "seconds": None, "weighted": None,
                "note": f"skipped: O(rounds*n^2) simulations at n={n}"}

        dt, s = _time(lambda: scheduler.neighborhood_search(
            jobs, max_count=max_count))
        row["methods"]["incremental"] = {
            "seconds": dt, "weighted": s.weighted_sum}

        # compile outside the timed region: the jitted search is reused
        # across replans of the same instance size in serving
        scheduler_jax.tabu_search_batched([jobs], max_rounds=1)
        dt, (_, (a,)) = _time(lambda: scheduler_jax.tabu_search_batched(
            [jobs], max_rounds=max_count))
        # score the returned assignment with the exact (float64) simulator
        # so all three methods' objectives share one evaluator
        exact = simulate(jobs, [MACHINES[int(i)] for i in a])
        row["methods"]["jax"] = {"seconds": dt,
                                 "weighted": exact.weighted_sum}

        ref = row["methods"]["reference"]["seconds"]
        for name, m in row["methods"].items():
            m["speedup_vs_reference"] = (
                ref / m["seconds"] if ref and m["seconds"] else None)
        records.append(row)
    return records


def bench_online_scenarios(seeds=6, n=20):
    """Competitive ratio (online / clairvoyant-offline) per arrival
    scenario and fleet shape. The clairvoyant baselines for a scenario's
    whole seed sweep are planned in ONE batched device call
    (online.competitive_ratio_batch -> scheduler.search_batched), shared
    by both replan modes."""
    from repro.core import online
    from repro.core.problems import ONLINE_SCENARIOS

    out = {}
    for scen, gen in ONLINE_SCENARIOS.items():
        out[scen] = {}
        for fleet, mpt in (("c1e1", {CC: 1, ES: 1}),
                           ("c2e3", {CC: 2, ES: 3})):
            instances = [gen(np.random.default_rng(1000 + seed), n=n)
                         for seed in range(seeds)]
            ratios = online.competitive_ratio_batch(
                instances, replans=("greedy", "tabu"),
                machines_per_tier=mpt)
            out[scen][fleet] = {
                replan: {"mean": float(np.mean(r)), "max": float(np.max(r))}
                for replan, r in ratios.items()}
    return out


def bench_batched(wards=32, n=100, max_count=5, repeats=3):
    """Fleet-scale planning throughput (wards/sec): one batched device
    call (tabu_search_batched) vs the sequential per-instance loop the
    repo used before the batched subsystem existed (`scheduler.search`
    per ward — on CPU that's the incremental Python path; also timed: a
    loop of per-instance (B = 1) jitted searches). Both sides are measured
    best-of-`repeats` after a warm-up call so jit compiles and load
    spikes don't skew the ratio. Batched-vs-per-instance disagreements
    after exact re-simulation are recorded as ``parity_mismatches``
    (benchmarks/check_regression.py fails on any nonzero value; the test
    suite's parity sweeps guard the same invariant)."""
    from repro.core import scheduler_jax

    instances = [_random_jobs(np.random.default_rng(3000 + i), n)
                 for i in range(wards)]
    max_rounds = max_count

    def _best_of(fn):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    scheduler_jax.tabu_search_batched(instances, max_rounds=1)   # compile
    scheduler_jax.tabu_search_batched(instances[:1], max_rounds=1)
    t_batched, (_, assigns_b) = _best_of(
        lambda: scheduler_jax.tabu_search_batched(
            instances, max_rounds=max_rounds))
    t_jax_loop, assigns_s = _best_of(lambda: [
        scheduler_jax.tabu_search_batched([jobs], max_rounds=max_rounds)[1][0]
        for jobs in instances])
    t_search_loop, _ = _best_of(lambda: [
        scheduler.search(jobs, max_count=max_count) for jobs in instances])

    # batched == per-instance, re-scored through the exact simulator
    mismatches = sum(
        simulate(jobs, [MACHINES[int(i)] for i in ab]).weighted_sum
        != simulate(jobs, [MACHINES[int(i)] for i in asolo]).weighted_sum
        for jobs, ab, asolo in zip(instances, assigns_b, assigns_s))
    return {
        "wards": wards, "n": n, "max_count": max_count,
        "seconds_batched": t_batched,
        "seconds_sequential_search_loop": t_search_loop,
        "seconds_sequential_jax_loop": t_jax_loop,
        "wards_per_s_batched": wards / t_batched,
        "wards_per_s_sequential": wards / t_search_loop,
        "speedup_batched_vs_sequential": t_search_loop / t_batched,
        "parity_mismatches": int(mismatches),
    }


def bench_contention(wards=32, n=100, cloud_machines=4, edge_machines=2,
                     max_count=5, max_sweeps=4):
    """Cross-ward shared-cloud contention (DESIGN.md §9): how badly B
    independent per-ward plans double-book the metropolitan cloud
    (``contention_gap`` — fleet-true / claimed objective of the naive
    plans, > 1 when overcommitted), how much of that gap the fixed-point
    `scheduler.search_fleet` recovers (``gap_closed``), how many sweeps
    convergence takes, and the contention-aware planning throughput in
    wards/sec. Jobs come from `problems.metro_jobs` (the paper's Table VI
    cost regime — cloud fast but far), the regime where every ward
    really loads the shared cloud.

    Sweeps are pinned to the incremental Python backend so this
    section's committed floors keep measuring the same code path now
    that ``sweep_backend="auto"`` takes the batched kernel on CPU too —
    the kernel path gets its own section, `bench_contention_interval`
    (DESIGN.md §12)."""
    from repro.core.problems import metro_jobs

    instances = [metro_jobs(np.random.default_rng(5000 + i), n=n)
                 for i in range(wards)]
    mpt = {CC: cloud_machines, ES: edge_machines}
    # warm the naive batched search's compile cache at the real shape
    # (max_sweeps=0: the Python sweeps have nothing to compile)
    scheduler.search_fleet(instances, machines_per_tier=mpt,
                           max_count=1, max_sweeps=0,
                           sweep_backend="python")
    t0 = time.perf_counter()
    plan = scheduler.search_fleet(instances, machines_per_tier=mpt,
                                  max_count=max_count,
                                  max_sweeps=max_sweeps,
                                  sweep_backend="python")
    seconds = time.perf_counter() - t0
    return {
        "wards": wards, "n": n,
        "cloud_machines": cloud_machines, "edge_machines": edge_machines,
        "max_count": max_count, "max_sweeps": max_sweeps,
        "naive_reported": plan.naive_reported,
        "naive_fleet_true": plan.naive_fleet.weighted_sum,
        "fleet_true": plan.fleet.weighted_sum,
        "contention_gap": plan.contention_gap,
        "gap_closed": plan.gap_closed,
        "improvement_vs_naive": plan.naive_fleet.weighted_sum
        / max(plan.fleet.weighted_sum, 1e-9),
        "sweeps": plan.sweeps,
        "seconds": seconds,
        "wards_per_s": wards / seconds,
    }


def bench_contention_interval(wards=32, n=100, cloud_machines=4,
                              edge_machines=2, max_count=5, max_sweeps=4):
    """The §12 interval-reservation fleet path: `search_fleet` with its
    defaults — interval background, batched Gauss–Seidel sweeps on CPU
    too — on the exact fleet `bench_contention` times through the pinned
    Python sweeps.

    Guarded: planning throughput (wards/s — the tentpole's >= 10x over
    the pre-interval floor), the recovered gap, and
    ``fraction_of_batched``: this path's throughput as a fraction of ONE
    independent §8 `search_batched` call over the same fleet, timed
    in-section (so ``--runs N`` re-times both sides together) — the
    "fleet sweeps at §8 batched speeds" claim as a committed ratio.
    ``parity_with_phantom`` is a hard invariant downstream: the interval
    plan must reproduce the frozen-phantom construction's plan
    bit-identically, or strictly beat its fleet-true objective.
    ``compiled_programs`` counts the programs the device search's jitted
    entry compiled after the warm-up, over the timed and parity runs: the
    §12 bucketing contract makes those all hits, and `check_regression.py`
    fails when the count grows past the committed one."""
    from repro.core.problems import metro_jobs

    instances = [metro_jobs(np.random.default_rng(5000 + i), n=n)
                 for i in range(wards)]
    mpt = {CC: cloud_machines, ES: edge_machines}
    # warm BOTH compiled shapes: the naive batched search at (B, n) and
    # the batched sweep at the padded (jobs + reservations) row bucket —
    # the same naive incumbent (same seeds, same max_count) yields the
    # same first-sweep background, so the warmed bucket is the timed one
    scheduler.search_fleet(instances, machines_per_tier=mpt,
                           max_count=max_count, max_sweeps=1)
    programs0 = scheduler_jax._tabu_run_packed._cache_size()
    t0 = time.perf_counter()
    plan = scheduler.search_fleet(instances, machines_per_tier=mpt,
                                  max_count=max_count,
                                  max_sweeps=max_sweeps)
    seconds = time.perf_counter() - t0
    # the independent §8 floor on this host: one batched search over the
    # same fleet (compiled already — the naive stage above uses it)
    t0 = time.perf_counter()
    scheduler.search_batched(instances, machines_per_tier=mpt,
                             max_count=max_count)
    t_indep = time.perf_counter() - t0
    phantom = scheduler.search_fleet(instances, machines_per_tier=mpt,
                                     max_count=max_count,
                                     max_sweeps=max_sweeps,
                                     background="phantom")
    parity = plan.assignments == phantom.assignments \
        or plan.fleet.weighted_sum < phantom.fleet.weighted_sum
    return {
        "wards": wards, "n": n,
        "cloud_machines": cloud_machines, "edge_machines": edge_machines,
        "max_count": max_count, "max_sweeps": max_sweeps,
        "naive_reported": plan.naive_reported,
        "naive_fleet_true": plan.naive_fleet.weighted_sum,
        "fleet_true": plan.fleet.weighted_sum,
        "contention_gap": plan.contention_gap,
        "gap_closed": plan.gap_closed,
        "improvement_vs_naive": plan.naive_fleet.weighted_sum
        / max(plan.fleet.weighted_sum, 1e-9),
        "sweeps": plan.sweeps,
        "seconds": seconds,
        "wards_per_s": wards / seconds,
        "seconds_independent_batched": t_indep,
        "fraction_of_batched": t_indep / seconds,
        "phantom_fleet_true": phantom.fleet.weighted_sum,
        "parity_with_phantom": bool(parity),
        "compiled_programs":
        scheduler_jax._tabu_run_packed._cache_size() - programs0,
    }


def bench_metro(wards=4, hours=2.0, seed=0):
    """Streaming metro traffic (DESIGN.md §10): the canonical scenario
    (`metro.traces.default_scenario` — diurnal + surge arrivals, cloud
    failures, elastic capacity) replayed under the greedy, tabu-replan
    and fleet fixed-point policies on identical traces. Guarded metrics:
    engine throughput in events/s (the tabu run — the replanning hot
    path) and the tabu-vs-greedy deadline miss-rate improvement, which
    `check_regression.py` additionally requires to stay strictly > 1
    (replanning must actually beat commit-and-hold)."""
    from repro.launch.serve import run_metro

    out = run_metro(wards=wards, hours=hours, seed=seed, verbose=False)
    g, t, f = out["greedy"], out["tabu"], out["fleet"]
    # improvement is vacuous when greedy is already perfect (None, so the
    # gate skips it rather than hard-failing a flawless run), and a
    # perfect tabu run is floored at half-a-missed-job so one committed
    # baseline can't demand a near-infinite ratio forever after
    g_miss, t_miss = g["miss_rate"], t["miss_rate"]
    improvement = None if g_miss == 0 else \
        g_miss / max(t_miss, 0.5 / max(g["completions"], 1))
    return {
        "wards": wards, "hours": hours, "seed": seed,
        "jobs": g["completions"],
        "events_tabu": t["events"],
        "events_per_s": t["events_per_s"],
        "miss_rate_greedy": g_miss,
        "miss_rate_tabu": t_miss,
        "miss_rate_fleet": f["miss_rate"],
        "miss_rate_improvement": improvement,
        "p50": {k: v["p50"] for k, v in out.items()},
        "p99": {k: v["p99"] for k, v in out.items()},
        "utilization_tabu": t["utilization"],
    }


CHAOS_PACKS = ("edge_brownout", "mass_casualty_crash",
               "degraded_network", "diurnal_day")


def _ratio(base, other, completions):
    """miss-rate improvement `base/other` with bench_metro's semantics:
    None (vacuous) when the baseline is already perfect, the divisor
    floored at half a missed job so a perfect run can't demand a
    near-infinite ratio forever after."""
    return None if base == 0 else \
        base / max(other, 0.5 / max(completions, 1))


def bench_metro_scenarios(packs=CHAOS_PACKS, seed=0):
    """Chaos scenario packs (DESIGN.md §11): every registered pack
    replayed at its canonical shape under greedy, tabu-replan and the
    shedding wrapper on identical traces/failures/network windows.

    Guarded per pack: engine throughput (events/s, tabu), the
    tabu-vs-greedy miss-rate improvement, and the shed policy's
    life-critical miss-rate improvement vs greedy (the admission-control
    claim: sacrificing a bounded share of the lowest-weight class must
    protect the life-critical SLA). The search backend is pinned to the
    Python path, so the committed numbers come from one backend on any
    platform."""
    from repro.launch.serve import run_metro

    out = {}
    for pack in packs:
        res = run_metro(seed=seed, scenario=pack,
                        policies=("greedy", "tabu", "shed"),
                        verbose=False, jax_threshold=10 ** 9)
        g, t, sh = res["greedy"], res["tabu"], res["shed"]
        out[pack] = {
            "seed": seed,
            "jobs": g["completions"] + g["shed"],
            "events_per_s": t["events_per_s"],
            "miss_rate_greedy": g["miss_rate"],
            "miss_rate_tabu": t["miss_rate"],
            "miss_rate_shed": sh["miss_rate"],
            "miss_rate_improvement": _ratio(
                g["miss_rate"], t["miss_rate"], g["completions"]),
            "critical_miss_greedy": g["critical_miss_rate"],
            "critical_miss_shed": sh["critical_miss_rate"],
            "critical_improvement_shed": _ratio(
                g["critical_miss_rate"], sh["critical_miss_rate"],
                g["completions"]),
            "shed_rate_shed": sh["shed_rate"],
            "retries_tabu": t["retries"],
            "wasted_machine_seconds_tabu": t["wasted_machine_seconds"],
            "max_attempts_tabu": t["max_attempts"],
            "event_log_hash_tabu": t["event_log_hash"],
        }
    return out


def bench_metro_hedging(seed=0):
    """Tail-tolerant hedging under fail-slow machines (DESIGN.md §13):
    the `fail_slow_tail` pack — deep slowdown windows crawling the ward
    edge pools at 3-8% speed, cloud healthy — replayed under tabu-replan
    with and without the deadline-aware hedging wrapper on identical
    traces and slowdown windows.

    Guarded: engine throughput of the hedged run (events/s) and two
    ratios `check_regression.py` holds as HARD ranking invariants at any
    tolerance — the hedged run must strictly beat the unhedged run on
    both the life-critical miss rate (`critical_improvement_hedge`) and
    the p99 response (`p99_improvement_hedge`). The search backend is
    pinned to the Python path, so the committed numbers come from one
    backend on any platform."""
    from repro.launch.serve import run_metro

    def one(hedged):
        return run_metro(seed=seed, scenario="fail_slow_tail",
                         policies=("tabu",), verbose=False,
                         jax_threshold=10 ** 9, hedge=hedged)["tabu"]

    base, hedged = one(False), one(True)
    return {
        "seed": seed,
        "jobs": hedged["completions"] + hedged["shed"],
        "events_per_s": hedged["events_per_s"],
        "critical_miss_unhedged": base["critical_miss_rate"],
        "critical_miss_hedged": hedged["critical_miss_rate"],
        "critical_improvement_hedge": _ratio(
            base["critical_miss_rate"], hedged["critical_miss_rate"],
            base["completions"]),
        "p99_unhedged": base["p99"],
        "p99_hedged": hedged["p99"],
        "p99_improvement_hedge": base["p99"] / hedged["p99"],
        "p999_unhedged": base["p999"],
        "p999_hedged": hedged["p999"],
        "hedges": hedged["hedges"],
        "hedge_wins": hedged["hedge_wins"],
        "hedge_rate": hedged["hedge_rate"],
        "hedge_waste": hedged["hedge_waste"],
        "event_log_hash_unhedged": base["event_log_hash"],
        "event_log_hash_hedged": hedged["event_log_hash"],
    }


def bench_metro_observability(seed=0):
    """Flight-recorder cost + parity (DESIGN.md §15): every chaos pack
    replayed twice under tabu-replan (hedged on `fail_slow_tail`, whose
    races exercise the hedge spans) — once untraced, once with the
    tracer armed — on identical traces/failures/windows.

    Guarded: per-pack ``crc_parity`` (the traced run's event log must
    hash bit-identically to the untraced run's — the tracer is a
    read-only observer; a HARD invariant in check_regression.py) and
    the aggregate ``events_per_s_retention`` (traced throughput as a
    fraction of untraced over all packs), which the gate holds above
    1/1.15: the armed recorder may cost at most 15%. The search backend
    is pinned to the Python path, so the numbers come from one backend
    on any platform."""
    import zlib

    from repro.metro import (HedgingPolicy, make_policy, simulate_metro,
                             traces)

    packs = CHAOS_PACKS + ("fail_slow_tail",)
    mpt = {CC: 2, ES: 2}
    out = {"seed": seed, "packs": {}}
    sec_untraced = sec_traced = events_total = 0.0
    spans_total = 0
    for pack in packs:
        sc = traces.make_scenario(pack, seed)
        hedged = pack == "fail_slow_tail"

        def one(traced):
            pol = make_policy("tabu", jax_threshold=10 ** 9)
            kw = {}
            if hedged:
                pol = HedgingPolicy(inner=pol)
                kw["hedge_factor"] = 1.5
            return simulate_metro(
                sc.traces, pol, machines_per_tier=mpt,
                failures=sc.failures, scale_events=sc.scales,
                network_events=sc.network, slowdowns=sc.slowdowns,
                trace=traced, **kw)

        one(False)      # warm-up: first replay of a pack pays cold-start
        base, traced = one(False), one(True)
        sb, st = base.summary(), traced.summary()
        parity = zlib.crc32(repr(base.event_log).encode()) \
            == zlib.crc32(repr(traced.event_log).encode())
        out["packs"][pack] = {
            "hedged": hedged,
            "jobs": st["completions"] + st["shed"],
            "events": st["events"],
            "spans": len(traced.trace.spans),
            "crc_parity": bool(parity),
            "events_per_s_untraced": sb["events_per_s"],
            "events_per_s_traced": st["events_per_s"],
            "retention": st["events_per_s"] / sb["events_per_s"],
        }
        events_total += st["events"]
        spans_total += len(traced.trace.spans)
        sec_untraced += sb["events"] / sb["events_per_s"]
        sec_traced += st["events"] / st["events_per_s"]
    out.update(
        events=int(events_total), spans=spans_total,
        crc_parity_all=all(p["crc_parity"] for p in out["packs"].values()),
        events_per_s_retention=sec_untraced / sec_traced)
    return out


def bench_online_fleet(seeds=3, wards=4, n=10, cloud_machines=2,
                       edge_machines=2):
    """Online fleet replanning vs the clairvoyant fixed point
    (`online.competitive_ratio_fleet`, DESIGN.md §9 follow-up): the
    price of event-by-event ward-aware replanning against
    `search_fleet`'s fleet-true plan on the same shared cloud, per seed
    over the contention benchmark's `metro_jobs` regime."""
    from repro.core import online
    from repro.core.problems import metro_jobs

    mpt = {CC: cloud_machines, ES: edge_machines}
    runs = []
    for s in range(seeds):
        ward_jobs = [metro_jobs(
            np.random.default_rng(8000 + s * wards + b), n=n, horizon=30.0)
            for b in range(wards)]
        runs.append(online.competitive_ratio_fleet(
            ward_jobs, machines_per_tier=mpt))
    ratios = [r["ratio"] for r in runs]
    return {"wards": wards, "n": n,
            "cloud_machines": cloud_machines,
            "edge_machines": edge_machines,
            "runs": runs,
            "mean_ratio": float(np.mean(ratios)),
            "max_ratio": float(np.max(ratios))}


def bench_scheduler_scale(with_online_scenarios: bool = False,
                          out_path: str | None = None):
    rng = np.random.default_rng(0)
    rows, csv = [], []
    report = {"bench": "scheduler_scale", "backend": jax.default_backend(),
              "head_to_head": [], "eval_throughput": {}, "quality": {},
              "online": {}, "batched": {}, "contention": {},
              "contention_interval": {}, "metro": {}, "metro_hedging": {},
              "metro_observability": {}}

    # 1) Algorithm-2 head-to-head across implementations and scales
    for row in bench_head_to_head():
        report["head_to_head"].append(row)
        n = row["n"]
        for name, m in row["methods"].items():
            if m["seconds"] is None:
                continue
            rows.append((f"tabu_{name}", n, m["seconds"], m["weighted"]))
            speed = m["speedup_vs_reference"]
            csv.append(
                f"sched_tabu_{name}_n{n},{m['seconds']*1e6:.0f},"
                f"weighted={m['weighted']:.0f}"
                + (f";speedup_vs_seed={speed:.1f}x" if speed else ""))

    # 2) JAX batched evaluation throughput (incl. multi-machine tiers)
    jobs = _random_jobs(rng, 50)
    rel, w, proc, trans = scheduler_jax.specs_to_arrays(jobs)
    assigns = jax.numpy.asarray(rng.integers(0, 3, size=(4096, 50)),
                                jax.numpy.int32)
    for mpt in ((1, 1), (4, 2)):
        scheduler_jax.evaluate_assignments(assigns, rel, w, proc, trans,
                                           machines_per_tier=mpt)  # warm
        t0 = time.perf_counter()
        m = scheduler_jax.evaluate_assignments(assigns, rel, w, proc, trans,
                                               machines_per_tier=mpt)
        jax.block_until_ready(m["weighted"])
        dt = time.perf_counter() - t0
        per = dt / 4096 * 1e6
        label = f"c{mpt[0]}e{mpt[1]}"
        rows.append((f"jax_eval_{label}", 4096, dt, per))
        csv.append(f"sched_jax_eval_4096x50_{label},{per:.2f},"
                   f"candidates_per_s={4096/dt:.0f}")
        report["eval_throughput"][label] = {
            "candidates": 4096, "n": 50, "seconds": dt,
            "candidates_per_s": 4096 / dt}

    # 2b) stochastic-search baseline honors the deployed fleet (the seed
    # implementation silently scored every candidate on an idle (1, 1)
    # fleet — regression-guarded by recording the fleet-true objective)
    jobs = _random_jobs(np.random.default_rng(7), 30)
    key = jax.random.PRNGKey(0)
    initial = np.asarray([MACHINES.index(t)
                          for t in scheduler.greedy_schedule(
                              jobs, machines_per_tier={CC: 2, ES: 3})],
                         np.int32)
    v, a = scheduler_jax.stochastic_search(
        jobs, key, initial, iters=50, machines_per_tier=(2, 3))
    exact = simulate(jobs, [MACHINES[int(i)] for i in a],
                     machines_per_tier={CC: 2, ES: 3})
    csv.append(f"sched_stochastic_c2e3_n30,0,"
               f"weighted={exact.weighted_sum:.0f};claimed={v:.0f}")
    report["quality"]["stochastic_c2e3_n30"] = {
        "weighted": exact.weighted_sum, "claimed": v}

    # 3) heuristic optimality gap on small instances
    gaps = []
    for seed in range(5):
        jobs = _random_jobs(np.random.default_rng(seed), 8)
        ours = scheduler.neighborhood_search(jobs)
        v, _ = scheduler_jax.exact_optimum_jax(jobs, objective="weighted")
        gaps.append(ours.weighted_sum / max(v, 1e-9) - 1.0)
    csv.append(f"sched_optimality_gap_n8,0,mean_gap={np.mean(gaps):.2%};"
               f"max_gap={np.max(gaps):.2%}")
    report["quality"]["optimality_gap_n8"] = {
        "mean": float(np.mean(gaps)), "max": float(np.max(gaps))}

    # 4) online (non-clairvoyant) competitive ratio — beyond paper
    from repro.core import online
    ratios_g, ratios_t = [], []
    for seed in range(8):
        jobs = _random_jobs(np.random.default_rng(seed + 100), 12)
        off = scheduler.neighborhood_search(jobs).weighted_sum
        ratios_g.append(online.online_schedule(jobs, replan="greedy")
                        .weighted_sum / max(off, 1e-9))
        ratios_t.append(online.online_schedule(jobs, replan="tabu")
                        .weighted_sum / max(off, 1e-9))
    csv.append(f"sched_online_competitive,0,"
               f"greedy={np.mean(ratios_g):.3f};"
               f"tabu_replan={np.mean(ratios_t):.3f}")
    report["online"] = {"greedy": float(np.mean(ratios_g)),
                        "tabu_replan": float(np.mean(ratios_t))}

    # 5) fleet-scale batched planning throughput (wards/sec)
    report["batched"] = bench_batched()
    b = report["batched"]
    rows.append(("batched_wards", b["wards"], b["seconds_batched"],
                 b["wards_per_s_batched"]))
    csv.append(
        f"sched_batched_B{b['wards']}_n{b['n']},"
        f"{b['seconds_batched']*1e6:.0f},"
        f"wards_per_s={b['wards_per_s_batched']:.0f};"
        f"speedup_vs_sequential={b['speedup_batched_vs_sequential']:.1f}x;"
        f"parity_mismatches={b['parity_mismatches']}")

    # 5b) cross-ward shared-cloud contention (DESIGN.md §9)
    report["contention"] = bench_contention()
    c = report["contention"]
    rows.append(("contention_wards", c["wards"], c["seconds"],
                 c["wards_per_s"]))
    csv.append(
        f"sched_contention_B{c['wards']}_n{c['n']},"
        f"{c['seconds']*1e6:.0f},"
        f"gap={c['contention_gap']:.3f}x;"
        f"gap_closed={c['gap_closed']:.0%};"
        f"sweeps={c['sweeps']};"
        f"wards_per_s={c['wards_per_s']:.1f}")

    # 5b2) the §12 interval-reservation path on the same fleet: batched
    # sweeps on CPU, gated against both the naive fleet and the §8 floor
    report["contention_interval"] = bench_contention_interval()
    ci = report["contention_interval"]
    rows.append(("contention_interval_wards", ci["wards"], ci["seconds"],
                 ci["wards_per_s"]))
    csv.append(
        f"sched_contention_interval_B{ci['wards']}_n{ci['n']},"
        f"{ci['seconds']*1e6:.0f},"
        f"gap_closed={ci['gap_closed']:.0%};"
        f"sweeps={ci['sweeps']};"
        f"wards_per_s={ci['wards_per_s']:.1f};"
        f"fraction_of_batched={ci['fraction_of_batched']:.2f};"
        f"parity_with_phantom={ci['parity_with_phantom']};"
        f"compiled_programs={ci['compiled_programs']}")

    # 5c) streaming metro traffic: policy comparison + engine throughput
    # (DESIGN.md §10)
    report["metro"] = bench_metro()
    m = report["metro"]
    rows.append(("metro_events", m["events_tabu"], 0.0,
                 m["events_per_s"]))
    imp = m["miss_rate_improvement"]
    csv.append(
        f"sched_metro_B{m['wards']}_{m['hours']:g}h,0,"
        f"miss_greedy={m['miss_rate_greedy']:.3f};"
        f"miss_tabu={m['miss_rate_tabu']:.3f};"
        f"miss_fleet={m['miss_rate_fleet']:.3f};"
        f"improvement={'vacuous' if imp is None else f'{imp:.2f}x'};"
        f"events_per_s={m['events_per_s']:.0f}")

    # 5d) chaos scenario packs: crash/shed/degraded-network regimes
    # (DESIGN.md §11)
    report["metro_scenarios"] = bench_metro_scenarios()
    for pack, ms in report["metro_scenarios"].items():
        rows.append((f"metro_{pack}", ms["jobs"], 0.0,
                     ms["events_per_s"]))
        mi, ci = ms["miss_rate_improvement"], \
            ms["critical_improvement_shed"]
        csv.append(
            f"sched_metro_{pack},0,"
            f"jobs={ms['jobs']};"
            f"miss_greedy={ms['miss_rate_greedy']:.3f};"
            f"miss_tabu={ms['miss_rate_tabu']:.3f};"
            f"improvement={'vacuous' if mi is None else f'{mi:.2f}x'};"
            f"crit_shed={'vacuous' if ci is None else f'{ci:.2f}x'};"
            f"shed_rate={ms['shed_rate_shed']:.3f};"
            f"retries={ms['retries_tabu']};"
            f"events_per_s={ms['events_per_s']:.0f}")

    # 5e) deadline-aware hedging vs fail-slow stragglers (DESIGN.md §13)
    report["metro_hedging"] = bench_metro_hedging()
    mh = report["metro_hedging"]
    rows.append(("metro_hedging", mh["jobs"], 0.0, mh["events_per_s"]))
    chi = mh["critical_improvement_hedge"]
    csv.append(
        f"sched_metro_hedging,0,"
        f"jobs={mh['jobs']};"
        f"crit_unhedged={mh['critical_miss_unhedged']:.4f};"
        f"crit_hedged={mh['critical_miss_hedged']:.4f};"
        f"crit_improvement={'vacuous' if chi is None else f'{chi:.2f}x'};"
        f"p99_improvement={mh['p99_improvement_hedge']:.3f}x;"
        f"hedges={mh['hedges']};wins={mh['hedge_wins']};"
        f"hedge_waste={mh['hedge_waste']:.1f};"
        f"events_per_s={mh['events_per_s']:.0f}")

    # 5f) flight-recorder overhead + traced/untraced CRC parity
    # (DESIGN.md §15)
    report["metro_observability"] = bench_metro_observability()
    mo = report["metro_observability"]
    rows.append(("metro_observability", mo["events"], 0.0,
                 mo["events_per_s_retention"]))
    csv.append(
        f"sched_metro_observability,0,"
        f"packs={len(mo['packs'])};"
        f"spans={mo['spans']};"
        f"crc_parity={mo['crc_parity_all']};"
        f"events_per_s_retention={mo['events_per_s_retention']:.3f}")

    # 6) per-scenario online competitive ratios (slower; gated by --online)
    if with_online_scenarios:
        scen = bench_online_scenarios()
        report["online"]["scenarios"] = scen
        for name, fleets in scen.items():
            for fleet, ratios in fleets.items():
                csv.append(
                    f"sched_online_{name}_{fleet},0,"
                    f"greedy={ratios['greedy']['mean']:.3f};"
                    f"tabu_replan={ratios['tabu']['mean']:.3f}")
        fleet_cr = bench_online_fleet()
        report["online"]["fleet"] = fleet_cr
        csv.append(
            f"sched_online_fleet_B{fleet_cr['wards']}_n{fleet_cr['n']},0,"
            f"mean_ratio={fleet_cr['mean_ratio']:.3f};"
            f"max_ratio={fleet_cr['max_ratio']:.3f}")

    out_path = out_path or BENCH_JSON
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    csv.append(f"# scheduler report written to {out_path},0,")
    return rows, csv


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--online", action="store_true",
                    help="also run the (slower) per-scenario online "
                         "competitive-ratio section")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    for line in bench_scheduler_scale(with_online_scenarios=args.online)[1]:
        print(line)
