"""Perf regression gate for the scheduler hot paths.

Runs a fresh `benchmarks/scheduler_scale.py` sweep and compares it against
the committed floors in BENCH_scheduler.json, the same way tests guard
correctness: exits nonzero when any guarded metric regresses by more than
``--tolerance`` (default 30%).

Guarded metrics (all RELATIVE, so they transfer across machine speeds,
except wards/sec which assumes the committed baseline ran on comparable
hardware — regenerate the baseline when the CI host changes):

  * head-to-head ``speedup_vs_reference`` per (n, method) — the
    incremental and jitted searches must stay fast relative to the seed
    reference implementation;
  * ``jax_vs_incremental`` per n (derived: incremental seconds / jax
    seconds) — the delta-evaluated jitted search must not fall back
    behind the incremental Python path (the PR-3 n=1000 regression fix);
  * batched ``speedup_batched_vs_sequential`` and
    ``wards_per_s_batched`` — fleet planning throughput (DESIGN.md §8);
  * batched ``parity_mismatches`` must be exactly 0 (not a perf floor: the
    batched search must return the per-instance search's objectives);
  * contention ``improvement_vs_naive``, ``gap_closed`` and
    ``wards_per_s`` — the fixed-point fleet search must keep recovering
    the shared-cloud double-booking gap at speed (DESIGN.md §9); plus two
    hard invariants whenever a fresh contention section exists: the
    benchmark fleet must exhibit a nonzero contention gap (> 1 — if it
    does not, the benchmark no longer measures anything) and the fleet
    search must strictly beat the naive plans on the fleet-true
    objective;
  * contention_interval ``improvement_vs_naive``, ``gap_closed``,
    ``wards_per_s`` and ``fraction_of_batched`` — the §12
    interval-reservation fleet path must hold both its absolute
    throughput and its ratio to the independent §8 batched floor; plus
    hard invariants whenever the fresh section exists:
    ``parity_with_phantom`` must be True (the interval background must
    reproduce the frozen-phantom plan bit-identically or strictly beat
    it fleet-true) and the compiled-shape cache must report zero
    evictions (the §12 bucketing contract keeps the benchmark inside a
    handful of compiled shapes);
  * metro ``events_per_s`` and ``miss_rate_improvement`` — the streaming
    traffic engine must keep its event throughput and the tabu-vs-greedy
    deadline miss-rate win (DESIGN.md §10); plus the hard invariant that
    the improvement stays strictly > 1 whenever a fresh metro section
    exists;
  * per chaos scenario pack (``metro_scenarios``, DESIGN.md §11):
    ``events_per_s``, the tabu-vs-greedy ``miss_rate_improvement`` and
    the shedding policy's ``critical_improvement_shed``; plus hard
    ranking invariants — whenever the committed baseline shows a policy
    winning a pack (improvement > 1), the fresh run must not show it
    losing (<= 1), whatever the tolerance;
  * metro_hedging (DESIGN.md §13): ``events_per_s`` of the hedged run
    plus two HARD ranking invariants whenever a fresh section exists —
    under the ``fail_slow_tail`` pack the hedged tabu run must strictly
    beat the unhedged run on BOTH the life-critical miss rate
    (``critical_improvement_hedge`` > 1; None is vacuous — the unhedged
    run missed nothing) and the p99 response
    (``p99_improvement_hedge`` > 1), at any tolerance;
  * metro_observability (DESIGN.md §15): ``events_per_s_retention`` —
    the armed flight recorder's throughput as a fraction of the
    untraced run over every chaos pack; plus hard invariants whenever a
    fresh section exists — per-pack ``crc_parity`` must be True (the
    tracer is a read-only observer: a traced run's event log must hash
    bit-identically to the untraced run's) and the retention must stay
    above 1/1.15 (recording may cost at most 15%), at any tolerance.

Wall-clock throughput floors (events/s, wards/s, speedups) are prone to
host-throttling flakes: ``--runs N`` re-measures ONLY the failed
wall-clock floors up to N-1 more times and gates on the best
observation. Invariant and quality floors stay single-shot — a ranking
loss or parity mismatch is not a flake.

Invocation (documented in ROADMAP.md):

    PYTHONPATH=src python benchmarks/check_regression.py \
        --baseline BENCH_scheduler.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

# metrics measured from wall-clock timings (rerunnable via --runs);
# everything else is deterministic quality and stays single-shot
_WALL_CLOCK_TOKENS = ("events_per_s", "wards_per_s", "speedup",
                      "jax_vs_incremental", "fraction_of_batched",
                      "retention")


def _is_wall_clock(key: str) -> bool:
    return any(tok in key for tok in _WALL_CLOCK_TOKENS)


def _head_to_head_metrics(report: dict) -> dict:
    """-> {metric name: value} of guarded relative head-to-head metrics."""
    out = {}
    for row in report.get("head_to_head", ()):
        n = row["n"]
        methods = row.get("methods", {})
        for name, m in methods.items():
            speed = m.get("speedup_vs_reference")
            if speed:
                out[f"n{n}/{name}/speedup_vs_reference"] = speed
        inc = (methods.get("incremental") or {}).get("seconds")
        jx = (methods.get("jax") or {}).get("seconds")
        if inc and jx:
            out[f"n{n}/jax_vs_incremental"] = inc / jx
    return out


def _batched_metrics(report: dict) -> dict:
    b = report.get("batched") or {}
    out = {}
    for key in ("speedup_batched_vs_sequential", "wards_per_s_batched"):
        if b.get(key):
            out[f"batched/{key}"] = b[key]
    return out


def _contention_metrics(report: dict) -> dict:
    c = report.get("contention") or {}
    out = {}
    for key in ("improvement_vs_naive", "gap_closed", "wards_per_s"):
        if c.get(key):
            out[f"contention/{key}"] = c[key]
    return out


def _contention_interval_metrics(report: dict) -> dict:
    c = report.get("contention_interval") or {}
    out = {}
    for key in ("improvement_vs_naive", "gap_closed", "wards_per_s",
                "fraction_of_batched"):
        if c.get(key):
            out[f"contention_interval/{key}"] = c[key]
    return out


def _metro_metrics(report: dict) -> dict:
    m = report.get("metro") or {}
    out = {}
    for key in ("events_per_s", "miss_rate_improvement"):
        if m.get(key):
            out[f"metro/{key}"] = m[key]
    return out


def _metro_scenario_metrics(report: dict) -> dict:
    out = {}
    for pack, m in sorted((report.get("metro_scenarios") or {}).items()):
        for key in ("events_per_s", "miss_rate_improvement",
                    "critical_improvement_shed"):
            if m.get(key):         # None improvements are vacuous: skip
                out[f"metro_scenarios/{pack}/{key}"] = m[key]
    return out


def _metro_hedging_metrics(report: dict) -> dict:
    m = report.get("metro_hedging") or {}
    out = {}
    for key in ("events_per_s", "critical_improvement_hedge",
                "p99_improvement_hedge"):
        if m.get(key):             # None improvement is vacuous: skip
            out[f"metro_hedging/{key}"] = m[key]
    return out


def _metro_observability_metrics(report: dict) -> dict:
    m = report.get("metro_observability") or {}
    out = {}
    if m.get("events_per_s_retention"):
        out["metro_observability/events_per_s_retention"] = \
            m["events_per_s_retention"]
    return out


_METRIC_FNS = (_head_to_head_metrics, _batched_metrics,
               _contention_metrics, _contention_interval_metrics,
               _metro_metrics, _metro_scenario_metrics,
               _metro_hedging_metrics, _metro_observability_metrics)


def compare(committed: dict, fresh: dict, tolerance: float = 0.30,
            best: dict | None = None) -> list:
    """-> list of human-readable regression strings (empty == pass).

    A metric regresses when fresh < committed * (1 - tolerance). Metrics
    present in only one report are skipped (the gate tightens as the
    committed baseline gains sections, and never blocks on new ones).
    `best` overlays best-of-N re-measurements per metric key — callers
    populate it only for wall-clock floors (--runs), so invariant and
    quality floors always gate on the single fresh run.
    """
    problems = []
    for metrics in _METRIC_FNS:
        com, fre = metrics(committed), metrics(fresh)
        for key, floor in com.items():
            got = fre.get(key)
            if got is None:
                continue
            if best and best.get(key, got) > got:
                got = best[key]
            if got < floor * (1.0 - tolerance):
                problems.append(
                    f"{key}: {got:.3g} < committed {floor:.3g} "
                    f"- {tolerance:.0%}")
    mism = (fresh.get("batched") or {}).get("parity_mismatches")
    if mism:
        problems.append(f"batched/parity_mismatches: {mism} != 0")
    cont = fresh.get("contention") or {}
    if cont:
        # hard invariants, not perf floors (DESIGN.md §9): the benchmark
        # fleet must actually overcommit the shared cloud, and the fleet
        # search must strictly beat the naive plans fleet-true
        if cont.get("contention_gap", 0.0) <= 1.0:
            problems.append(
                f"contention/contention_gap: {cont.get('contention_gap')} "
                f"<= 1 (benchmark fleet no longer double-books the cloud)")
        if not cont.get("fleet_true", 0.0) < cont.get(
                "naive_fleet_true", 0.0):
            problems.append(
                f"contention: fleet_true {cont.get('fleet_true')} does not "
                f"strictly beat naive_fleet_true "
                f"{cont.get('naive_fleet_true')}")
    ci = fresh.get("contention_interval") or {}
    if ci:
        # hard invariants (DESIGN.md §12): the interval background must
        # reproduce the frozen-phantom oracle's plan (or strictly beat
        # it fleet-true), and the bucketed dispatch cache must absorb
        # the benchmark's shape traffic without a single eviction
        if not ci.get("parity_with_phantom", False):
            problems.append(
                "contention_interval/parity_with_phantom: False "
                "(interval background diverged from the frozen-phantom "
                "construction without beating it fleet-true)")
        evs = (ci.get("compiled_shapes") or {}).get("evictions", 0)
        if evs:
            problems.append(
                f"contention_interval/compiled_shapes.evictions: {evs} "
                f"!= 0 (§12 bucketing no longer bounds shape churn)")
    metro = fresh.get("metro") or {}
    if metro:
        # hard invariant (DESIGN.md §10): committed tabu replanning must
        # STRICTLY beat greedy commit-and-hold on SLA deadline miss-rate
        # on the benchmark traffic — improvement <= 1 means the metro
        # subsystem's reason to exist has regressed, whatever the floors.
        # A None improvement means greedy itself missed nothing (the
        # traffic no longer stresses anyone), which is vacuous, not a
        # regression.
        imp = metro.get("miss_rate_improvement", 0.0)
        if imp is not None and not imp > 1.0:
            problems.append(
                f"metro/miss_rate_improvement: {imp} <= 1 (tabu replan "
                f"no longer beats greedy on deadline miss-rate)")
    # per-scenario ranking invariants (DESIGN.md §11): a policy the
    # committed baseline shows WINNING a chaos pack (ratio > 1) must not
    # show up losing it (<= 1) in the fresh run — tolerance never
    # excuses a rank flip. Fresh None stays vacuous (greedy perfect).
    com_sc = committed.get("metro_scenarios") or {}
    fre_sc = fresh.get("metro_scenarios") or {}
    for pack in sorted(set(com_sc) & set(fre_sc)):
        for field, label in (
                ("miss_rate_improvement", "tabu replan"),
                ("critical_improvement_shed",
                 "shedding's life-critical protection")):
            floor = com_sc[pack].get(field)
            got = fre_sc[pack].get(field)
            if floor is not None and floor > 1.0 \
                    and got is not None and not got > 1.0:
                problems.append(
                    f"metro_scenarios/{pack}/{field}: {got:.3g} <= 1 "
                    f"(committed {floor:.3g}; {label} no longer wins "
                    f"this pack)")
    # hedging ranking invariants (DESIGN.md §13): whenever a fresh
    # metro_hedging section exists, the hedged tabu run must STRICTLY
    # beat the unhedged run under fail_slow_tail on BOTH the
    # life-critical miss rate and p99 response — tolerance never excuses
    # either loss. A None critical improvement is vacuous (the unhedged
    # run missed no life-critical deadline: nothing to rescue).
    mh = fresh.get("metro_hedging") or {}
    if mh:
        for field, label in (
                ("critical_improvement_hedge", "life-critical miss rate"),
                ("p99_improvement_hedge", "p99 response")):
            got = mh.get(field)
            if got is not None and not got > 1.0:
                problems.append(
                    f"metro_hedging/{field}: {got:.3g} <= 1 (hedged tabu "
                    f"no longer beats unhedged on {label} under "
                    f"fail_slow_tail)")
    # observability invariants (DESIGN.md §15): the flight recorder is a
    # read-only observer — a traced run's event log must hash
    # bit-identically to the untraced run's on every pack — and the
    # armed recorder may cost at most 15% throughput (retention >
    # 1/1.15). Parity is never a flake; the retention bound IS
    # wall-clock, so it honors --runs best-of re-measurement.
    mo = fresh.get("metro_observability") or {}
    if mo:
        for pack in sorted(mo.get("packs") or {}):
            if not mo["packs"][pack].get("crc_parity", False):
                problems.append(
                    f"metro_observability/{pack}/crc_parity: False "
                    f"(traced event log diverged from the untraced run "
                    f"- the tracer mutated engine state)")
        key = "metro_observability/events_per_s_retention"
        ret = mo.get("events_per_s_retention", 0.0)
        if best and best.get(key, ret) > ret:
            ret = best[key]
        if not ret > 1.0 / 1.15:
            problems.append(
                f"{key}: {ret:.3g} <= {1.0 / 1.15:.3g} (armed flight "
                f"recorder costs more than 1.15x throughput)")
    return problems


def _remeasure(failed_keys) -> dict:
    """Re-run ONLY the benchmark sections behind the failed wall-clock
    floors; -> a partial report holding just those sections."""
    import scheduler_scale as ss

    sections, packs = set(), set()
    for key in failed_keys:
        head = key.split("/", 1)[0]
        if head == "metro_scenarios":
            packs.add(key.split("/")[1])
        else:
            sections.add("head_to_head" if head.startswith("n") else head)
    partial: dict = {}
    if "head_to_head" in sections:
        partial["head_to_head"] = ss.bench_head_to_head()
    if "batched" in sections:
        partial["batched"] = ss.bench_batched()
    if "contention" in sections:
        partial["contention"] = ss.bench_contention()
    if "contention_interval" in sections:
        partial["contention_interval"] = ss.bench_contention_interval()
    if "metro" in sections:
        partial["metro"] = ss.bench_metro()
    if "metro_hedging" in sections:
        partial["metro_hedging"] = ss.bench_metro_hedging()
    if "metro_observability" in sections:
        partial["metro_observability"] = ss.bench_metro_observability()
    if packs:
        partial["metro_scenarios"] = ss.bench_metro_scenarios(
            packs=sorted(packs))
    return partial


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default="BENCH_scheduler.json",
                    help="committed report with the floors to hold")
    ap.add_argument("--tolerance", type=float, default=0.30,
                    help="allowed fractional regression (default 0.30)")
    ap.add_argument("--fresh", default=None,
                    help="compare an existing report instead of running "
                         "the benchmark (mainly for tests)")
    ap.add_argument("--runs", type=int, default=1,
                    help="measure failed WALL-CLOCK throughput floors up "
                         "to this many times total and gate on the best "
                         "observation (host-throttling flake armor); "
                         "invariant/quality floors stay single-shot")
    args = ap.parse_args(argv)

    with open(args.baseline) as f:
        committed = json.load(f)
    if args.fresh:
        with open(args.fresh) as f:
            fresh = json.load(f)
    else:
        from scheduler_scale import bench_scheduler_scale
        out = os.path.join(tempfile.mkdtemp(prefix="bench_fresh_"),
                           "BENCH_scheduler.json")
        bench_scheduler_scale(out_path=out)
        with open(out) as f:
            fresh = json.load(f)
        print(f"fresh report: {out}")

    problems = compare(committed, fresh, tolerance=args.tolerance)
    best: dict = {}
    for attempt in range(2, max(1, args.runs) + 1):
        failed_wall = sorted({p.split(":", 1)[0] for p in problems
                              if _is_wall_clock(p.split(":", 1)[0])})
        if not failed_wall or args.fresh:
            break            # nothing rerunnable (or no benchmark to run)
        print(f"re-measuring {len(failed_wall)} wall-clock floor(s), "
              f"run {attempt}/{args.runs}: {', '.join(failed_wall)}")
        partial = _remeasure(failed_wall)
        for fn in _METRIC_FNS:
            for key, val in fn(partial).items():
                if key in failed_wall and val > best.get(key, 0.0):
                    best[key] = val
        problems = compare(committed, fresh, tolerance=args.tolerance,
                           best=best)

    if problems:
        print("PERF REGRESSION vs committed baseline:")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(f"perf floors held (tolerance {args.tolerance:.0%})")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from repro.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    sys.exit(main())
