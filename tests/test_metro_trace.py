"""Flight-recorder tests (DESIGN.md §15): span-tree structure (one root
per job, nested non-negative phases), sanitizer-I3 agreement, hedge-race
span accounting, the exact additive deadline-miss attribution, the
traced/untraced CRC-parity contract over every chaos pack, exporter
round-trips, the engine self-profile, and the windowed-metrics final
flush."""
import json
import zlib

import pytest

from repro.core.tiers import CC, ES
from repro.metro import traces
from repro.metro.engine import MetroEngine, simulate_metro
from repro.metro.metrics import MetroMetrics
from repro.metro.policies import HedgingPolicy, TabuPolicy
from repro.metro.tracing import TERMS, MetroTrace

MPT = {CC: 2, ES: 2}
ALL_PACKS = ("default", "edge_brownout", "mass_casualty_crash",
             "degraded_network", "diurnal_day", "fail_slow_tail")


def _run(pack="edge_brownout", seed=0, wards=2, horizon=45.0,
         hedged=False, **kw):
    sc = traces.make_scenario(pack, seed, wards=wards, horizon=horizon)
    pol = TabuPolicy(jax_threshold=10 ** 9)
    ekw = {}
    if hedged:
        pol = HedgingPolicy(inner=pol)
        ekw["hedge_factor"] = 1.5
    return simulate_metro(sc.traces, pol, machines_per_tier=MPT,
                          failures=sc.failures, scale_events=sc.scales,
                          network_events=sc.network,
                          slowdowns=sc.slowdowns, **ekw, **kw)


@pytest.fixture(scope="module")
def traced_brownout():
    return _run("edge_brownout", trace=True)


@pytest.fixture(scope="module")
def traced_tail():
    # the pack's canonical shape: reduced horizons never enter the deep
    # slowdown windows, so no hedge race would fire
    return _run("fail_slow_tail", wards=None, horizon=None, hedged=True,
                trace=True, profile=True, retry_backoff=0.5,
                max_attempts=4)


# ------------------------------------------------------- span structure
def test_off_by_default_and_zero_state():
    res = _run("diurnal_day", horizon=30.0)
    assert res.trace is None
    assert res.profile is None


def test_one_root_span_per_job(traced_brownout):
    res = traced_brownout
    roots = [sp for sp in res.trace.spans if sp.name == "root"]
    total = res.metrics.completions + res.metrics.shed
    assert len(roots) == total
    assert len({sp.trace for sp in roots}) == len(roots)
    # every root carries the job identity and closed non-negatively
    for sp in roots:
        assert sp.parent is None and sp.cat == "job"
        assert {"episode", "wclass", "weight", "deadline",
                "outcome", "missed"} <= set(sp.attrs)
        assert sp.t1 >= sp.t0


def test_span_nesting_and_no_negative_durations(traced_brownout,
                                                traced_tail):
    for res in (traced_brownout, traced_tail):
        by_id = {sp.span: sp for sp in res.trace.spans}
        for sp in res.trace.spans:
            assert sp.t1 >= sp.t0, (sp.name, sp.t0, sp.t1)
            if sp.parent is not None:
                par = by_id[sp.parent]
                assert par.t0 <= sp.t0 and sp.t1 <= par.t1, \
                    (sp.name, par.name)


def test_decision_backoff_and_attempt_span_counts(traced_tail):
    res = traced_tail
    spans = res.trace.spans
    # crash retries open a new attempt: attempt spans per job == the
    # completion record's attempt count (each killed attempt closes one
    # span, the final completion closes the last)
    completed = {}
    for rec in res.event_log:
        if rec[0] == "complete":
            completed[(rec[2], rec[3])] = rec[9]     # attempts
    by_job = {}
    for sp in spans:
        if sp.cat == "attempt" and sp.name == "attempt":
            by_job.setdefault(sp.trace, []).append(sp)
    for (b, i), attempts in completed.items():
        got = by_job.get(f"w{b}j{i}", [])
        assert len(got) == attempts, (b, i)
        outcomes = [sp.attrs["outcome"] for sp in got]
        assert outcomes.count("complete") == 1
        assert all(o == "killed" for o in outcomes[:-1])
    # retry records with a real backoff gap produce backoff spans
    n_backoff = sum(1 for sp in spans if sp.name == "backoff")
    n_retry = sum(1 for rec in res.event_log if rec[0] == "retry")
    assert n_backoff <= n_retry
    assert res.metrics.retries == 0 or n_retry > 0


# --------------------------------------------------- sanitizer agreement
def test_sanitizer_started_attempts_match_traced_spans():
    sc = traces.make_scenario("mass_casualty_crash", 0, wards=2,
                              horizon=45.0)
    eng = MetroEngine(sc.traces, TabuPolicy(jax_threshold=10 ** 9),
                      machines_per_tier=MPT, failures=sc.failures,
                      scale_events=sc.scales, network_events=sc.network,
                      slowdowns=sc.slowdowns)
    res = eng.run(sanitize=True, trace=True)
    started = eng._san._started
    assert started, "sanitizer saw no started attempts"
    # every attempt the sanitizer registered as STARTED must be visible
    # in the trace as a span occupying that (machine, slot)
    occupancy = {}
    for sp in res.trace.spans:
        if sp.cat == "attempt" and "machine" in sp.attrs:
            occupancy.setdefault(sp.trace, []).append(
                (sp.attrs["machine"], sp.attrs.get("slot")))
    for (b, i, _is_hedge, _k), (machine, slot, _t0) in started.items():
        assert (machine, slot) in occupancy.get(f"w{b}j{i}", []), \
            (b, i, machine, slot)


# ----------------------------------------------------------- hedge races
def test_hedge_race_one_winner_one_loser(traced_tail):
    res = traced_tail
    spans = res.trace.spans
    cancels = [rec for rec in res.event_log if rec[0] == "hedge_cancel"]
    losers = [sp for sp in spans if sp.name == "hedge_loser"]
    assert res.metrics.hedges > 0, "pack no longer exercises hedging"
    # one cancelled-loser span per cancellation, cut at the winner
    assert len(losers) == len(cancels)
    assert all(sp.attrs["outcome"] == "cancelled" for sp in losers)
    # hedge uniqueness (engine I5): at most one dispatch marker per job
    n_hedge = {}
    for sp in spans:
        if sp.name == "hedge":
            n_hedge[sp.trace] = n_hedge.get(sp.trace, 0) + 1
    assert all(n == 1 for n in n_hedge.values())
    # a won race: exactly one completing attempt flagged hedge_win with
    # its loser span present on the same job trace
    won = [r for r in res.trace.rows if r["hedge_win"]]
    assert len(won) == res.metrics.hedge_wins
    loser_traces = {sp.trace for sp in losers}
    for r in won:
        tid = f"w{r['ward']}j{r['index']}"
        wins = [sp for sp in spans
                if sp.trace == tid and sp.name == "attempt"
                and sp.attrs.get("hedge_win")]
        assert len(wins) == 1
        promoted = any(sp.trace == tid and sp.name == "hedge_promote"
                       for sp in spans)
        assert promoted or tid in loser_traces


def test_service_segments_partition_service_span(traced_tail):
    res = traced_tail
    by_id = {sp.span: sp for sp in res.trace.spans}
    segs = {}
    for sp in res.trace.spans:
        if sp.name == "service_seg":
            segs.setdefault(sp.parent, []).append(sp)
    assert segs, "fail_slow_tail produced no segmented service spans"
    for parent_id, parts in segs.items():
        svc = by_id[parent_id]
        parts.sort(key=lambda s: s.t0)
        assert parts[0].t0 == svc.t0 and parts[-1].t1 == svc.t1
        for a, b in zip(parts, parts[1:]):
            assert a.t1 == b.t0
        assert any(s.attrs["rate"] != 1.0 for s in parts)


# ----------------------------------------------------------- attribution
def test_attribution_terms_sum_exactly(traced_brownout, traced_tail):
    for res in (traced_brownout, traced_tail):
        assert res.trace.rows, "no finished jobs"
        for r in res.trace.rows:
            assert set(r["terms"]) == set(TERMS)
            assert sum(r["terms"].values()) == \
                pytest.approx(r["response"], abs=1e-9)
            assert r["dominant"] in TERMS
            # no negative components: waiting/transmit/service/slowdown
            # are physical durations, retry_waste is time actually lost
            for t, v in r["terms"].items():
                assert v >= -1e-9, (r["job"], t, v)


def test_blame_table_aggregates_missed_rows(traced_tail):
    tr = traced_tail.trace
    missed = tr.attribution(missed_only=True)
    table = tr.blame_table()
    assert sum(row["misses"] for row in table) == len(missed)
    for row in table:
        assert row["dominant"] in TERMS
        for t in TERMS:
            assert row["total_terms"][t] == pytest.approx(
                sum(r["terms"][t] for r in missed
                    if (r["wclass"], r["tier"])
                    == (row["wclass"], row["tier"])), abs=1e-9)
    text = tr.format_postmortem("tabu", traced_tail.profile)
    assert text.startswith("postmortem[tabu]")
    pm = tr.postmortem_json("tabu", traced_tail.profile)
    assert json.dumps(pm)        # JSON-serializable end to end


def test_shed_jobs_attribute_all_time_to_wait_and_retries():
    res = _run("mass_casualty_crash", horizon=45.0, trace=True,
               max_attempts=1)
    dropped = [r for r in res.trace.rows if r["outcome"] != "complete"]
    assert dropped, "pack no longer exhausts any retry budget"
    for r in dropped:
        assert r["terms"]["service"] == 0.0
        assert r["terms"]["transmit"] == 0.0
        assert r["terms"]["slowdown"] == 0.0
        assert r["missed"]


# ------------------------------------------------------------ parity
@pytest.mark.parametrize("pack", ALL_PACKS)
def test_traced_run_is_bit_identical(pack):
    hedged = pack == "fail_slow_tail"
    base = _run(pack, horizon=30.0, hedged=hedged)
    traced = _run(pack, horizon=30.0, hedged=hedged, trace=True,
                  profile=True)
    assert zlib.crc32(repr(base.event_log).encode()) == \
        zlib.crc32(repr(traced.event_log).encode())
    assert base.metrics.summary(base.utilization) == \
        traced.metrics.summary(traced.utilization)


# ------------------------------------------------------------ exporters
def test_jsonl_export_roundtrip(tmp_path, traced_brownout):
    path = tmp_path / "trace.jsonl"
    n = traced_brownout.trace.write(str(path), "jsonl")
    lines = path.read_text().splitlines()
    assert n == len(lines) == len(traced_brownout.trace.spans)
    for line, sp in zip(lines, traced_brownout.trace.spans):
        d = json.loads(line)
        assert d["span"] == sp.span and d["name"] == sp.name


def test_chrome_export_structure(tmp_path, traced_tail):
    path = tmp_path / "trace.chrome.json"
    n = traced_tail.trace.write(str(path), "chrome")
    doc = json.loads(path.read_text())
    ev = doc["traceEvents"]
    assert n == len(ev)
    phases = {e["ph"] for e in ev}
    assert {"M", "X", "b", "e"} <= phases
    assert all(e["dur"] >= 0.0 for e in ev if e["ph"] == "X")
    # async begin/end events balance per (id, name)
    opens = {}
    for e in ev:
        if e["ph"] == "b":
            opens[(e["id"], e["name"])] = \
                opens.get((e["id"], e["name"]), 0) + 1
        elif e["ph"] == "e":
            opens[(e["id"], e["name"])] -= 1
    assert all(v == 0 for v in opens.values())
    # machine-slot occupancy rows never overlap (engine invariant I2)
    rows = {}
    for e in ev:
        if e["ph"] == "X" and e.get("cat") == "occupancy":
            rows.setdefault((e["pid"], e["tid"]), []).append(
                (e["ts"], e["ts"] + e["dur"]))
    assert rows
    for spans in rows.values():
        spans.sort()
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert start >= end - 1e-6


def test_unknown_trace_format_rejected(tmp_path):
    tr = MetroTrace(spans=[], rows=[])
    with pytest.raises(ValueError, match="unknown trace format"):
        tr.write(str(tmp_path / "x"), "protobuf")


# ------------------------------------------------------------- profiling
def test_engine_profile_accounts_for_the_run(traced_tail):
    prof = traced_tail.profile
    assert prof is not None
    assert prof["events"] == traced_tail.summary()["events"]
    assert prof["seconds_total"] > 0.0
    assert prof["decide_calls"] > 0
    assert prof["handlers_by_kind"]
    busy = (prof["replay"] + prof["policy"] + prof["sanitize"]
            + prof["hedge_hook"])
    assert 0.0 <= busy <= prof["seconds_total"] * 1.05
    for removed in ("heap_pushes", "compiled_shapes",
                    "compiled_shapes_delta", "shapes_before"):
        assert removed not in prof


# ------------------------------------------- windowed metrics final flush
def test_metrics_flush_preserves_open_window():
    m = MetroMetrics(window=60.0)
    m.record(10.0, "c", response=25.0, deadline=20.0, tier=CC, proc=5.0)
    m.record_shed(30.0, "c")
    assert not m.recent            # both land in the still-open window
    m.flush()
    assert len(m.recent) == 1
    m.flush()                      # idempotent: nothing open anymore
    assert len(m.recent) == 1
    s = m.summary()
    assert s["recent_windows"] == 1
    assert s["recent_finished"] == 2
    assert s["recent_misses"] >= 1
    assert 0.0 <= s["recent_miss_rate"] <= 1.0


def test_engine_flushes_final_partial_window(traced_brownout):
    s = traced_brownout.metrics.summary(traced_brownout.utilization)
    assert s["recent_windows"] >= 1
    assert s["recent_finished"] > 0
    assert s["recent_p99"] >= 0.0
