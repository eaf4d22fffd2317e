"""The per-unit streams of a deployment of several hospitals' care units
(bench/traffic/units.py), and the readers of the per-layer metrics its
runner records (bench/runners/metro_units.py)."""

import pytest

from bench import common
from bench.runners import metro_units
from bench.traffic import gen, units
from repro.utils.spans import Span

SEEDS = [0, 7, 2**31 + 5]


@pytest.fixture(scope="module")
def cfg():
    return common.load_config(common.load_spec(), "metro15icu")


@pytest.fixture(scope="module")
def mix():
    return gen.load_mix("metro15icu_stream")


def _jobs(job):
    return (job.release, job.weight, tuple(sorted(job.proc.items())),
            tuple(sorted(job.trans.items())))


def test_rates_are_each_units_share_of_the_mean(cfg, mix):
    stays = [u["icu_stays"] for u in cfg["units"]]
    mean = sum(stays) / len(stays)
    for u, n in zip(cfg["units"], stays):
        assert u["rate_multiplier"] == pytest.approx(n / mean, rel=1e-15)
    periods = [units.unit_mix(cfg, mix, u)["period"]
               for u in range(len(stays))]
    assert periods == pytest.approx([12.8465, 27.4909, 30.4685, 35.0628,
                                     40.9145], abs=1e-4)


def _traces(cfg, mix, seed):
    return metro_units.Cell(cfg, mix, seed).traces


@pytest.mark.parametrize("seed", SEEDS)
def test_unit_job_counts(cfg, mix, seed):
    traces = _traces(cfg, mix, seed)
    assert len(traces) == cfg["wards"] == 15
    assert [len(t) for t in traces] == [230, 100, 90, 80, 70] * 3
    assert sum(map(len, traces)) == 1710
    for t in traces:
        names = [j.name for j in t]
        assert len(set(names)) == len(names)
        assert [j.release for j in t] == sorted(j.release for j in t)
    # whole periods only, all begun inside the horizon
    for u in range(len(cfg["units"])):
        m = units.unit_mix(cfg, mix, u)
        assert m["periods"] * m["period"] <= cfg["horizon"] < \
            (m["periods"] + 1) * m["period"]


def test_every_seed_sends_the_same_jobs(cfg, mix):
    # on a pooled cloud the order of a unit's periods sets the pass share
    # of a replay's searches, so no seed reorders them: every seed sends
    # the same trace, each unit's periods in the order its index draws
    first = _traces(cfg, mix, SEEDS[0])
    assert all(_traces(cfg, mix, s) == first for s in SEEDS[1:])
    n = len(cfg["units"])
    for w, t in enumerate(first):
        want = gen.stream(cfg, units.unit_mix(cfg, mix, w % n), w % n)[0]
        assert list(map(_jobs, t)) == list(map(_jobs, want))
        assert [j.name for j in t] == [j.name for j in want]


def test_same_type_units_release_together(cfg, mix):
    traces = units.streams(cfg, mix)
    n = len(cfg["units"])
    for u in range(n):
        same = [traces[h * n + u] for h in range(cfg["hospitals"])]
        assert all(list(map(_jobs, t)) == list(map(_jobs, same[0]))
                   for t in same)
    # units of different types release at different rates
    assert len({len(traces[u]) for u in range(n)}) == n


def _span(name, parent, t0, t1, **attrs):
    return Span(name, parent, None, t0, t1, attrs)


def test_searches_pair_each_dispatch_with_its_fetch():
    recorded = [
        _span("policy.decide", -1, 0, 100),
        _span("scheduler.dispatch", 0, 10, 20, regime="pass",
              rows_real=40, reserved_rows=36),
        _span("scheduler.fetch", 0, 20, 60),
        _span("policy.decide", -1, 200, 300),
        _span("scheduler.dispatch", 3, 210, 215, regime="round",
              rows_real=12),
        _span("scheduler.fetch", 3, 215, 230),
        _span("scheduler.dispatch", 3, 240, None, regime="round"),
    ]
    got = metro_units.searches(recorded)
    assert [g["regime"] for g in got] == ["pass", "round"]
    assert got[0]["search_s"] == pytest.approx(50e-9)
    assert got[1]["search_s"] == pytest.approx(20e-9)
    assert got[0]["reserved_rows"] == 36 and "reserved_rows" not in got[1]


def _read(name, record):
    return common.metric_reader(name).read(record)


def test_readers_on_a_recorded_window():
    record = {
        "decisions": 4,
        "spans": {"engine.requests": {"n": 5, "total_s": 0.002,
                                      "wards": 4, "background": 90}},
        "searches": [
            {"regime": "pass", "reserved_rows": 30, "search_s": 0.003},
            {"regime": "pass", "reserved_rows": 34, "search_s": 0.005},
            {"regime": "round", "reserved_rows": 2, "search_s": 0.001},
            {"regime": "round", "reserved_rows": 0, "search_s": 0.001},
        ]}
    assert _read("reserved_rows_per_search", record) == pytest.approx(16.5)
    assert _read("pass_search_ms", record) == pytest.approx(4.0)
    assert _read("engine_requests_ms", record) == pytest.approx(0.5)


def test_readers_find_nothing_without_the_spans():
    # an untraced window, and a program whose dispatch does not count
    # reservation rows and whose searches never took the pass regime
    for record in ({"decisions": 3, "seconds": 1.0, "decide_s": [0.1]},
                   {"decisions": 3, "spans": {},
                    "searches": [{"regime": "round", "search_s": 0.001}]}):
        for name in ("reserved_rows_per_search", "pass_search_ms",
                     "engine_requests_ms"):
            assert _read(name, record) is None


class _Req:
    def __init__(self, ward, names):
        self.ward = ward
        self.shifted = [type("Job", (), {"name": n}) for n in names]


class _Live:
    joint = False

    def __init__(self):
        self.asked = []

    def decide(self, requests, now):
        from repro.utils import spans
        assert spans.armed() is not None
        self.asked.append(now)
        return [["cloud"] * len(r.shifted) for r in requests]


def test_a_traced_replay_is_live_only_in_its_stretch():
    reqs = [_Req(k % 3, [f"j{k}"]) for k in range(10)]
    replayed = [(r, ["device"]) for r in reqs]
    live = _Live()
    staged = metro_units.Staged(live, replayed, 4, 6)
    got = [staged.decide([r], float(k)) for k, r in enumerate(reqs)]
    staged.close()
    assert live.asked == [4.0, 5.0]
    assert got == [[["device"]]] * 4 + [[["cloud"]]] * 2 + \
        [[["device"]]] * 4
    assert staged.t1 >= staged.t0 and staged.rec is not None
    assert staged.joint is False             # the live policy's, through


def test_a_traced_replay_stops_where_it_diverges():
    reqs = [_Req(0, ["a"]), _Req(1, ["b"])]
    staged = metro_units.Staged(_Live(), [(reqs[0], ["edge"])] * 2, 5, 6)
    assert staged.decide([reqs[0]], 0.0) == [["edge"]]
    with pytest.raises(RuntimeError, match="set-up's replay had"):
        staged.decide([reqs[1]], 1.0)
