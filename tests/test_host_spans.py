"""Host span recorder (`repro.utils.spans`, DESIGN.md §15 "Engine
self-profile"): off by default and stateless while off; armed, one span
tree per engine event from pop to commit, down to the device search's
pack, dispatch, fetch and re-score; on the profiler's host plane as
`repro.*` events; and never a change to what the engine decides."""
import zlib
from dataclasses import replace

import pytest

from repro.core import problems, scheduler
from repro.metro import TabuPolicy, simulate_metro
from repro.utils import spans

MPT = {"cloud": 1, "edge": 1}
SEARCH_PHASES = ("scheduler.pack", "scheduler.dispatch", "scheduler.fetch",
                 "scheduler.rescore")


def table6_stream(periods=2, period=25.0):
    """Table VI's ten jobs, repeated `periods` times `period` apart."""
    return [[replace(j, name=f"{j.name}.{p}", release=j.release + p * period)
             for p in range(periods) for j in problems.table6_jobs()]]


def replay(**kw):
    return simulate_metro(table6_stream(), TabuPolicy(jax_threshold=0),
                          machines_per_tier=MPT, **kw)


@pytest.fixture(autouse=True, scope="module")
def _isolate_compiled_shapes():
    """Every replay here forces the JAX search (jax_threshold=0), which
    records its bucketed shape in the module-global fast-path set;
    restore the set so later test modules keep their CPU default
    dispatch."""
    saved = set(scheduler._COMPILED_SHAPES)
    stats = dict(scheduler._SHAPE_STATS)
    yield
    scheduler._COMPILED_SHAPES.clear()
    scheduler._COMPILED_SHAPES.update(saved)
    scheduler._SHAPE_STATS.update(stats)


@pytest.fixture(scope="module")
def recorded():
    replay()                       # compile outside the recorded run
    with spans.recording() as rec:
        res = replay()
    return res, rec


def children(rec, k):
    return [s for s in rec.spans if s.parent == k]


def test_off_by_default_and_stateless(monkeypatch):
    assert spans.armed() is None
    # no clock read and no new object per call while off
    monkeypatch.setattr(spans, "perf_counter_ns", None)
    a, b = spans.span("x"), spans.span("y", kind="arrive", seq=3)
    assert a is b
    with a as inner:
        assert inner is a
    res = replay()
    assert res.profile is None and spans.armed() is None


def test_recording_arms_once_and_disarms():
    with spans.recording() as outer:
        with spans.recording() as inner:
            assert inner is outer
            with spans.span("a"):
                pass
        assert spans.armed() is outer
    assert spans.armed() is None
    assert [s.name for s in outer.spans] == ["a"]


def test_set_adds_attrs_to_an_open_span():
    with spans.span("off") as off:
        off.set(n=1)                  # the shared no-op while unarmed
    with spans.recording() as rec:
        with spans.span("work", seq=4) as sp:
            sp.set(done=3, kind="x")
    (s,) = rec.spans
    assert s.attrs == {"seq": 4, "done": 3, "kind": "x"}
    assert rec.summary()["work"]["done"] == 3


def test_every_decision_event_holds_the_search_phases(recorded):
    res, rec = recorded
    decides = [k for k, s in enumerate(rec.spans)
               if s.name == "policy.decide"]
    events = [s for s in rec.spans if s.name == "engine.event"]
    assert len(events) == res.events
    assert [s.attrs["seq"] for s in events] == list(range(1, res.events + 1))
    assert decides
    held = set()
    for k in decides:
        pd = rec.spans[k]
        ev = rec.spans[pd.parent]
        assert ev.name == "engine.event" and ev.attrs["kind"] == "arrive"
        assert pd.seq == ev.attrs["seq"]
        held.add(pd.parent)
        (search,) = [s for s in children(rec, k)
                     if s.name == "scheduler.search"]
        search_k = rec.spans.index(search)
        assert [s.name for s in children(rec, search_k)] == \
            list(SEARCH_PHASES)
        assert all(s.seq == pd.seq for s in children(rec, search_k))
    # one policy call per decision event, and the request build before it
    assert len(held) == len(decides)
    for k in held:
        names = [s.name for s in children(rec, k)]
        assert names.count("policy.decide") == 1
        assert names[0] == "engine.requests"
    dispatch = rec.summary()["scheduler.dispatch"]
    assert dispatch["n"] == len(decides) == dispatch["B"]
    assert dispatch["regime=round"] + dispatch.get("regime=pass", 0) == \
        len(decides)
    assert 0 < dispatch["rows_real"] <= dispatch["rows_padded"]
    assert dispatch["slots"] >= len(decides)
    assert dispatch["h2d_bytes"] > 0


def test_every_search_moves_one_array_each_way(recorded):
    # the packed boundary (DESIGN.md §8): one buffer in, one array out,
    # on every device search of the run
    res, rec = recorded
    dispatch = [s for s in rec.spans if s.name == "scheduler.dispatch"]
    fetch = [s for s in rec.spans if s.name == "scheduler.fetch"]
    assert dispatch and len(fetch) == len(dispatch)
    assert all(s.attrs["h2d_arrays"] == 1 for s in dispatch)
    assert all(s.attrs["d2h_arrays"] == 1 for s in fetch)
    assert all(s.attrs["h2d_bytes"] > 0 for s in dispatch)
    assert zlib.crc32(repr(res.event_log).encode()) == \
        zlib.crc32(repr(replay().event_log).encode())


def test_spans_nest_in_time(recorded):
    _, rec = recorded
    for s in rec.spans:
        assert s.t1 is not None and s.t1 >= s.t0
        if s.parent >= 0:
            p = rec.spans[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1
            assert s.seq == p.seq


def test_summary_self_time_and_decision_latency(recorded):
    _, rec = recorded
    summ = rec.summary()
    search = summ["scheduler.search"]
    phases = sum(summ[n]["total_s"] for n in SEARCH_PHASES)
    assert search["self_s"] == pytest.approx(search["total_s"] - phases,
                                             abs=1e-9)
    assert "seq" not in summ["engine.event"]
    lat = rec.durations("engine.event", holding="policy.decide")
    assert len(lat) == summ["policy.decide"]["n"]
    assert min(lat) >= 0.0


def test_profiled_run_decides_the_same(recorded):
    res, rec = recorded
    bare = replay()
    profiled = replay(profile=True)
    crc = [zlib.crc32(repr(r.event_log).encode())
           for r in (bare, profiled, res)]
    assert crc[0] == crc[1] == crc[2]
    prof = profiled.profile
    assert prof["decide_calls"] == rec.summary()["policy.decide"]["n"]
    assert set(prof["handlers_by_kind"]) == \
        {s.attrs["kind"] for s in rec.spans if s.name == "engine.event"}
    assert spans.armed() is None


def test_profile_records_into_an_armed_recorder():
    with spans.recording() as rec:
        with spans.span("outer"):
            res = replay(profile=True)
    assert res.profile["decide_calls"] == rec.summary()["policy.decide"]["n"]
    assert rec.spans[0].name == "outer"
    assert {s.parent for s in rec.spans if s.name == "engine.event"} == {0}


def test_armed_spans_reach_the_profiler_trace(tmp_path):
    import jax
    with spans.recording():
        jax.profiler.start_trace(str(tmp_path))
        with spans.span("outer"):
            with spans.span("inner"):
                pass
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events}
    assert {"repro.outer", "repro.inner"} <= names
