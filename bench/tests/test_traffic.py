"""The configuration's job table is the paper's Table VI as the program
ships it, and the stream sends every seed the same periods (each the
table's release times with its rows in one pairing), in another
order."""
import pytest

from bench import common
from bench.traffic import gen
from repro.core.problems import table6_jobs

SEEDS = [0, 7, 2**31 + 5, 2**40 + 1]


def _table6():
    spec = common.load_spec()
    return common.load_config(spec, "table6")


def _work(traces, period=25.0):
    """Each period's jobs, as (release within the period, sizes), and
    the periods as a sorted list."""
    by_period = {}
    for t in traces:
        for j in t:
            p = int(j.name.split("-")[0][1:])
            by_period.setdefault(p, []).append(
                (j.release - p * period, j.weight,
                 tuple(sorted(j.proc.items())),
                 tuple(sorted(j.trans.items()))))
    return sorted(sorted(v) for v in by_period.values())


def test_table_is_the_papers_table6():
    rows = gen.table_rows(_table6())
    want = table6_jobs()
    assert [r["name"] for r in rows] == [j.name for j in want]
    for r, j in zip(rows, want):
        assert (r["release"], r["weight"]) == (j.release, j.weight)
        assert (r["cloud_proc"], r["edge_proc"], r["device_proc"]) == \
            (j.proc["cloud"], j.proc["edge"], j.proc["device"])
        assert (r["cloud_trans"], r["edge_trans"]) == \
            (j.trans["cloud"], j.trans["edge"])
        assert j.trans["device"] == 0


def test_one_period_holds_table6():
    # Table VI's release times and its ten jobs, in one pairing
    mix = dict(gen.load_mix("table6_stream"), periods=1)
    want = table6_jobs()

    def sizes(j):
        return (j.weight, sorted(j.proc.items()), sorted(j.trans.items()))
    for seed in SEEDS:
        got = gen.stream(_table6(), mix, seed)[0]
        assert sorted(j.release for j in got) == \
            sorted(j.release for j in want)
        assert sorted(map(sizes, got)) == sorted(map(sizes, want))


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_sends_the_same_work(seed):
    mix = gen.load_mix("table6_stream")
    cfg = _table6()
    got = gen.stream(cfg, mix, seed)
    assert _work(got) == _work(gen.stream(cfg, mix, 1))
    assert len(got) == 1 and len(got[0]) == 10 * mix["periods"]
    names = [j.name for j in got[0]]
    assert len(set(names)) == len(names)
    releases = [j.release for j in got[0]]
    assert releases == sorted(releases)


def test_same_seed_same_inputs():
    mix = gen.load_mix("table6_stream")
    cfg = _table6()
    assert gen.stream(cfg, mix, 11) == gen.stream(cfg, mix, 11)
    assert gen.stream(cfg, mix, 11) != gen.stream(cfg, mix, 12)
