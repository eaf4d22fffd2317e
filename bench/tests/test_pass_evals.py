"""The reader of `pass_evals_per_search` (bench/metrics/), on summaries as
the program's span recorder makes them."""

import pytest

from bench import common


def _read(record):
    return common.metric_reader("pass_evals_per_search").read(record)


def test_pass_evals_per_search_reads_the_recorded_fetch_counter():
    # a summary as the program's recorder makes it: two `pass` searches
    # whose fetch counted 3 and 2 evaluations, and one `round` search,
    # whose fetch counts none
    from repro.utils import spans
    with spans.recording() as rec:
        for regime, evals in (("pass", 3), ("round", None), ("pass", 2)):
            with spans.span("scheduler.dispatch", regime=regime):
                pass
            with spans.span("scheduler.fetch", d2h_arrays=1) as fetch:
                if evals is not None:
                    fetch.set(pass_evals=evals)
    record = {"decisions": 3, "spans": rec.summary()}
    assert _read(record) == pytest.approx(2.5)


@pytest.mark.parametrize("record", [
    # only `round` searches
    {"decisions": 3, "spans": {
        "scheduler.dispatch": {"n": 2, "regime=round": 2},
        "scheduler.fetch": {"n": 2, "d2h_arrays": 2}}},
    # `pass` searches from a program that does not count evaluations
    {"decisions": 3, "spans": {
        "scheduler.dispatch": {"n": 2, "regime=pass": 2},
        "scheduler.fetch": {"n": 2, "d2h_arrays": 2}}},
    # an untraced run
    {"decisions": 3, "seconds": 1.0, "decide_s": [0.1]},
], ids=["round_only", "no_counter", "untraced"])
def test_pass_evals_per_search_finds_nothing_without_pass_counts(record):
    assert _read(record) is None
