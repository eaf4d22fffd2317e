"""A run whose timed path is broken underneath reads `correct: false`.

Each test plants one fault in the program (the chip is skipped, the rest
of a run is driven as bench/run.py drives it) and checks that the
comparison with the reference catches it: a search that returns its
state unchanged, half of a request's jobs left out of the search, an
answer altered where it is produced. The cell runs on one chip, so no
exchange between chips can be left out."""
import pytest

from bench.tests.test_cells import cpu_run
from repro.core import scheduler_jax
from repro.metro import policies

CELL = "table6.replan"
FLIP = {"cloud": "edge", "edge": "device", "device": "cloud"}


@pytest.fixture
def kernel(monkeypatch):
    """Replace the device search with f(original, jobs, *a, **kw)."""
    orig = scheduler_jax.tabu_search_batched

    def plant(f):
        monkeypatch.setattr(scheduler_jax, "tabu_search_batched",
                            lambda jobs, *a, **kw: f(orig, jobs, *a, **kw))
    return plant


def test_search_returns_state_unchanged(kernel):
    # zero rounds: the kernel hands back its starting assignment
    kernel(lambda orig, jobs, *a, **kw: orig(jobs, *a,
                                             **dict(kw, max_rounds=0)))
    assert cpu_run(CELL)["correct"] is False


def test_half_of_the_jobs_left_out(kernel):
    def half(orig, jobs, *a, **kw):
        objs, assigns = orig(jobs, *a, **kw)
        _, start = orig(jobs, *a, **dict(kw, max_rounds=0))
        out = []
        for got, keep, ward in zip(assigns, start, jobs):
            h = len(ward) // 2
            out.append(list(got[:h]) + list(keep[h:len(ward)])
                       + list(got[len(ward):]))
        return objs, out
    kernel(half)
    assert cpu_run(CELL)["correct"] is False


def test_answer_altered(monkeypatch):
    orig = policies.TabuPolicy.decide

    def altered(self, requests, now):
        return [[FLIP[a[0]]] + a[1:] for a in orig(self, requests, now)]
    monkeypatch.setattr(policies.TabuPolicy, "decide", altered)
    assert cpu_run(CELL)["correct"] is False
