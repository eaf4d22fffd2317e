"""Runner for metro mixes of several hospitals' care units: the
`metro_replay` runner over one ward per unit per hospital
(bench/traffic/units.py), all sharing the configuration's cloud. Every
seed replays the same trace, so every run does the same work.

Set-up, the untraced window and the comparison with the plain
reference (`plan_gap` over every answer timed in a window, `commit_gap`
over every committed replay, the cloud's capacity checked across all
wards) are that runner's; set-up also keeps its replay's answers.

A traced window (`annotate`) is one replay of which only `LIVE`
decisions in its middle are live and measured. In a pooled-cloud
replay about half the device searches run the `pass` regime, thousands
of small device operations each; the profiler takes minutes to write
out and read back the trace of a whole replay, and over two minutes for
a fifth of one. The middle of the replay is its steady state, with the
cloud queue built up. The rest of the replay is answered as set-up's
replay of the same trace answered it, without the device, which leaves
the engine in the same state (a diverging request stops the run). Around the live stretch the
runner opens the benchmark's `window` span and arms the program's span
recorder (`repro.utils.spans.recording()`), and the record keeps what
the per-layer metrics of this mix read:

* `spans`: the recorder's summary, per span name (`engine.requests`
  with its counts, `scheduler.dispatch` with its counters, ...);
* `searches`: one dict per device search, the `scheduler.dispatch`
  attrs (`rows_real`, `rows_padded`, `slots`, `regime`, and
  `reserved_rows` where the program counts them) and `search_s`, from
  the dispatch's start to the end of the `scheduler.fetch` after it.
"""
from __future__ import annotations

import time
from typing import List

from bench.common import span
from bench.runners import metro_replay
from bench.traffic import units

LIVE = 90            # decisions a traced window measures


class Cell(metro_replay.Cell):
    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg = cfg
        self.traces = units.streams(cfg, mix)    # the same for every seed
        self.machines = dict(cfg["machines_per_tier"])
        self.results: list = []
        self.answers: list = []
        self.replayed: list = []         # set-up's (request, tiers)

    def setup(self) -> None:
        self._replay([], self.replayed, False)

    def window(self, seconds: float, annotate: bool) -> dict:
        if not annotate:
            return super().window(seconds, annotate)
        from repro.metro import make_policy, simulate_metro
        log: List[tuple] = []
        kw = dict(self.cfg["replan_policy"])
        live = metro_replay.TimedPolicy(make_policy(kw.pop("name"), **kw),
                                        log, self.answers, True)
        start = max(0, (len(self.replayed) - LIVE) // 2)
        staged = Staged(live, self.replayed, start, start + LIVE)
        self.results.append(simulate_metro(
            self.traces, policy=staged, machines_per_tier=self.machines))
        staged.close()
        return {"seconds": staged.t1 - staged.t0,
                "decide_s": [b - a for a, b in log],
                "decisions": len(log),
                "spans": staged.rec.summary(),
                "searches": searches(staged.rec.spans)}


class Staged:
    """The policy of a traced replay: the timed `live` policy for the
    requests numbered [start, stop), with the `window` span and the span
    recorder open around them; `replayed`'s answers for the others."""

    def __init__(self, live, replayed: list, start: int, stop: int):
        self.live = live
        self.replayed = replayed
        self.start, self.stop = start, stop
        self.k = 0                       # requests answered so far
        self.rec = self._recording = self._window = None
        self.t0 = self.t1 = None

    def __getattr__(self, key):
        return getattr(self.live, key)

    def decide(self, requests, now):
        if self.k >= self.start and self.t0 is None:
            self._open()
        if self.k >= self.stop:
            self.close()
        k, self.k = self.k, self.k + len(requests)
        if self._window is not None:
            return self.live.decide(requests, now)
        out = []
        for req, (was, tiers) in zip(requests, self.replayed[k:]):
            if _jobs(req) != _jobs(was):
                raise RuntimeError(f"request {k}: {_jobs(req)}; set-up's "
                                   f"replay had {_jobs(was)}")
            out.append(list(tiers))
            k += 1
        if len(out) != len(requests):
            raise RuntimeError("the traced replay has more requests than "
                               "set-up's")
        return out

    def _open(self) -> None:
        from repro.utils import spans
        self._window = span("window", True)
        self._window.__enter__()
        self._recording = spans.recording()
        self.rec = self._recording.__enter__()
        self.t0 = time.perf_counter()

    def close(self) -> None:
        """End the live stretch, if it is open."""
        if self._window is None:
            return
        self.t1 = time.perf_counter()
        self._recording.__exit__(None, None, None)
        self._window.__exit__(None, None, None)
        self._window = None


def _jobs(req) -> tuple:
    """Which ward's which jobs a request asks about."""
    return req.ward, [s.name for s in req.shifted]


def searches(recorded) -> List[dict]:
    """Each `scheduler.dispatch` span's attrs, with `search_s` up to the
    end of the `scheduler.fetch` that follows it under the same parent."""
    out, dispatched = [], {}
    for s in recorded:
        if s.t1 is None:
            continue
        if s.name == "scheduler.dispatch":
            dispatched[s.parent] = s
        elif s.name == "scheduler.fetch" and s.parent in dispatched:
            d = dispatched.pop(s.parent)
            out.append(dict(d.attrs, search_s=(s.t1 - d.t0) * 1e-9))
    return out
