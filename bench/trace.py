"""From a JAX profiler trace to the numbers the per-layer metrics read.

`load(log_dir)` reads the `.xplane.pb` that `jax.profiler` wrote and
keeps three things, all in nanoseconds on the trace's one clock:

* `ops`: every operation the device ran, per device plane, from the
  plane's "XLA Ops" line, each with the program ("XLA Modules" line) it
  ran in;
* `spans`: the benchmark's own host spans (`bench.window`,
  `bench.decide`, `bench.plan`), written with
  `jax.profiler.TraceAnnotation`;
* `devices`: how many device planes there were.

`summarize` reduces that to the device's busy time inside the window
(the union of op intervals, averaged over devices), the busy time inside
each kind of host span, the operations that took the most device time
(each named with its program), and the longest idle gaps, each named by
the host span open at the time. The reduction is kept as plain functions of that small
record, so bench/tests checks it on a recorded trace.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def load(log_dir: str) -> dict:
    """The compact record of the newest trace under `log_dir`."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    ops: List[list] = []
    spans: List[list] = []
    devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            devices += 1
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.end_ns, e.name)
                for e in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else ()))
            line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            if line is None:
                continue
            k = 0
            for e in line.events:
                # the program an op ran in: the module span around it
                while k + 1 < len(modules) and modules[k + 1][0] <= e.start_ns:
                    k += 1
                mod = modules[k][2] if modules and \
                    modules[k][0] <= e.start_ns <= modules[k][1] else ""
                ops.append([devices - 1, e.name, mod, e.start_ns, e.end_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name[len(SPAN_PREFIX):],
                                      e.start_ns, e.end_ns])
    return {"ops": ops, "spans": spans, "devices": devices}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def summarize(tr: dict, idle_label: str = "host") -> dict:
    """Busy and idle time of the device inside the `window` span, in
    seconds. `idle_label` names host time outside every other span."""
    windows = [(s, e) for name, s, e in tr["spans"] if name == "window"]
    if not windows or not tr["devices"] or not tr["ops"]:
        return {}
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    per_dev: Dict[int, List[Interval]] = defaultdict(list)
    for dev, _, _, s, e in tr["ops"]:
        per_dev[dev].append((s, e))
    busy = {d: union(clip(v, lo, hi)) for d, v in per_dev.items()}
    n = tr["devices"]
    busy_s = sum(sum(e - s for s, e in v) for v in busy.values()) \
        * 1e-9 / n
    kinds: Dict[str, List[Interval]] = defaultdict(list)
    for name, s, e in tr["spans"]:
        if name != "window":
            kinds[name].append((s, e))
    kinds = {k: union(clip(v, lo, hi)) for k, v in kinds.items()}
    span_s = {k: sum(e - s for s, e in v) * 1e-9 for k, v in kinds.items()}
    busy_in = {k: sum(overlap(b, v) for b in busy.values()) * 1e-9 / n
               for k, v in kinds.items()}
    # device time by operation, named with the program it ran in
    by_op: Dict[str, float] = defaultdict(float)
    for _, name, mod, s, e in tr["ops"]:
        d = (min(e, hi) - max(s, lo)) * 1e-9
        if d > 0:
            by_op[f"{mod}:{name}" if mod else name] += d / n
    # idle gaps on device 0, named by the host span open at their middle
    gaps = []
    first = busy.get(min(busy), []) if busy else []
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    starts = {k: [a for a, _ in v] for k, v in kinds.items()}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append([_label(kinds, starts, (s + e) / 2, idle_label),
                         (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": window_s, "busy_s": busy_s, "span_s": span_s,
            "busy_in": busy_in, "top_ops": _top(by_op),
            "idle_gaps": gaps[:10]}


def _label(kinds: Dict[str, List[Interval]], starts: Dict[str, list],
           t: float, default: str) -> str:
    """The kind of host span open at instant t."""
    for k, v in kinds.items():
        i = bisect.bisect_right(starts[k], t) - 1
        if i >= 0 and v[i][1] >= t:
            return k
    return default


def _top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[name, s] for name, s in sorted(d.items(),
                                            key=lambda x: -x[1])[:k]]
