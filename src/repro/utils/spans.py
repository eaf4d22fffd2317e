"""Host span recorder: where the program's wall time goes, from the metro
engine's event loop down to the device fetch (DESIGN.md §15, "Engine
self-profile").

    with spans.span("scheduler.pack"):
        ...

marks one piece of host work; `set(**attrs)` on the entered span adds
counts known only at its end. While no recorder is armed, `span` hands
back one shared object whose enter and exit do nothing: no clock read,
no allocation. `recording()` arms a recorder for a block:

    with spans.recording() as rec:
        simulate_metro(traces, policy)
    rec.summary()["scheduler.fetch"]["total_s"]

While armed, each span records its name, its parent, its start and end
(`time.perf_counter_ns`) and its attrs in memory; nothing is written out
until a caller reads `rec.spans`, `rec.summary()` or `rec.durations()`.
Spans of one engine event share its sequence number: a span given a
`seq` attr passes it to every span opened inside it. Each armed span
also enters `jax.profiler.TraceAnnotation("repro." + name)` when JAX is
already imported, so a running profiler trace holds the same span on its
host plane, on the clock of the device's own operations; a pure-Python
run never imports JAX for it.

The recorder keeps its spans as columns of plain values, not as one
object per span, so a long recording leaves the garbage collector no
more objects to scan than an unrecorded run.

This module is the one place the program reads the wall clock for
profiling: simulated time lives in the engine's event heap, so a
recorded run makes the same decisions as an unrecorded one.
"""
from __future__ import annotations

import contextlib
import sys
from time import perf_counter_ns
from typing import Dict, Iterator, List, NamedTuple, Optional

PREFIX = "repro."

_armed: Optional["Recorder"] = None


class _Off:
    """The span handed out while no recorder is armed."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **attrs):
        return None


_OFF = _Off()


class Span(NamedTuple):
    """One recorded span. `parent` is the index of the enclosing span in
    `Recorder.spans` (-1 for none), `seq` the engine event it belongs to
    (None outside an event), `t0`/`t1` `perf_counter_ns` readings (`t1`
    is None while the span is open)."""
    name: str
    parent: int
    seq: Optional[int]
    t0: int
    t1: Optional[int]
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9


class _Open:
    """The context manager of one armed span, alive while it is open."""
    __slots__ = ("_rec", "_name", "_attrs", "_k", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec = rec
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        rec = self._rec
        if rec._open:
            parent = rec._open[-1]
            seq = rec._seq[parent]
        else:
            parent, seq = -1, None
        self._k = len(rec._name)
        rec._open.append(self._k)
        rec._name.append(self._name)
        rec._parent.append(parent)
        rec._seq.append(self._attrs.get("seq", seq))
        rec._attrs.append(self._attrs)
        rec._t1.append(None)
        rec._view = None
        self._ann = None
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(PREFIX + self._name)
            self._ann.__enter__()
        rec._t0.append(perf_counter_ns())
        return self

    def __exit__(self, *exc):
        rec = self._rec
        rec._t1[self._k] = perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rec._open.pop()
        rec._view = None
        return None

    def set(self, **attrs):
        """Add attrs known only once the span's work is done."""
        self._attrs.update(attrs)


class Recorder:
    """The spans of one armed block, in the order they opened."""

    def __init__(self):
        self._name: List[str] = []
        self._parent: List[int] = []
        self._seq: List[Optional[int]] = []
        self._t0: List[int] = []
        self._t1: List[Optional[int]] = []
        self._attrs: List[dict] = []
        self._open: List[int] = []
        self._view: Optional[List[Span]] = None

    @property
    def spans(self) -> List[Span]:
        """Every span so far, as `Span` records (built when read)."""
        if self._view is None:
            self._view = [Span(*row) for row in zip(
                self._name, self._parent, self._seq, self._t0, self._t1,
                self._attrs)]
        return self._view

    def summary(self) -> Dict[str, dict]:
        """Per span name, over the closed spans: `n`, `total_s`, `self_s`
        (total minus the time its child spans cover), each numeric attr
        summed, and each other attr counted as `<key>=<value>`. `seq` is
        an identifier and is not summed."""
        child_ns = [0] * len(self._name)
        for parent, t0, t1 in zip(self._parent, self._t0, self._t1):
            if t1 is not None and parent >= 0:
                child_ns[parent] += t1 - t0
        out: Dict[str, dict] = {}
        for name, t0, t1, attrs, child in zip(
                self._name, self._t0, self._t1, self._attrs, child_ns):
            if t1 is None:
                continue
            agg = out.setdefault(name,
                                 {"n": 0, "total_s": 0.0, "self_s": 0.0})
            agg["n"] += 1
            agg["total_s"] += (t1 - t0) * 1e-9
            agg["self_s"] += (t1 - t0 - child) * 1e-9
            for key, v in attrs.items():
                if key == "seq":
                    continue
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    agg[key] = agg.get(key, 0) + v
                else:
                    key = f"{key}={v}"
                    agg[key] = agg.get(key, 0) + 1
        return out

    def durations(self, name: str, holding: Optional[str] = None
                  ) -> List[float]:
        """Seconds of each closed `name` span; with `holding`, only those
        with a `holding` span somewhere inside."""
        keep = None
        if holding is not None:
            keep = set()
            for k, n in enumerate(self._name):
                if n != holding:
                    continue
                p = self._parent[k]
                while p >= 0 and self._name[p] != name:
                    p = self._parent[p]
                if p >= 0:
                    keep.add(p)
        return [(t1 - t0) * 1e-9 for k, (n, t0, t1) in enumerate(
                    zip(self._name, self._t0, self._t1))
                if n == name and t1 is not None
                and (keep is None or k in keep)]


def span(name: str, **attrs):
    """A context manager around one piece of host work: recorded while a
    recorder is armed, the shared no-op otherwise."""
    if _armed is None:
        return _OFF
    return _Open(_armed, name, attrs)


def armed() -> Optional[Recorder]:
    """The armed recorder, or None."""
    return _armed


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Arm a fresh recorder for the block and yield it. Inside a block
    that is already recording, yield the armed recorder instead, so an
    inner caller's spans land beside the outer caller's."""
    global _armed
    if _armed is not None:
        yield _armed
        return
    _armed = Recorder()
    try:
        yield _armed
    finally:
        _armed = None
