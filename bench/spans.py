"""Reduce the serving program's own spans from a profiler trace.

The engine and the front end annotate each phase of a scheduler
iteration with a ``jax.profiler.TraceAnnotation`` whose name starts with
``engine.`` or ``frontend.`` (PERF.md section 3 lists them). They land in
the same ``.xplane.pb`` as the device planes and the harness's own spans,
on the same clock. This module reads them inside the harness's window
(``trace.WINDOW``), with their stats (each ``engine.launch.*`` span
carries the rows its launch computed) and their nesting, and sets them
against the first chip's busy intervals (``trace.Summary``):

* ``pad_row_share``: padding rows over all rows the launches computed;
* ``sched_host_s``: host time per working ``engine.step`` spent in its
  ``engine.fill``, ``engine.stage`` and ``engine.launch.*`` spans, the
  work before the device can run the iteration;
* ``idle_host_s``: the chip's idle time inside program spans, per working
  ``engine.step``;
* ``idle_by_span``: the window's idle time by the innermost program or
  harness span open over it (``none`` where no span was open).

A working ``engine.step`` is one that launched a program; a step with no
slot occupied opens the span and launches nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import os
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import trace

PREFIXES = ("engine.", "frontend.")
STEP = "engine.step"
LAUNCH = "engine.launch."
HOST_WORK = ("engine.fill", "engine.stage")
# bench/run.py keeps a traced run's profile here until the run ends
TRACES = Path(__file__).resolve().parent.parent / ".bench_trace"


@dataclasses.dataclass
class Span:
    name: str
    start: int                # ns, on the trace's clock
    end: int
    stats: Dict[str, object]
    parent: Optional[int]     # index of the innermost program span around it


@dataclasses.dataclass
class Spans:
    window: Tuple[int, int]
    spans: List[Span]         # program spans starting in the window, by start

    def launches(self) -> List[Span]:
        return [s for s in self.spans if s.name.startswith(LAUNCH)]

    def step_of(self, i: int) -> Optional[int]:
        """Index of the ``engine.step`` span around span ``i``."""
        p = self.spans[i].parent
        while p is not None and self.spans[p].name != STEP:
            p = self.spans[p].parent
        return p

    def working_steps(self) -> List[int]:
        return sorted({self.step_of(i) for i, s in enumerate(self.spans)
                       if s.name.startswith(LAUNCH)} - {None})

    def pad_row_share(self) -> Optional[float]:
        """Percent of the rows the window's launches computed that were
        padding: chunk padding and decode rows of slots not decoding."""
        pad = sum(int(s.stats.get("pad_rows", 0)) for s in self.launches())
        rows = sum(int(s.stats.get("rows", 0)) for s in self.launches())
        if pad + rows <= 0:
            return None
        return 100.0 * pad / (pad + rows)

    def sched_host_s(self) -> Optional[float]:
        """Mean seconds per working step in its fill, stage and launch
        spans (their union: a launch may nest in a fill)."""
        steps = self.working_steps()
        if not steps:
            return None
        work: Dict[int, list] = {i: [] for i in steps}
        for i, s in enumerate(self.spans):
            if s.name in HOST_WORK or s.name.startswith(LAUNCH):
                st = self.step_of(i)
                if st in work:
                    work[st].append((s.start, s.end))
        tot = sum(_length(trace._merge(iv)) for iv in work.values())
        return tot * 1e-9 / len(steps)

    def idle_host_s(self, summary) -> Optional[float]:
        """The first chip's idle seconds inside program spans, per working
        step."""
        steps = self.working_steps()
        by = self.idle_by_span(summary)
        if not steps or not by:
            return None
        return sum(v for k, v in by.items()
                   if k.startswith(PREFIXES)) / len(steps)

    def idle_by_span(self, summary) -> Dict[str, float]:
        """Idle seconds of the first chip in the window, by the innermost
        span open over them: a program span inside a harness span
        (``trace.SPANS``) inside nothing (``none``). Empty without a
        device trace."""
        busy = getattr(summary, "busy", None)
        if not busy:
            return {}
        w0, w1 = self.window
        idle_to = _idle_until(busy[sorted(busy)[0]], w0)
        labels = [(s, e, 0, name)
                  for name, iv in getattr(summary, "spans", {}).items()
                  for s, e in iv]
        depth = [0] * len(self.spans)
        for i, s in enumerate(self.spans):
            if s.parent is not None:
                depth[i] = depth[s.parent] + 1
            labels.append((s.start, s.end, depth[i] + 1, s.name))
        marks = sorted({w0, w1} | {min(max(t, w0), w1)
                                   for s, e, _, _ in labels for t in (s, e)})
        opens: Dict[int, list] = {}
        closes: Dict[int, list] = {}
        for k, lab in enumerate(labels):
            opens.setdefault(lab[0], []).append(k)
            closes.setdefault(lab[1], []).append(k)
        active: Dict[int, Tuple[int, str]] = {
            k: (d, n) for k, (s, e, d, n) in enumerate(labels)
            if s < w0 < e}
        out: Dict[str, float] = {}
        for a, b in zip(marks, marks[1:]):
            for k in closes.get(a, ()):
                active.pop(k, None)
            for k in opens.get(a, ()):
                if labels[k][1] > a:
                    active[k] = labels[k][2:]
            idle = idle_to(b) - idle_to(a)
            if idle > 0:
                name = max(active.values())[1] if active else "none"
                out[name] = out.get(name, 0.0) + idle * 1e-9
        return out


def _length(iv: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in iv)


def _idle_until(merged: Sequence[Tuple[int, int]],
                t0: int) -> Callable[[int], int]:
    """t -> idle ns in [t0, t) given the merged busy intervals."""
    starts = [s for s, _ in merged]
    done = [0]
    for s, e in merged:
        done.append(done[-1] + e - s)

    def busy_until(t: int) -> int:
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0
        s, e = merged[i - 1]
        return done[i - 1] + min(t, e) - s

    base = busy_until(t0)
    return lambda t: (t - t0) - (busy_until(t) - base)


def reduce(profile, window: Optional[Tuple[int, int]] = None) -> Spans:
    """The program spans of a ``jax.profiler.ProfileData`` that start in
    the window (the host annotation ``trace.WINDOW`` unless given), nested
    by containment on their thread."""
    win = None
    lines = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            got = []
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    win = (int(ev.start_ns), int(ev.end_ns))
                elif ev.name.startswith(PREFIXES):
                    got.append((int(ev.start_ns), int(ev.end_ns), ev.name,
                                dict(ev.stats)))
            if got:
                lines.append(got)
    window = window or win
    if window is None:
        raise ValueError(f"trace has no {trace.WINDOW!r} annotation")
    w0, w1 = window
    spans: List[Span] = []
    for got in lines:
        stack: List[int] = []
        for s, e, name, stats in sorted(
                (g for g in got if w0 <= g[0] < w1),
                key=lambda g: (g[0], -g[1])):
            while stack and spans[stack[-1]].end <= s:
                stack.pop()
            parent = stack[-1] if stack and spans[stack[-1]].end >= e \
                else None
            spans.append(Span(name, s, e, stats, parent))
            stack.append(len(spans) - 1)
    order = sorted(range(len(spans)), key=lambda i: spans[i].start)
    index = {old: new for new, old in enumerate(order)}
    return Spans(window, [dataclasses.replace(
        spans[i], parent=None if spans[i].parent is None
        else index[spans[i].parent]) for i in order])


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Spans:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path))


def of(r) -> Optional[Spans]:
    """The program spans of a traced run's ``harness.Reading``: its
    ``spans`` where it has them, else read from the profile the run keeps
    under ``.bench_trace`` while its metrics are read. None when the run
    was not traced or the profile's window is not the reading's."""
    if hasattr(r, "spans"):
        return r.spans
    window = getattr(r.summary, "window", None)
    if window is None:
        return None
    try:
        path = trace.find_xplane(str(TRACES))
        got = _load(path, os.path.getmtime(path))
    except (FileNotFoundError, ValueError):
        return None
    return got if got.window == tuple(window) else None
