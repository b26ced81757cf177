"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The harness wraps the traced part of its window in a host annotation
(``WINDOW``) and each call into the system in a span (``tick``,
``submit``, ``loadgen``). The reduction reads, on one clock:

* device busy time: the union of the intervals in which an operation ran
  on each chip (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the window and averaged over chips;
* time per device operation, by name, and the programs launched (the
  ``XLA Modules`` line);
* the idle gaps between busy intervals, each named by the host span that
  was open at its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
SPANS = ("tick", "idle_tick", "submit", "loadgen")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


_HLO = re.compile(r"^%([\w.-]+?)(?:\.\d+)? = (\S+) ")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str           # the HLO instruction's name without its number
    start: int          # ns, on the trace's clock
    end: int
    result: str         # its result type, layouts left out


def _op(name: str, start: int, end: int) -> Op:
    """An ``XLA Ops`` event: the trace names it by its whole HLO
    instruction (``%cim_matmul_fused_pallas.72 = f32[256,8192]{...}
    custom-call(...)``)."""
    m = _HLO.match(name)
    if not m:
        return Op(name, start, end, "")
    return Op(m.group(1), start, end, _LAYOUT.sub("", m.group(2))[:60])


@dataclasses.dataclass
class Summary:
    window: Tuple[int, int]
    busy: Dict[str, List[Tuple[int, int]]]   # device -> merged intervals
    ops: Dict[str, List[Op]]                 # device -> ops in the window
    modules: Dict[str, int]                  # device -> programs launched
    spans: Dict[str, List[Tuple[int, int]]]  # host span name -> intervals

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds per chip, averaged over the chips traced."""
        if not self.busy:
            return 0.0
        tot = sum(_length(iv) for iv in self.busy.values())
        return tot * 1e-9 / len(self.busy)

    def op_time_s(self, pattern: str) -> float:
        """Seconds of device operations whose instruction name matches
        ``pattern`` (a regular expression), summed over chips."""
        rx = re.compile(pattern)
        return sum((o.end - o.start) for ops in self.ops.values()
                   for o in ops if rx.search(o.name)) * 1e-9

    def top_ops(self, n: int = 10) -> List[list]:
        """The operations that took most time, by instruction name and
        result type. Control flow (``while``, ``conditional``, ``call``)
        is left out: its event spans the operations of its body."""
        by = defaultdict(int)
        for ops in self.ops.values():
            for o in ops:
                if o.name not in _CONTAINERS:
                    by[f"{o.name} {o.result}".strip()] += o.end - o.start
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def span_count(self, name: str) -> int:
        return len(self.spans.get(name, ()))

    def gaps(self) -> List[Tuple[str, int, int]]:
        """Idle gaps of the first chip inside the window, each named by the
        host span open at its midpoint ("none" where no span was open)."""
        if not self.busy:
            return []
        dev = sorted(self.busy)[0]
        w0, w1 = self.window
        out, t = [], w0
        for s, e in self.busy[dev] + [(w1, w1)]:
            if s > t:
                out.append((self.span_at((s + t) // 2), t, s))
            t = max(t, e)
        return out

    def span_at(self, t: int) -> str:
        for name in SPANS:
            iv = self.spans.get(name, [])
            i = bisect.bisect_right(iv, (t, float("inf"))) - 1
            if i >= 0 and iv[i][0] <= t < iv[i][1]:
                return name
        return "none"

    def idle_in(self, name: str) -> Tuple[float, int]:
        """(idle seconds of the first chip inside spans ``name``, number of
        such spans)."""
        iv = self.spans.get(name, [])
        if not self.busy or not iv:
            return 0.0, len(iv)
        dev = sorted(self.busy)[0]
        idle = sum((e - s) - _overlap(self.busy[dev], s, e) for s, e in iv)
        return idle * 1e-9, len(iv)

    def breakdown(self, n: int = 10) -> dict:
        gaps = sorted(self.gaps(), key=lambda g: g[1] - g[2])[:n]
        return {"device_ops": self.top_ops(n),
                "idle_gaps": [[g[0], (g[2] - g[1]) * 1e-9] for g in gaps]}


def _merge(iv: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(iv: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in iv)


def _overlap(merged: Sequence[Tuple[int, int]], s: int, e: int) -> int:
    i = max(bisect.bisect_right(merged, (s, float("inf"))) - 1, 0)
    tot = 0
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        tot += max(0, min(b, e) - max(a, s))
        i += 1
    return tot


def find_xplane(root: str) -> str:
    hits = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(hits, key=os.path.getmtime)


def reduce(profile, window: Optional[Tuple[int, int]] = None) -> Summary:
    """Summarise a ``jax.profiler.ProfileData``. The window is the host
    annotation ``WINDOW`` unless given."""
    spans: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    win = None
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        win = (int(ev.start_ns), int(ev.end_ns))
                    elif ev.name in SPANS:
                        spans[ev.name].append((int(ev.start_ns),
                                               int(ev.end_ns)))
        elif _DEVICE.match(plane.name):
            devices.append(plane)
    window = window or win
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    w0, w1 = window
    busy, ops, modules = {}, {}, {}
    for plane in devices:
        got: List[Op] = []
        nmod = 0
        for line in plane.lines:
            if line.name == "XLA Modules":
                nmod += sum(1 for ev in line.events
                            if w0 <= ev.start_ns < w1)
            elif line.name == "XLA Ops":
                for ev in line.events:
                    s, e = int(ev.start_ns), int(ev.end_ns)
                    if e <= w0 or s >= w1:
                        continue
                    got.append(_op(ev.name, max(s, w0), min(e, w1)))
        busy[plane.name] = _merge([(o.start, o.end) for o in got])
        ops[plane.name] = got
        modules[plane.name] = nmod
    return Summary(window, busy, ops, modules,
                   {k: sorted(v) for k, v in spans.items()})


def load(root: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(root)))
