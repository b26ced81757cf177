#!/usr/bin/env python3
"""Run one cell as ``bench/run.py`` does and print, after its line, what
``bench/harness.py`` does not report yet (PERF.md section 7):

* ``counters``: the change in ``Engine.counters`` over the measured
  window, from the end of the warm-up to the window's close;
* ``iter_ms``: the window's mean milliseconds per scheduler iteration,
  and ``iter_ms_head`` over the part of the window that a traced run
  traces (the cell's ``trace_seconds``, from the profiler's start);
* ``idle_by_span`` (traced runs): the traced window's device idle seconds
  by the innermost program or harness span open over them
  (``bench/spans.py``), and ``tick_idle_in_program``, the share of the
  idle time inside the harness's ``tick`` spans that program spans hold;
  ``spans_per_step``, and ``drain_lag_ms``, from the device's last
  operation to each ``engine.drain``'s return, a check of the one clock.

  python3 bench/notes.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is ``{"notes": {...}}``; the line
before it is ``bench/run.py``'s own.
"""

from __future__ import annotations

import bisect
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import harness, spans, trace  # noqa: E402

run = harness.load_module(BENCH / "run.py", "run")


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b}


def _drain_lag_ms(sp, summary):
    """[min, median] over ``engine.drain`` spans of the time from the
    first chip's last operation before the drain returned to its return:
    the host cannot return before the device is done, so a negative lag
    is the two clocks' disagreement."""
    if not summary.busy:
        return None
    busy = summary.busy[sorted(summary.busy)[0]]
    ends = [e for _, e in busy]
    lags = []
    for s in sp.spans:
        if s.name == "engine.drain":
            i = bisect.bisect_right(ends, s.end) - 1
            if i >= 0:
                lags.append((s.end - ends[i]) * 1e-6)
    lags.sort()
    return [lags[0], lags[len(lags) // 2]] if lags else None


def main(argv=None) -> int:
    import jax

    seen = {}

    def snap(key):
        if key not in seen and "engine" in seen:
            seen[key] = (time.perf_counter(),
                         seen["engine"].counters.snapshot())

    run_cell = harness.run

    def ran(cell, *a, **kw):
        seen["trace_s"] = float(cell.shape["trace_seconds"])
        return run_cell(cell, *a, **kw)

    watch = harness.watch_fused_step

    def watch_engine(engine):
        seen["engine"] = engine
        return watch(engine)

    run_until = harness.Driver.run_until

    def warmed(self, *a, **kw):
        run_until(self, *a, **kw)
        seen.pop("start", None)
        snap("start")

    step = harness.Driver.step
    head = []                      # (clock, iterations) after each step

    def stepped(self, *a, **kw):
        busy = step(self, *a, **kw)
        if "start" in seen and "end" not in seen:
            head.append((time.perf_counter(),
                         seen["engine"].counters.iterations))
        return busy

    window = harness.Window

    def closed(*a, **kw):
        snap("end")
        return window(*a, **kw)

    start_trace = jax.profiler.start_trace

    def started(*a, **kw):
        out = start_trace(*a, **kw)
        snap("trace_start")
        return out

    load = trace.load

    def loaded(root):
        from jax.profiler import ProfileData

        prof = ProfileData.from_file(trace.find_xplane(root))
        summary = trace.reduce(prof)
        sp = spans.reduce(prof)
        by = sp.idle_by_span(summary)
        prog = sum(v for k, v in by.items() if k.startswith(spans.PREFIXES))
        seen["idle_by_span"] = by
        seen["tick_idle_in_program"] = (
            prog / (prog + by.get("tick", 0.0)) if prog else None)
        seen["spans_per_step"] = len(sp.spans) / max(
            len(sp.working_steps()), 1)
        seen["drain_lag_ms"] = _drain_lag_ms(sp, summary)
        return load(root)

    harness.run = ran
    harness.watch_fused_step = watch_engine
    harness.Driver.run_until = warmed
    harness.Driver.step = stepped
    harness.Window = closed
    jax.profiler.start_trace = started
    trace.load = loaded
    rc = run.main(argv)
    if rc != 0 or "end" not in seen:
        return rc
    (t0, c0), (t1, c1) = seen["start"], seen["end"]
    d = _delta(c0, c1)
    notes = {"counters": d,
             "iter_ms": 1e3 * (t1 - t0) / max(d["iterations"], 1)}
    h0, hc = seen.get("trace_start", seen["start"])
    cut = [h for h in head if h[0] <= h0 + seen["trace_s"]]
    if cut:
        notes["iter_ms_head"] = 1e3 * (cut[-1][0] - h0) / max(
            cut[-1][1] - hc["iterations"], 1)
    for k in ("idle_by_span", "tick_idle_in_program", "spans_per_step",
              "drain_lag_ms"):
        if k in seen:
            notes[k] = seen[k]
    print(json.dumps({"notes": notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
