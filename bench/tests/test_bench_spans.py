"""The reduction of the serving program's spans (``bench/spans.py``) and
the three metrics that read it: on spans made by hand; on a small trace
of a tiny engine serving a scripted request set, recorded on a TPU v5e
(``bench/data/record_spans.py``); and on a trace recorded before the
program had spans, where every reader must return None."""

import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spans, trace  # noqa: E402

DATA = ROOT / "bench" / "data"
READERS = ("pad_row_share.chat", "sched_host_ms.chat", "idle_host_ms.chat")
# the scripted set of bench/data/record_spans.py: chunks of 8, 2 slots
LENS, MAX_NEW, CHUNK = (5, 19, 12, 9), (4, 6, 3, 5), 8


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                               name)


def _reading(summary, **extra):
    r = harness.Reading(summary=summary, ticks=[], recs=[], dims={},
                        shape={}, peaks={}, flops=None)
    for k, v in extra.items():
        setattr(r, k, v)
    return r


def _hand():
    def span(name, s, e, parent=None, **stats):
        return spans.Span(name, s, e, stats, parent)

    sp = spans.Spans((0, 100), [
        span("engine.step", 10, 60),
        span("engine.fill", 10, 15, 0),
        span("engine.stage", 15, 25, 0),
        span("engine.launch.step", 25, 30, 0, rows=6, pad_rows=2),
        span("engine.drain", 60, 70),
        span("frontend.pump", 70, 80),
        span("engine.step", 80, 82),          # no slot held: no launch
    ])
    summary = trace.Summary(
        window=(0, 100), busy={"/device:TPU:0": [(28, 65)]},
        ops={"/device:TPU:0": []}, modules={"/device:TPU:0": 1},
        spans={"tick": [(5, 85)], "loadgen": [(85, 95)]})
    return sp, summary


def test_hand_made_spans():
    sp, summary = _hand()
    assert sp.working_steps() == [0]
    assert sp.step_of(3) == 0 and sp.step_of(4) is None
    assert sp.pad_row_share() == pytest.approx(25.0)
    assert sp.sched_host_s() == pytest.approx(20e-9)
    by = sp.idle_by_span(summary)
    assert by == pytest.approx({
        "none": 10e-9, "tick": 8e-9, "engine.fill": 5e-9,
        "engine.stage": 10e-9, "engine.launch.step": 3e-9,
        "engine.drain": 5e-9, "frontend.pump": 10e-9, "engine.step": 2e-9,
        "loadgen": 10e-9})
    assert sum(by.values()) == pytest.approx(
        summary.window_s - summary.busy_s)
    # idle in every program span, the step that launched nothing included
    assert sp.idle_host_s(summary) == pytest.approx(35e-9)
    r = _reading(summary, spans=sp)
    got = {n: _reader(n).read(r) for n in READERS}
    assert got == pytest.approx({"pad_row_share.chat": 25.0,
                                 "sched_host_ms.chat": 20e-6,
                                 "idle_host_ms.chat": 35e-6})


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(DATA / "spans_trace.xplane.pb"))
    return spans.reduce(prof), trace.reduce(prof)


def test_recorded_spans_nest_and_count_the_scripted_rows(recorded):
    sp, summary = recorded
    ticks = summary.span_count("tick")
    assert ticks > 0
    top = [s.name for s in sp.spans if s.parent is None]
    assert top == ["frontend.admit", "engine.step", "engine.drain",
                   "frontend.pump"] * ticks
    assert len(sp.working_steps()) == ticks
    launches = sp.launches()
    assert {s.name for s in launches} <= {"engine.launch.step",
                                          "engine.launch.decode"}
    assert all(sp.step_of(sp.spans.index(s)) is not None for s in launches)
    pad = sum(-(-n // CHUNK) * CHUNK - n for n in LENS)
    assert sum(s.stats["prefill_rows"] for s in launches) == sum(LENS)
    assert sum(s.stats["decode_rows"] for s in launches) == sum(
        m - 1 for m in MAX_NEW)
    idle = sum(s.stats["pad_rows"] for s in launches) - pad
    assert 0 <= idle <= 2 * len(launches)
    assert sp.pad_row_share() == pytest.approx(
        100 * (pad + idle) / (pad + idle + sum(LENS) + sum(MAX_NEW)
                              - len(MAX_NEW)))
    assert 0 < sp.sched_host_s() < summary.window_s


def test_readers_find_the_run_profile(recorded, tmp_path, monkeypatch):
    """A traced run's reading carries no spans of its own; the readers
    find the profile the run keeps under ``.bench_trace`` and check that
    its window is the reading's."""
    sp, summary = recorded
    run = tmp_path / "cell-1"
    run.mkdir()
    shutil.copy(DATA / "spans_trace.xplane.pb", run / "t.xplane.pb")
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    found = {n: _reader(n).read(_reading(summary)) for n in READERS}
    given = {n: _reader(n).read(_reading(summary, spans=sp))
             for n in READERS}
    assert found == given
    assert found["pad_row_share.chat"] == pytest.approx(sp.pad_row_share())
    assert found["sched_host_ms.chat"] == pytest.approx(
        1e3 * sp.sched_host_s())
    if summary.busy:
        assert found["idle_host_ms.chat"] == pytest.approx(
            1e3 * sp.idle_host_s(summary))
    other = trace.Summary((0, 1), {}, {}, {}, {})
    assert all(_reader(n).read(_reading(other)) is None for n in READERS)


def test_readers_read_nothing_where_the_program_has_no_spans(
        tmp_path, monkeypatch):
    """The trace recorded before the program had spans: every reader
    returns None and none raises."""
    from jax.profiler import ProfileData

    run = tmp_path / "cell-1"
    run.mkdir()
    shutil.copy(DATA / "small_trace.xplane.pb", run / "t.xplane.pb")
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    summary = trace.reduce(ProfileData.from_file(str(run / "t.xplane.pb")))
    assert spans.of(_reading(summary)).spans == []
    assert all(_reader(n).read(_reading(summary)) is None for n in READERS)
