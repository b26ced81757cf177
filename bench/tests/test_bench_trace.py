"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on intervals made by hand and on a small trace recorded on a TPU v5e
(``bench/data/record_trace.py``)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

SMALL = ROOT / "bench" / "data" / "small_trace.xplane.pb"


def _summary():
    ops = [trace._op("%fusion.1 = f32[8]{0} fusion(%a)", 10, 20),
           trace._op("%fusion.2 = f32[8]{0} fusion(%b)", 15, 18),
           trace._op("%kernel_a.3 = bf16[4,2]{1,0:T(8,128)} custom-call(%c)",
                     30, 40)]
    return trace.Summary(
        window=(0, 50), busy={"/device:TPU:0": trace._merge(
            [(o.start, o.end) for o in ops])},
        ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": 2},
        spans={"tick": [(5, 25)], "loadgen": [(25, 45)]})


def test_busy_is_the_union_of_op_intervals():
    s = _summary()
    assert s.busy["/device:TPU:0"] == [(10, 20), (30, 40)]
    assert s.busy_s == pytest.approx(20e-9)
    assert s.window_s == pytest.approx(50e-9)
    assert s.op_time_s("^kernel_a$") == pytest.approx(10e-9)
    assert s.op_time_s("^fusion$") == pytest.approx(13e-9)
    assert s.ops["/device:TPU:0"][2].result == "bf16[4,2]"


def test_gaps_are_named_by_the_open_span():
    s = _summary()
    assert s.gaps() == [("tick", 0, 10), ("loadgen", 20, 30),
                        ("none", 40, 50)]
    assert s.idle_in("tick") == (pytest.approx(10e-9), 1)
    b = s.breakdown()
    assert b["device_ops"] == [["fusion f32[8]", pytest.approx(13e-9)],
                               ["kernel_a bf16[4,2]", pytest.approx(10e-9)]]
    assert len(b["idle_gaps"]) == 3


@pytest.fixture(scope="module")
def small():
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_file(str(SMALL)))


def test_small_trace_window_and_spans(small):
    assert small.span_count("tick") == 3
    assert small.span_count("loadgen") == 3
    assert 0 < small.busy_s < small.window_s
    assert len(small.busy) == 1


def test_small_trace_busy_and_gaps_fill_the_window(small):
    idle = sum(e - s for _, s, e in small.gaps()) * 1e-9
    assert idle + small.busy_s == pytest.approx(small.window_s, rel=1e-9)
    # the 2 ms pauses between ticks are the longest gaps. The device clock
    # of this trace runs 1.4-2.5 ms behind the host's, so a gap is named by
    # the span open at its midpoint only to within that.
    longest = sorted(small.gaps(), key=lambda g: g[1] - g[2])[0]
    assert longest[2] - longest[1] > 2e6
    assert longest[0] in ("loadgen", "tick")


def test_small_trace_ops_and_programs(small):
    total = small.op_time_s(".")
    assert total >= small.busy_s
    top = small.breakdown()["device_ops"]
    assert 0 < len(top) <= 10
    assert sum(v for _, v in top) <= total + 1e-12
    assert list(small.modules.values())[0] >= 3
