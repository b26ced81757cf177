"""The serving program's spans and counters (PERF.md section 3).

A tiny ``Engine`` behind a ``Frontend`` serves a scripted request set
under ``jax.profiler.trace``. Each working tick must show one
``engine.step`` holding its fill, stage and launch spans, then one
``engine.drain``; the rows each launch span carries must be the scripted
prompts, their chunk padding and the decoded tokens; ``Engine.counters``
must agree with the benchmark's outside reading of the slot state
(``bench.harness.tick_work``); and tracing must change no token and no
launch.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.model import build
from repro.serving.engine import Engine, Request
from repro.serving.frontend import Frontend

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, spans, trace  # noqa: E402

LENS = (5, 19, 12, 9)
MAX_NEW = (4, 6, 3, 5)
CHUNK = 8
SLOTS = 2


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                              vocab_size=128, n_heads=4, n_kv_heads=2,
                              head_dim=32)
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _serve(cfg, params, tmp=None, **kw):
    """Serve the scripted set through a Frontend, one tick per loop, with
    the harness's ``tick`` span around each tick. Returns the token
    streams, per-tick (launches, drains) counts, the per-tick slot work
    the harness derives, and the engine."""
    eng = Engine(cfg, params, max_slots=SLOTS, max_len=48, seed=3, **kw)
    clock = Clock()
    fe = Frontend(eng, queue_limit=8, max_retries=0, clock=clock)
    rng = np.random.default_rng(7)
    tickets = [fe.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                         max_new=m, rid=f"r{i}")
               for i, (n, m) in enumerate(zip(LENS, MAX_NEW))]
    if tmp is not None:
        jax.profiler.start_trace(str(tmp))
    counts, work = [], []
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            while fe.pending():
                pre = harness._slots(eng)
                with jax.profiler.TraceAnnotation("tick"):
                    fe.tick()
                work.append(harness.tick_work(pre, harness._slots(eng)))
                counts.append((eng.counters.launches, eng.counters.drains))
                clock.t += 0.01
    finally:
        if tmp is not None:
            jax.profiler.stop_trace()
    assert all(t.outcome == "completed" for t in tickets)
    return [t.tokens for t in tickets], counts, work, eng


def _read(tmp):
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(trace.find_xplane(str(tmp)))
    return spans.reduce(prof), trace.reduce(prof)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "percall"])
def test_every_working_tick_has_its_phases_and_rows(setup, tmp_path, fused):
    cfg, params = setup
    toks, counts, work, eng = _serve(cfg, params, tmp_path,
                                     chunk_size=CHUNK, fused_step=fused)
    sp, summary = _read(tmp_path)
    ticks = summary.spans["tick"]
    assert len(ticks) == len(work)
    top = [s for s in sp.spans if s.parent is None]
    assert [s.name for s in top] == ["frontend.admit", "engine.step",
                                     "engine.drain", "frontend.pump"] * len(
                                         ticks)
    for k, (t0, t1) in enumerate(ticks):
        inside = [i for i, s in enumerate(sp.spans) if t0 <= s.start < t1]
        step = [i for i in inside if sp.spans[i].name == "engine.step"]
        assert len(step) == 1
        kids = {sp.spans[i].name for i in inside if sp.step_of(i) == step[0]}
        assert {"engine.fill", "engine.stage"} <= kids
        launches = [sp.spans[i] for i in inside
                    if sp.spans[i].name.startswith(spans.LAUNCH)]
        assert launches and all(sp.step_of(i) == step[0] for i in inside
                                if sp.spans[i].name.startswith(spans.LAUNCH))
        names = {s.name for s in launches}
        assert names <= ({"engine.launch.step", "engine.launch.decode"}
                         if fused else {"engine.launch.prefill_chunk",
                                        "engine.launch.decode"})
        for s in launches:
            assert s.stats["rows"] == (s.stats["decode_rows"]
                                       + s.stats["prefill_rows"])
        # the launches' rows are the slot work the harness sees
        assert sum(s.stats["decode_rows"] for s in launches) == len(
            work[k].decode_lens)
        assert sum(s.stats["prefill_rows"] for s in launches) == sum(
            v for _, v, _ in work[k].chunks)
    launches = sp.launches()
    pre_pad = sum(-(-n // CHUNK) * CHUNK - n for n in LENS)
    assert sum(s.stats["prefill_rows"] for s in launches) == sum(LENS)
    assert sum(s.stats["decode_rows"] for s in launches) == sum(
        m - 1 for m in MAX_NEW)
    c = eng.counters
    assert (c.prefill_rows, c.prefill_pad_rows) == (sum(LENS), pre_pad)
    assert c.decode_rows == sum(m - 1 for m in MAX_NEW)
    assert sum(s.stats["pad_rows"] for s in launches) == (
        pre_pad + c.decode_idle_rows)
    assert c.launches == len(launches)
    assert c.drains == len(ticks)
    assert c.tokens_drained == sum(MAX_NEW)
    assert c.iterations == len(sp.working_steps()) == len(ticks)
    assert c.fused_fallbacks == 0 and eng.fused_step_error is None
    assert sp.pad_row_share() == pytest.approx(
        100 * (pre_pad + c.decode_idle_rows)
        / (pre_pad + c.decode_idle_rows + sum(LENS) + c.decode_rows))
    assert sp.sched_host_s() > 0


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "percall"])
def test_counters_agree_with_the_harness_slot_diff(setup, fused):
    cfg, params = setup
    _, _, work, eng = _serve(cfg, params, chunk_size=CHUNK,
                             fused_step=fused)
    c = eng.counters
    assert c.decode_rows == sum(len(w.decode_lens) for w in work)
    assert c.prefill_rows == sum(v for w in work for _, v, _ in w.chunks)


def test_tracing_changes_no_token_and_no_launch(setup, tmp_path):
    cfg, params = setup
    on = _serve(cfg, params, tmp_path, chunk_size=CHUNK)
    off = _serve(cfg, params, chunk_size=CHUNK)
    assert on[0] == off[0]
    assert on[1] == off[1]
    assert [t.decode_lens for t in on[2]] == [t.decode_lens for t in off[2]]


def test_whole_prompt_launches_count_bucket_padding(setup, tmp_path):
    cfg, params = setup
    _, _, _, eng = _serve(cfg, params, tmp_path, chunk_size=0)
    sp, _ = _read(tmp_path)
    pre = [s for s in sp.launches() if s.name == "engine.launch.prefill"]
    assert sorted(s.stats["prefill_rows"] for s in pre) == sorted(LENS)
    assert sorted(s.stats["pad_rows"] for s in pre) == sorted(
        max(8, 1 << (n - 1).bit_length()) - n for n in LENS)
    # a whole-prompt prefill launches from the fill, inside the step
    assert all(sp.spans[s.parent].name == "engine.fill" for s in pre)
    assert eng.counters.prefill_rows == sum(LENS)


def test_compiles_are_counted_once_per_program(setup):
    cfg, params = setup
    eng = Engine(cfg, params, max_slots=SLOTS, max_len=48, seed=3,
                 chunk_size=CHUNK)
    eng.generate([Request(prompt=np.arange(1, 12, dtype=np.int32),
                          max_new_tokens=4)])
    before = eng.counters.snapshot()
    assert before["compiles"] > 0 and before["traces"] > 0
    eng.generate([Request(prompt=np.arange(1, 12, dtype=np.int32),
                          max_new_tokens=4)])
    after = eng.counters.snapshot()
    assert after["compiles"] == before["compiles"]
    assert after["traces"] == before["traces"]
    assert after["iterations"] > before["iterations"]


def test_fused_step_fallback_is_recorded(setup):
    cfg, params = setup
    eng = Engine(cfg, params, max_slots=SLOTS, max_len=48, seed=3,
                 chunk_size=CHUNK)

    def boom(*a, **kw):
        raise RuntimeError("injected step fault")

    eng._step = boom
    out = eng.generate([Request(prompt=np.arange(1, 12, dtype=np.int32),
                                max_new_tokens=4)])
    assert len(out[0]) == 4
    assert not eng._fused_ok
    assert eng.counters.fused_fallbacks == 1
    assert eng.fused_step_error == "RuntimeError: injected step fault"
