"""A whole run at a tiny size on the CPU: the harness past its look for a
chip, with the served path sound and then broken underneath, and the
control put in its place.

The tiny model keeps the cell's configuration (digital bf16 linears,
greedy decoding) at small widths, and the cell's traffic at short
lengths. What is under test is the harness's window, bookkeeping and
comparison: a sound stream reads gaps of rounding's width, the float8
control and a stream whose tokens are altered where the engine produces
them read gaps above the limit.
"""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness  # noqa: E402

LIMITS = {"gap_max": 0.02}


def _cell(loop="open"):
    config = json.loads(
        (ROOT / "bench/configs/qwen2-0.5b-off.json").read_text())
    config.update(num_hidden_layers=2, hidden_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                  intermediate_size=256, vocab_size=512)
    config["serving"]["attn_impl"] = "einsum"
    traffic = json.loads((ROOT / "bench/traffic/conv-open.json").read_text())
    traffic.update(
        prompt={"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 4,
                "max": 40},
        output={"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 3,
                "max": 16},
        pool=512, prime_requests=2, warm_completions=4)
    if loop == "closed":
        traffic.update(loop="closed", clients_per_slot=2, think_s=0.0,
                       warm_completions_per_client=0.5)
    shape = {"max_slots": 4, "max_len": 64, "chunk_size": 16,
             "rate_rps": 200.0, "trace_seconds": 1, "check_tokens": 64,
             "check_requests": 6, "limits": dict(LIMITS)}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if m["name"] in ("out_tok_s", "itl_p95_ms", "setup_s")]
    return harness.Cell("tiny", {"chips": 1}, config, traffic, shape, e2e,
                        [])


def _run(seed, loop="open", **kw):
    return harness.run(_cell(loop), seed, 1.0, None, time.perf_counter(),
                       **kw)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct_and_reports_its_metrics(loop):
    r = _run(2**31 + 17, loop, readings=True)
    assert r.correct, r.checks
    assert r.attempted > 0 and r.failed == 0
    assert set(r.metrics) == {"out_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r.metrics.values())
    assert set(r.checks) == set(LIMITS)
    assert r.notes["sampled"] >= 6 and r.notes["sampled_tokens"] >= 64
    assert r.path["fused_step"] in (True, False)
    # what the limit is set from: a corrupted stream reads far higher
    assert r.notes["altered_gap_max"] > 3 * LIMITS["gap_max"]


def test_control_in_the_programs_place_is_not_correct():
    """The reference at float8, at each position the token it puts first,
    reads a gap above the limit."""
    r = _run(2**33 + 3, readings=True)
    assert r.correct, r.checks
    assert r.notes["control_gap_max"] > LIMITS["gap_max"]


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serving import engine as eng

    drain = eng.Engine.drain_pending

    def altered(self):
        before = {id(r): len(r.out_tokens) for r in self._reqs}
        drain(self)
        for r in self._reqs:
            n = before[id(r)]
            r.out_tokens[n:] = [(t + 1) % self.cfg.vocab_size
                                for t in r.out_tokens[n:]]

    monkeypatch.setattr(eng.Engine, "drain_pending", altered)
    r = _run(5)
    assert not r.correct
    assert r.checks["gap_max"]["value"] > LIMITS["gap_max"]


def test_no_served_stream_is_not_correct():
    checks, correct, _ = check.run(_cell(), 6, [])
    assert not correct
    assert all(c["value"] == float("inf") for c in checks.values())


def test_tick_work_reads_decode_rows_and_chunks_from_slot_state():
    pre = [(1, 100, True, 5, 100), (2, 0, False, 0, 600),
           (3, 512, False, 0, 600), (4, 50, True, 63, 50), None]
    post = [(1, 100, True, 6, 100), (2, 256, False, 0, 600),
            (3, 600, True, 2, 600), (5, 0, False, 0, 30),
            (6, 30, True, 2, 30)]
    t = harness.tick_work(pre, post)
    # slot 0 decoded over 105 keys; slot 2 joined the decode after its
    # last chunk; slot 3's request decoded its last token and left; slot 4
    # took a new request whose only chunk finished its prompt
    assert sorted(t.decode_lens) == [31, 105, 113, 601]
    assert t.chunks == [(0, 256, False), (512, 88, True), (0, 30, True)]
