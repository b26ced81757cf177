"""Serving benchmark: steady-state decode throughput, fused vs per-slot loop.

The fused ``Engine`` advances all ``max_slots`` slots with ONE jitted
batch-axis decode program per token step and samples on device; the frozen
seed ``LoopEngine`` dispatches one batch-1 program per slot per step and
syncs every sampled token to the host. Both are measured at max_slots=4 on
a shrunk qwen2 config, in ``off`` and ``sim`` CIM modes.

Steady-state decode time is isolated by differencing two generates that
share prompts (and therefore prefill work) but differ in new-token count:

  decode_tok_s = slots * (long - short) / (t_long - t_short)

Since PR 4 the sim rows also record the deploy fast path (DESIGN.md §12):
``fused_decode_tok_s_sim`` is the engine default (pre-quantized weight
planes, deployed at construction), ``fused_nodeploy_decode_tok_s_sim``
re-runs the PR 3 per-call-quantization path on the same machine, and
``deploy_speedup_sim`` is their machine-independent ratio (the CI
acceptance floor) — since PR 5 measured as the median of interleaved
*paired* reps on two persistent engines (``_deploy_ratio_samples``; the
unpaired ratio drifted 0.73-1.62x across identical runs on the 2-core
container, which is noise, not a 1.8x effect). ``sim_vs_pr3_x`` compares
against the last PR 3 run recorded on the reference container
(meaningful there, trend-only in CI).

Since PR 7 the bench also records the *dispatch-count witness* for the
single-launch scheduler step (DESIGN.md §15): ``launches_per_iter_fused``
vs ``launches_per_iter_percall`` count jitted program launches per
scheduler iteration on a mixed chunked-prefill + decode workload, and
``launch_drop_x`` is their ratio — the CI acceptance gates on the launch
count, not wall-clock, because on the 2-core interpret-mode container the
dispatch-tail win is structural (fewer launches) while wall-clock is
dominated by emulation noise.

Since PR 8 the run also records the async front-end's scheduling tails
(DESIGN.md §16): ``frontend_queue_wait_p50/p99_s`` and
``frontend_ttft_p50/p99_s`` over a 12-request burst into the bounded
admission queue, from the structured per-request MetricsLog records
(compile excluded via a warm-up request).

Results append to BENCH_serving.json at the repo root (PR-over-PR record):

  PYTHONPATH=src python -m benchmarks.serving_bench
"""

from __future__ import annotations

import os
import time

import numpy as np

_BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_serving.json")

SLOTS = 4
PROMPT_LEN = 16
SHORT, LONG = 4, 68

# last sim-mode fused run recorded before the PR 4 deploy fast path landed
# (BENCH_serving.json, 2026-08-01T14:44 on the 2-core reference container);
# the PR 4 acceptance is >= 2x this on the same container.
PR3_SIM_BASELINE_TOK_S = 474.5


def _setup():
    from benchmarks.common import tiny_serving_setup

    return tiny_serving_setup()


def _requests(cfg, new_tokens: int):
    from repro.serving.engine import Request

    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, PROMPT_LEN,
                                        dtype=np.int32),
                    max_new_tokens=new_tokens)
            for _ in range(SLOTS)]


def _timed_generate(engine, cfg, new_tokens: int) -> float:
    t0 = time.perf_counter()
    outs = engine.generate(_requests(cfg, new_tokens))
    dt = time.perf_counter() - t0
    assert all(len(o) == new_tokens for o in outs)
    return dt


def _decode_tok_s(engine_cls, cfg, params, mode: str, **engine_kw) -> float:
    engine = engine_cls(cfg, params, max_slots=SLOTS,
                        max_len=PROMPT_LEN + LONG + 8, cim_mode=mode,
                        **engine_kw)
    _timed_generate(engine, cfg, SHORT)          # compile prefill + decode
    # min-of-3: the differenced ratio is sensitive to a single slow sample
    # on the 2-core container (a min-of-2 run once recorded the deployed
    # engine at 0.87x its own baseline; the CI floor gates this number)
    t_short = min(_timed_generate(engine, cfg, SHORT) for _ in range(3))
    t_long = min(_timed_generate(engine, cfg, LONG) for _ in range(3))
    return SLOTS * (LONG - SHORT) / max(t_long - t_short, 1e-9)


def _deploy_ratio_samples(cfg, params, reps: int = 5):
    """Paired deployed-vs-nodeploy decode ratios for the CI floor.

    The unpaired version (measure one engine fully, then the other)
    recorded ratios from 0.73 to 1.62 across identical runs on the 2-core
    container — machine drift between the two measurements dominates the
    ~1.8x effect being gated. Pairing interleaves the two engines inside
    each rep (same machine state), reuses both compiled engines across
    reps, and the gate takes the median rep.
    """
    from repro.serving.engine import Engine

    kw = dict(max_slots=SLOTS, max_len=PROMPT_LEN + LONG + 8,
              cim_mode="sim")
    dep = Engine(cfg, params, **kw)
    nod = Engine(cfg, params, deploy=False, **kw)
    for e in (dep, nod):
        _timed_generate(e, cfg, SHORT)           # compile prefill + decode
        _timed_generate(e, cfg, LONG)
    ratios, nod_tok_s = [], 0.0
    for _ in range(reps):
        ds = min(_timed_generate(dep, cfg, SHORT) for _ in range(2))
        dl = min(_timed_generate(dep, cfg, LONG) for _ in range(2))
        ns = min(_timed_generate(nod, cfg, SHORT) for _ in range(2))
        nl = min(_timed_generate(nod, cfg, LONG) for _ in range(2))
        ratios.append(max(nl - ns, 1e-9) / max(dl - ds, 1e-9))
        nod_tok_s = SLOTS * (LONG - SHORT) / max(nl - ns, 1e-9)
    return ratios, nod_tok_s


def _launch_witness(cfg, params) -> dict:
    """Jitted launches per scheduler iteration, fused step vs per-call.

    Prefill-heavy ragged prompts (2-5 chunks each at chunk_size=16) with a
    standing admission queue (2x more requests than slots) and short
    generations keep several slots mid-prefill for most iterations — the
    workload where the per-call path pays (#prefilling slots + 1) launches
    per iteration and the fused ``_step`` pays exactly one. A
    decode-dominated workload would flatter neither side: per-call already
    launches ~1 program per pure-decode iteration. Token streams are
    asserted equal first: the witness must never trade correctness for the
    launch count.
    """
    from repro.serving.engine import Engine, Request

    lens = [64, 48, 80, 32, 56, 40, 72, 24]

    def reqs():
        rng = np.random.default_rng(1)
        return [Request(prompt=rng.integers(0, cfg.vocab_size, L,
                                            dtype=np.int32),
                        max_new_tokens=4)
                for L in lens]

    kw = dict(max_slots=SLOTS, max_len=128, chunk_size=16)
    fused = Engine(cfg, params, **kw)
    percall = Engine(cfg, params, fused_step=False, **kw)
    a = fused.generate(reqs())
    b = percall.generate(reqs())
    assert a == b, "fused-step scheduler diverged from the per-call path"
    assert fused._fused_ok, "fused engine silently fell back to per-call"

    def per_iter(eng):
        return eng.counters.launches / max(eng.counters.iterations, 1)

    return {
        "launches_per_iter_fused": per_iter(fused),
        "launches_per_iter_percall": per_iter(percall),
        "launch_drop_x": per_iter(percall) / per_iter(fused),
    }


def _frontend_latency(cfg, params) -> dict:
    """Queue-wait and TTFT tails through the async front-end (§16).

    12 requests burst into a 4-slot engine behind the bounded-admission
    front-end; per-request queue wait and TTFT come from the structured
    MetricsLog records. Percentiles are computed over the measured burst
    only — a separate warm-up request eats the prefill/decode compile so
    the tails reflect scheduling, not XLA."""
    from repro.serving.engine import Engine
    from repro.serving.frontend import Frontend
    from repro.serving.metrics import percentile

    eng = Engine(cfg, params, max_slots=SLOTS,
                 max_len=PROMPT_LEN + SHORT + 8, cim_mode="off")
    fe = Frontend(eng, queue_limit=12, high_watermark=8, low_watermark=4,
                  clock=time.perf_counter)
    rng = np.random.default_rng(2)

    def _one(rid):
        return fe.submit(list(rng.integers(0, cfg.vocab_size, PROMPT_LEN)),
                         SHORT, rid=rid)

    warm = _one("warm")
    while fe.pending():
        fe.tick()
    assert warm.outcome == "completed", warm.outcome
    burst = [_one(f"lat-{i}") for i in range(12)]
    while fe.pending():
        fe.tick()
    assert all(t.outcome == "completed" for t in burst), \
        [t.outcome for t in burst]
    waits = [t.record.queue_wait_s for t in burst]
    ttfts = [t.record.ttft_s for t in burst]
    return {
        "frontend_queue_wait_p50_s": percentile(waits, 50),
        "frontend_queue_wait_p99_s": percentile(waits, 99),
        "frontend_ttft_p50_s": percentile(ttfts, 50),
        "frontend_ttft_p99_s": percentile(ttfts, 99),
    }


def run() -> dict:
    from repro.serving.engine import Engine, LoopEngine

    cfg, params = _setup()
    out: dict = {"slots": SLOTS, "prompt_len": PROMPT_LEN,
                 "decode_tokens": LONG - SHORT}
    out.update(_launch_witness(cfg, params))
    out.update(_frontend_latency(cfg, params))
    for mode in ("off", "sim"):
        fused = _decode_tok_s(Engine, cfg, params, mode)
        loop = _decode_tok_s(LoopEngine, cfg, params, mode)
        out[f"fused_decode_tok_s_{mode}"] = fused
        out[f"loop_decode_tok_s_{mode}"] = loop
        out[f"speedup_{mode}"] = fused / loop
    # before/after for the PR 4 deploy fast path: same machine, same shapes,
    # deploy=False is exactly the PR 3 per-call-quantization engine.
    # Interleaved paired sampling + median (see _deploy_ratio_samples) —
    # the unpaired ratio was too drift-sensitive for the 1.2x CI floor.
    ratios, nodeploy = _deploy_ratio_samples(cfg, params)
    out["fused_nodeploy_decode_tok_s_sim"] = nodeploy
    out["deploy_speedup_sim_samples"] = sorted(round(r, 3) for r in ratios)
    out["deploy_speedup_sim"] = float(np.median(ratios))
    out["sim_vs_pr3_x"] = out["fused_decode_tok_s_sim"] / PR3_SIM_BASELINE_TOK_S
    from benchmarks.common import append_run
    append_run(_BENCH_JSON, out)
    return out


if __name__ == "__main__":
    for k, v in run().items():
        print(f"{k}: {v}")
