"""Serving CLI: batched generation with CIM-sim linears.

Defaults to the fused slot-batched engine (one jitted decode step advances
all slots, DESIGN.md §10); ``--engine loop`` runs the frozen per-slot
reference engine for comparison.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 6 --new-tokens 12 [--cim sim] [--engine fused|loop] \
      [--attn-impl kernel] [--chunk-size 32]

``--chunk-size`` controls the fused engine's chunked prefill
(DESIGN.md §13): admitted prompts stream through one fixed-shape jitted
chunk program interleaved with decode steps — exactly 1 prefill trace and
no decode stall behind a long prompt. ``0`` forces the legacy whole-prompt
bucketed path; the default (auto) chunks the right-pad-safe families and
falls back to whole-prompt for ssm/hybrid/moe.

``--attn-impl kernel`` routes cached GQA attention through the
length-aware Pallas decode kernel + causal-pruned flash prefill
(DESIGN.md §11): decode cost scales with each slot's live context, not
cache capacity. The default einsum path is the bit-stable reference.

``--cim sim`` auto-deploys pre-quantized weight planes at engine
construction (core.deploy, DESIGN.md §12) — the macro's weight-stationary
contract: weights quantize once per engine, not once per token per layer.
``--deploy off`` serves the PR 3 per-call-quantization path for comparison.

``--guard`` (sim mode, fused engine) runs every CIM matmul under the ABFT
checksum guard with the degradation ladder (DESIGN.md §14) and prints the
per-layer trip/hard counters after the run. ``--fault-stuck`` /
``--fault-transient`` / ``--fault-slot`` inject a deterministic fault
scenario to watch the ladder work; ``--fail-after`` arms the request-fail
rung (failed requests print as FAILED with their structured RequestError,
the batch keeps going).

``--drift-*`` injects the temporal drift model (DESIGN.md §17) into the
fused sim-mode engine — per-column gain/offset random walks, a coherent
temperature excursion, abrupt supply steps — and ``--calibrate`` arms the
online background calibration + canary watchdog against it: probe chunks
interleave with decode (at most one launch per step), fitted trims install
atomically, and the watchdog escalates recalibrate -> boosted recalibrate
-> digital pin (via the PR 6 guard when ``--guard`` is armed). The run
prints the calibration/watchdog event log and, per request, the ABFT guard
trip/hard counts.

``--frontend`` serves through the resilient asyncio front-end
(DESIGN.md §16) instead of one batch ``generate()`` call: bounded
admission (``--queue-limit``, overflow shed with reason), per-request
deadlines (``--deadline-s``) and TTFT budgets (``--ttft-budget-s``),
retry-with-backoff on retryable failures (``--retries``), and graceful
drain on SIGINT/SIGTERM bounded by ``--drain-deadline-s``. ``--stagger-s``
spaces out arrivals to exercise admission under load. With ``--ladder``
the backlog watermarks (``--high-watermark`` / ``--low-watermark``) drive
load-adaptive CB vote degradation (``--ladder-votes``, sim mode's noise
model; mutually exclusive with --guard). The run ends with the structured
per-request records (queue wait, TTFT, tok/s, votes, retries, outcome)
and the MetricsLog summary.
"""

from __future__ import annotations

import argparse
import asyncio
import signal
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build
from repro.serving.engine import Engine, LoopEngine, Request, RequestError


def _build_argparser():
    ap = argparse.ArgumentParser(
        description="CR-CIM serving demo: fused slot-batched engine, "
                    "optionally behind the resilient async front-end")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument(
        "--replicas", type=int, default=1,
        help="data-parallel Engine replicas behind the health-aware "
             "ReplicaRouter (serving/router.py, DESIGN.md §18); each "
             "replica owns --slots slots and the same seed, so failover "
             "migration replays streams bit-for-bit in off mode")
    ap.add_argument(
        "--guard-segments", type=int, default=1,
        help="ABFT checksum segments per plane (core/guard.py): G>1 splits "
             "the checksum into G per-column-group sums, making dilute "
             "bitcell flips detectable (needs --guard)")
    ap.add_argument("--cim", default="off", choices=["off", "sim"])
    ap.add_argument("--engine", default="fused", choices=["fused", "loop"])
    ap.add_argument(
        "--deploy", default="auto", choices=["auto", "on", "off"],
        help="pre-quantize CIM-routed weights once at engine construction "
             "(sim-mode inference fast path, DESIGN.md §12); 'auto' deploys "
             "whenever --cim sim")
    ap.add_argument(
        "--chunk-size", type=int, default=-1,
        help="fused-engine prefill chunk (tokens): prompts stream through "
             "one fixed-shape jitted chunk trace interleaved with decode "
             "steps (DESIGN.md §13); 0 = legacy whole-prompt bucketed "
             "prefill, -1 = auto (chunk dense/vlm, whole-prompt for the "
             "exact-length families)")
    ap.add_argument(
        "--ttft", action="store_true",
        help="record and print per-request TTFT (fused engine only). "
             "Off by default: the per-first-token block_until_ready stalls "
             "the fused engine's async dispatch pipeline, which would skew "
             "the printed tok/s in --engine fused-vs-loop comparisons")
    ap.add_argument(
        "--attn-impl", default="config",
        choices=["config", "einsum", "kernel"],
        help="cached-GQA attention path: 'kernel' = length-aware Pallas "
             "decode kernel + causal-pruned flash prefill (O(live-context) "
             "per decode step, the production TPU path; runs in interpret "
             "mode on CPU); 'einsum' = dense masked-softmax reference; "
             "'config' defers to the arch config (default einsum)")
    ap.add_argument(
        "--guard", action="store_true",
        help="ABFT checksum guard + degradation ladder on every CIM matmul "
             "(fused engine, --cim sim only; DESIGN.md §14)")
    ap.add_argument(
        "--fault-stuck", type=float, default=0.0,
        help="stuck-at bitcell rate applied to the deployed weight planes")
    ap.add_argument(
        "--fault-transient", type=float, default=0.0,
        help="transient disturbance magnitude (units of layer output noise "
             "std) injected into the slots named by --fault-slot")
    ap.add_argument(
        "--fault-slot", type=int, action="append", default=None,
        help="slot index hit by the transient fault (repeatable)")
    ap.add_argument(
        "--fault-seed", type=int, default=0,
        help="fault scenario seed (deterministic realisations)")
    ap.add_argument(
        "--fail-after", type=int, default=0,
        help="fail a request after this many hard-tripping steps "
             "(0 = never fail; keep serving on the digital recompute)")
    # ------------------------------------------- async front-end (§16)
    ap.add_argument(
        "--frontend", action="store_true",
        help="serve through the resilient asyncio front-end: bounded "
             "admission, deadlines/TTFT budgets, deterministic retries, "
             "streaming delivery, SIGINT/SIGTERM graceful drain "
             "(DESIGN.md §16; fused engine only)")
    ap.add_argument(
        "--queue-limit", type=int, default=16,
        help="front-end admission backlog bound; overflow requests are "
             "shed synchronously with a structured reason")
    ap.add_argument(
        "--high-watermark", type=int, default=None,
        help="backlog depth at/above which the vote-degradation ladder "
             "climbs one rung per tick (default queue-limit // 2)")
    ap.add_argument(
        "--low-watermark", type=int, default=None,
        help="backlog depth below which the ladder descends back toward "
             "full votes (default high-watermark // 2)")
    ap.add_argument(
        "--ladder", action="store_true",
        help="load-adaptive CB vote degradation: admissions above the high "
             "watermark run reduced majority votes (extra output-referred "
             "comparator noise in sim mode); mutually exclusive with "
             "--guard")
    ap.add_argument(
        "--ladder-votes", default="3,1",
        help="comma-separated vote counts for ladder rungs 1.. (rung 0 is "
             "always full fidelity), strictly decreasing, e.g. '3,1'")
    ap.add_argument(
        "--deadline-s", type=float, default=None,
        help="per-request wall-clock deadline (seconds from submit); "
             "expired requests are cancelled queued, mid-prefill or "
             "mid-decode, slot recycled token-clean")
    ap.add_argument(
        "--ttft-budget-s", type=float, default=None,
        help="per-request time-to-first-token budget; requests with no "
             "token by then end deadline_expired")
    ap.add_argument(
        "--retries", type=int, default=1,
        help="max retry attempts for retryable failures; retries replay "
             "the identical token stream (rid-keyed sampling) absent "
             "faults")
    ap.add_argument(
        "--drain-deadline-s", type=float, default=10.0,
        help="graceful-drain bound after stop/SIGINT: accepted work gets "
             "this long to finish before being cancelled")
    ap.add_argument(
        "--stagger-s", type=float, default=0.0,
        help="spacing between request arrivals in --frontend mode "
             "(0 = all at once, the overload case)")
    ap.add_argument(
        "--temperature", type=float, default=0.0,
        help="sampling temperature (0 = greedy)")
    # -------------------------------- temporal drift + calibration (§17)
    ap.add_argument(
        "--drift-walk", type=float, default=0.0,
        help="temporal drift: per-column gain random-walk std at the KL "
             "horizon (fused engine, --cim sim only; DESIGN.md §17)")
    ap.add_argument(
        "--drift-walk-offset", type=float, default=0.0,
        help="per-column offset random-walk std, in z-units of the macro's "
             "readout sigma")
    ap.add_argument(
        "--drift-temp", type=float, default=0.0,
        help="temperature-excursion gain amplitude (global sinusoid x "
             "per-column sensitivity)")
    ap.add_argument(
        "--drift-supply", type=float, default=0.0,
        help="abrupt supply-step offset magnitude (z-units); pairs with "
             "--drift-supply-every")
    ap.add_argument(
        "--drift-supply-every", type=int, default=0,
        help="steps between supply-step events (0 = none)")
    ap.add_argument(
        "--drift-seed", type=int, default=0,
        help="drift trajectory seed (deterministic, replayable)")
    ap.add_argument(
        "--calibrate", action="store_true",
        help="online background calibration + canary watchdog against the "
             "injected drift: probe chunks interleave with decode (at most "
             "one launch per step), fitted trims install atomically, the "
             "canary escalates through recalibrate -> digital pin "
             "(DESIGN.md §17; needs --drift-* and deployed sim mode)")
    ap.add_argument(
        "--calib-every", type=int, default=256,
        help="full-calibration cadence in engine steps")
    ap.add_argument(
        "--canary-every", type=int, default=8,
        help="canary watchdog cadence in engine steps (0 disables)")
    return ap


def _drift_from_args(args):
    if not (args.drift_walk or args.drift_walk_offset or args.drift_temp
            or (args.drift_supply and args.drift_supply_every)):
        return None
    from repro.core.drift import DriftSpec
    return DriftSpec(seed=args.drift_seed,
                     walk_gain_std=args.drift_walk,
                     walk_offset_std=args.drift_walk_offset,
                     temp_gain_amp=args.drift_temp,
                     supply_offset_mag=args.drift_supply,
                     supply_every=args.drift_supply_every)


def _build_engine(args, cfg, params):
    engine_cls = Engine if args.engine == "fused" else LoopEngine
    engine_kw = dict(cim_mode=args.cim,
                     attn_impl=(None if args.attn_impl == "config"
                                else args.attn_impl),
                     deploy={"auto": None, "on": True,
                             "off": False}[args.deploy])
    if engine_cls is Engine:
        # only -1 means auto; other negatives pass through so the engine's
        # own chunk_size validation rejects them loudly
        engine_kw["chunk_size"] = (None if args.chunk_size == -1
                                   else args.chunk_size)
        engine_kw["record_ttft"] = args.ttft
        if args.guard:
            from repro.serving.engine import DegradePolicy
            if args.guard_segments > 1:
                from repro.core.guard import GuardSpec
                engine_kw["guard"] = GuardSpec(segments=args.guard_segments)
            else:
                engine_kw["guard"] = True
            if args.fail_after > 0:
                engine_kw["degrade"] = DegradePolicy(
                    pin_after=1, fail_after=args.fail_after)
        if args.ladder:
            from repro.core.sac import DegradeLadder
            votes = tuple(int(v) for v in args.ladder_votes.split(",") if v)
            engine_kw["ladder"] = DegradeLadder(votes=(None,) + votes)
        if args.fault_stuck > 0.0 or args.fault_transient > 0.0:
            from repro.core.faults import FaultSpec
            engine_kw["fault"] = FaultSpec(
                seed=args.fault_seed, stuck_rate=args.fault_stuck,
                transient_mag=args.fault_transient)
            engine_kw["fault_slots"] = args.fault_slot or ()
        drift = _drift_from_args(args)
        if drift is not None:
            engine_kw["drift"] = drift
        if args.calibrate:
            if drift is None:
                raise SystemExit("--calibrate needs a drift model "
                                 "(--drift-walk/--drift-temp/--drift-supply)")
            from repro.core.calibrate import CalibPolicy
            engine_kw["calib"] = CalibPolicy(
                every_steps=args.calib_every,
                canary_every=args.canary_every)
    elif args.guard or args.ladder or args.fault_stuck or args.fault_transient:
        raise SystemExit("--guard/--ladder/--fault-* need the fused engine "
                         "(--engine fused): the loop reference engine has "
                         "no guard or ladder path")
    elif _drift_from_args(args) is not None or args.calibrate:
        raise SystemExit("--drift-*/--calibrate need the fused engine "
                         "(--engine fused): the loop reference engine has "
                         "no drift or calibration path (DESIGN.md §17)")
    max_len = args.prompt_len + args.new_tokens + 8
    if args.replicas > 1:
        if engine_cls is not Engine:
            raise SystemExit("--replicas needs the fused engine "
                             "(--engine fused): the router speaks the "
                             "incremental session API")
        from repro.serving.router import ReplicaRouter, build_pool
        engines = build_pool(cfg, params, args.replicas,
                             max_slots=args.slots, max_len=max_len,
                             **engine_kw)
        return ReplicaRouter(engines)
    return engine_cls(cfg, params, max_slots=args.slots,
                      max_len=max_len, **engine_kw)


def _run_batch(args, engine, cfg):
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature)
            for _ in range(args.requests)]
    t0 = time.time()
    outs = engine.generate(reqs)
    dt = time.time() - t0
    failed = [isinstance(o, RequestError) for o in outs]
    total_tokens = sum(len(o) for o, f in zip(outs, failed) if not f)
    print(f"[{args.engine}] served {len(reqs)} requests "
          f"({sum(failed)} failed), {total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens / dt:.1f} tok/s)")
    if getattr(engine, "guard", None) is not None:
        trips = engine.guard_trip_counts
        hard = engine.guard_hard_counts
        print(f"  guard: per-layer trips {trips.tolist()} / "
              f"hard {hard.tolist()} "
              f"(total {int(trips.sum())}/{int(hard.sum())})")
        for i, r in enumerate(reqs):
            rep = engine.guard_report_of(r)
            if rep is not None and (rep["trips"] or rep["hard"]):
                print(f"  req{i}: guard trips={rep['trips']} "
                      f"hard={rep['hard']} layers={rep['hard_layers']}")
    if getattr(engine, "drift", None) is not None:
        evs = engine.take_drift_events()
        cals = [e for e in evs if e["kind"] == "calibrate"]
        trips_w = [e for e in evs if e["kind"] == "watchdog_trip"]
        print(f"  drift: {engine.drift_step} steps, "
              f"{len(cals)} calibrations, {len(trips_w)} watchdog trips"
              + (", ESCALATED to digital" if engine.drift_degraded
                 or getattr(engine, "_drift_pin_all", False) else ""))
        for e in evs[:8]:
            q = e.get("quality")
            print(f"    step {e['step']}: {e['kind']}"
                  + (f" quality={q:.2f}" if q is not None else "")
                  + (f" [{e['action']}]" if "action" in e else ""))
    for i, err in enumerate(getattr(engine, "request_errors", [])):
        if err is not None:
            print(f"  req{i}: FAILED — {err}")
    ttfts = [t for t in getattr(engine, "ttft_s", []) if t is not None]
    if ttfts:
        print(f"  TTFT mean {np.mean(ttfts) * 1e3:.0f} ms / "
              f"max {np.max(ttfts) * 1e3:.0f} ms "
              f"({engine.prefill_traces} prefill traces, "
              f"chunk={engine.chunk_size})")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: " + (f"FAILED ({o})" if isinstance(o, RequestError)
                               else f"{o[:10]}..."))


async def _run_frontend(args, engine, cfg):
    from repro.serving.frontend import Frontend
    fe = Frontend(engine, queue_limit=args.queue_limit,
                  high_watermark=args.high_watermark,
                  low_watermark=args.low_watermark,
                  default_ttft_budget_s=args.ttft_budget_s,
                  max_retries=args.retries,
                  drain_deadline_s=args.drain_deadline_s)
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, fe.stop)
        except (NotImplementedError, RuntimeError):
            pass  # non-unix event loop: ctrl-C falls back to KeyboardInterrupt
    runner = asyncio.create_task(fe.run())
    rng = np.random.default_rng(0)
    tickets = []
    t0 = time.time()
    for i in range(args.requests):
        t = fe.submit(list(rng.integers(0, cfg.vocab_size, args.prompt_len)),
                      args.new_tokens, temperature=args.temperature,
                      rid=f"req-{i}", timeout_s=args.deadline_s)
        tickets.append(t)
        if args.stagger_s > 0:
            await asyncio.sleep(args.stagger_s)
    await asyncio.gather(*(t.wait() for t in tickets))
    fe.stop()
    await runner
    dt = time.time() - t0
    total = sum(len(t.tokens) for t in tickets)
    print(f"[frontend] {len(tickets)} requests, {total} tokens in {dt:.1f}s "
          f"({total / dt:.1f} tok/s)")
    for t in tickets:
        r = t.record
        print(f"  {t.rid}: {r.outcome:<16} wait={r.queue_wait_s or 0:.3f}s "
              f"ttft={'-' if r.ttft_s is None else f'{r.ttft_s:.3f}s'} "
              f"toks={r.tokens_out} votes={r.votes_used} "
              f"retries={r.retries}"
              + (f" rep={r.replica}" if r.replica is not None else "")
              + (f" migrations={r.migrations}" if r.migrations else "")
              + (f" guard={r.guard_trips}/{r.guard_hard}"
                 if r.guard_trips is not None else "")
              + (f"  [{r.reason}]" if r.reason else ""))
    s = fe.metrics.summary()
    print(f"  summary: outcomes={s['outcomes']} "
          f"queue_wait_p99={s['queue_wait_p99_s']} "
          f"ttft_p99={s['ttft_p99_s']} "
          f"degraded={s['degraded_admissions']} "
          f"transitions={s['ladder_transitions']}")
    if getattr(engine, "drift", None) is not None:
        print(f"  drift: {engine.drift_step} steps, "
              f"calibrations={s['calibrations']} "
              f"watchdog_trips={s['watchdog_trips']} "
              f"escalations={s['drift_escalations']}")
        for c in fe.metrics.calibrations[:8]:
            q = c.quality
            print(f"    step {c.step}: {c.kind}"
                  + (f" quality={q:.2f}" if q is not None else ""))


def main():
    args = _build_argparser().parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    if args.frontend and args.engine != "fused":
        raise SystemExit("--frontend needs the fused engine "
                         "(--engine fused): the front-end drives the "
                         "incremental session API")
    engine = _build_engine(args, cfg, params)
    if engine.deployed:
        from repro.core.deploy import plane_summary
        ps = plane_summary(engine.params)
        print(f"deployed {ps['planes']} pre-quantized weight planes "
              f"({ps['int8_bytes'] / 2**20:.1f} MiB int8 vs "
              f"{ps['f32_bytes'] / 2**20:.1f} MiB f32 streamed per call)")
    if args.frontend:
        asyncio.run(_run_frontend(args, engine, cfg))
    else:
        _run_batch(args, engine, cfg)


if __name__ == "__main__":
    main()
