"""Structured per-request serving records + tail-latency summaries.

Every request that touches the front-end ends with exactly ONE
``RequestRecord`` whose ``outcome`` is one of the engine's terminal
vocabulary ({completed, failed, cancelled, deadline_expired, shed}) — the
zero-lost-requests invariant the overload soak gates on is literally
"len(records) == len(submissions) and every outcome is terminal".

Records carry the co-design dimensions next to the latency ones: the ladder
level / vote count a request was admitted at (the paper's accuracy/energy
knob, DESIGN.md §16) sits beside its queue wait and TTFT, so a bench run
can show what the degraded admissions bought. Ladder transitions are logged
separately (``MetricsLog.transitions``) with the queue depth that triggered
them. ``ServingCounters`` holds an engine's cumulative work counts
(iterations, launches, rows computed and padded, drains, fallbacks) and
the process's JAX trace and compile counts.

Kept dependency-free (stdlib only): the front-end imports it under asyncio,
the benches import it for BENCH_*.json summaries.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


def percentile(xs: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None on empty input.

    Nearest-rank (not interpolated) so a p99 over a handful of samples is
    an actual observed latency, never an extrapolation past the max.
    """
    if not xs:
        return None
    s = sorted(xs)
    if len(s) == 1:
        return s[0]
    rank = max(0, min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1)))))
    return s[rank]


@dataclasses.dataclass
class RequestRecord:
    """One request's lifecycle, closed exactly once."""

    rid: str
    outcome: str = "pending"          # terminal: engine.OUTCOMES
    reason: Optional[str] = None      # shed/cancel/failure detail
    submitted_s: float = 0.0          # clock at front-end submit
    admitted_s: Optional[float] = None   # clock at slot admission
    finished_s: Optional[float] = None   # clock at terminal outcome
    queue_wait_s: Optional[float] = None
    ttft_s: Optional[float] = None    # submit -> first streamed token
    tps: Optional[float] = None       # decode tokens/s (admit -> finish)
    tokens_out: int = 0
    degrade_level: int = 0            # ladder level at admission
    votes_used: Optional[int] = None  # majority-vote count at that level
    retries: int = 0                  # failure-retry attempts consumed
    guard_trips: Optional[int] = None  # ABFT per-request (L,) trip total
    guard_hard: Optional[int] = None   # ... hard-fault (digital-rung) total
    replica: Optional[str] = None      # replica that finished the request
    migrations: int = 0                # health-failover re-dispatches (router)

    def close(self, outcome: str, now: float,
              reason: Optional[str] = None) -> "RequestRecord":
        self.outcome = outcome
        self.finished_s = now
        if reason is not None:
            self.reason = reason
        if self.admitted_s is not None and self.tokens_out > 1:
            dt = now - self.admitted_s
            if dt > 0:
                self.tps = (self.tokens_out - 1) / dt
        return self


# process-wide tally of JAX's compile events (``note_compile_event`` is
# registered once per process as a ``jax.monitoring`` duration listener by
# ``serving.engine``). On a persistent-cache hit both events still fire: the
# jaxpr is traced again and the backend-compile event times the retrieval.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_TALLY = {"traces": 0, "compiles": 0}


def note_compile_event(event: str, duration_secs: float, **kwargs) -> None:
    if event == TRACE_EVENT:
        _COMPILE_TALLY["traces"] += 1
    elif event == COMPILE_EVENT:
        _COMPILE_TALLY["compiles"] += 1


@dataclasses.dataclass
class ServingCounters:
    """Cumulative counts of one engine's work over its lifetime.

    Rows are counted where a program is launched, from the host arrays
    that staged it: ``decode_rows`` are slots decoded, ``decode_idle_rows``
    the batch rows of a decode launch whose slot was not decoding,
    ``prefill_rows`` prompt tokens a chunk (or bucket) carried and
    ``prefill_pad_rows`` its padding. ``traces`` and ``compiles`` are
    process-wide: every engine of the process reads the same counts.
    """

    iterations: int = 0        # scheduler iterations that had work
    launches: int = 0          # jitted program launches
    decode_rows: int = 0
    decode_idle_rows: int = 0
    prefill_rows: int = 0
    prefill_pad_rows: int = 0
    drains: int = 0            # device->host token drains
    tokens_drained: int = 0
    fused_fallbacks: int = 0   # fused iterations that raised
    # grouped-matmul expert layers (models.moe.held_experts), summed over
    # layers and counted at the drain: rows routed to the held experts (in
    # all, and to each: ``expert_rows``), the rows the matmul computed
    # beyond them (tile padding), and the (layer, held expert) pairs that
    # had rows (whose weights the matmul read)
    expert_routed_rows: int = 0
    expert_pad_rows: int = 0
    expert_groups: int = 0
    expert_rows: List[int] = dataclasses.field(default_factory=list)
    # launches whose program addressed every attention cache leaf in place
    # by (layer, row), and launches whose program sliced a layer or a slot
    # of the stacked cache out and wrote it back (recurrent and hybrid
    # caches, the f32 dense megakernel)
    cache_inplace_launches: int = 0
    cache_slice_launches: int = 0

    @property
    def traces(self) -> int:
        return _COMPILE_TALLY["traces"]

    @property
    def compiles(self) -> int:
        return _COMPILE_TALLY["compiles"]

    def snapshot(self) -> Dict[str, int]:
        """Every scalar count, process-wide ones included, as a plain dict
        (``expert_rows``, one count per held expert, is read as is)."""
        out = dataclasses.asdict(self)
        del out["expert_rows"]
        out.update(_COMPILE_TALLY)
        return out


@dataclasses.dataclass
class LadderTransition:
    t_s: float
    level_from: int
    level_to: int
    queue_depth: int


@dataclasses.dataclass
class CalibrationEvent:
    """One background-calibration or watchdog event (DESIGN.md §17)."""

    t_s: float
    step: int                         # engine drift_step at the event
    kind: str                         # calibrate | watchdog | escalate
    quality: Optional[float] = None   # residual_var/sigma^2 (calibrate)
    detail: Optional[Dict[str, object]] = None


class MetricsLog:
    """Append-only request records + ladder transitions + summary()."""

    def __init__(self) -> None:
        self.records: List[RequestRecord] = []
        self.transitions: List[LadderTransition] = []
        self.calibrations: List[CalibrationEvent] = []

    def open(self, rid: str, now: float) -> RequestRecord:
        rec = RequestRecord(rid=rid, submitted_s=now)
        self.records.append(rec)
        return rec

    def note_transition(self, now: float, frm: int, to: int,
                        depth: int) -> None:
        self.transitions.append(LadderTransition(now, frm, to, depth))

    def note_calibration(self, now: float, event: Dict[str, object]) -> None:
        """Fold one engine drift event (``Engine.take_drift_events``) in."""
        detail = {k: v for k, v in event.items()
                  if k not in ("kind", "step", "quality")}
        self.calibrations.append(CalibrationEvent(
            t_s=now, step=int(event.get("step", -1)),
            kind=str(event.get("kind", "?")),
            quality=event.get("quality"),
            detail=detail or None))

    def summary(self) -> Dict[str, object]:
        recs = self.records
        by_outcome: Dict[str, int] = {}
        for r in recs:
            by_outcome[r.outcome] = by_outcome.get(r.outcome, 0) + 1
        waits = [r.queue_wait_s for r in recs if r.queue_wait_s is not None]
        ttfts = [r.ttft_s for r in recs if r.ttft_s is not None]
        tpss = [r.tps for r in recs if r.tps is not None]
        return {
            "n_requests": len(recs),
            "outcomes": by_outcome,
            "open_requests": sum(r.outcome == "pending" for r in recs),
            "queue_wait_p50_s": percentile(waits, 50),
            "queue_wait_p99_s": percentile(waits, 99),
            "ttft_p50_s": percentile(ttfts, 50),
            "ttft_p99_s": percentile(ttfts, 99),
            "tps_mean": (sum(tpss) / len(tpss)) if tpss else None,
            "degraded_admissions": sum(r.degrade_level > 0 for r in recs
                                       if r.admitted_s is not None),
            "retries_total": sum(r.retries for r in recs),
            "ladder_transitions": len(self.transitions),
            "shed_fraction": (by_outcome.get("shed", 0) / len(recs)
                              if recs else 0.0),
            "calibrations": sum(c.kind == "calibrate"
                                for c in self.calibrations),
            "watchdog_trips": sum(c.kind == "watchdog_trip"
                                  for c in self.calibrations),
            "drift_escalations": sum(c.kind == "escalate"
                                     for c in self.calibrations),
            "guard_trips_total": sum(r.guard_trips or 0 for r in recs),
            "guard_hard_total": sum(r.guard_hard or 0 for r in recs),
        }
