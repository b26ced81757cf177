"""Slot-batched continuous-batching serving engine (DESIGN.md §10).

Two engines share the ``Request`` API:

* ``Engine`` — the fused production engine. One stacked KV/state cache
  pytree of batch = ``max_slots`` is allocated once; a single jitted decode
  program advances *every* active slot per step against per-sequence cache
  lengths, samples the next token on device (temperature or argmax per row)
  and never round-trips a token through the host — emitted tokens are
  drained device→host in periodic batches. Prefill is *chunked*
  (DESIGN.md §13): admitted prompts stream through ONE fixed-shape jitted
  chunk program in ``chunk_size`` slices, interleaved with the decode
  steps of the other slots — exactly 1 prefill trace, bounded per-step
  latency, no decode stall behind a long prompt. The scheduler tracks each
  slot's prefill progress host-side. ``chunk_size=0`` (and the
  exact-length families: ssm/hybrid recurrent state would absorb chunk
  padding, moe routing capacity scales with per-forward token count) falls
  back to the whole-prompt power-of-two-bucket path — O(log2 max_len)
  traces, every decode slot stalled for the full prompt on admit.

* ``LoopEngine`` — the frozen seed reference ("vLLM-lite"): one batch-1
  cache per slot and one jitted decode dispatch per slot per token, with a
  host sync in ``_sample``. Kept verbatim for the fused-vs-loop equality
  test and as the baseline of ``benchmarks/serving_bench.py`` (per-request
  failure isolation was retrofitted — the RequestError contract below is
  shared by both engines — but the token math is untouched).

The scheduler is an *incremental session* (DESIGN.md §16): ``begin()`` /
``submit()`` / ``cancel()`` / ``step()`` / ``has_work()`` expose one
scheduler iteration at a time so the asyncio front-end
(``serving/frontend.py``) can admit, stream, expire and cancel requests
between steps; ``generate()`` is exactly ``begin`` + submit-all + step-loop
and therefore bit-identical to the pre-session batch API. Per-request
sampling keys derive from a stable request id (``Request.rid``) and the
token index — never from the engine's per-step key chain — so a re-submitted
request replays its sampled token stream bit-for-bit in off mode (per-row
decode logits are batch-invariant there; sim-mode readout noise is
batch-global by design and is reproduced only under the same batch
schedule). The per-step chain still feeds the CIM noise context, unchanged.

Robustness (DESIGN.md §14/§16): the fused ``Engine`` optionally runs every
CIM-routed matmul under the ABFT checksum guard (``guard=``, requires
sim-mode deployed planes) and escalates per (slot, layer) on guard trips
via ``DegradePolicy``; independently, a ``sac.DegradeLadder`` lets the
front-end admit requests at reduced majority-vote counts under load
(``Request.degrade_level`` → per-row extra readout noise in sim mode,
``models.layers._degrade_noise``). Failed requests — per-slot exception
during prefill, per-slot exception during *decode* (isolated by re-probing
each active slot solo against the same compiled program), or guard
hard-fail — yield a structured ``RequestError`` (reason, phase, slot,
retryable) at their position in the results list (never an exception), the
slot is recycled token-clean, and the rest of the batch is unaffected.

Observability: each phase of a scheduler iteration runs inside a
``jax.profiler.TraceAnnotation`` span — ``engine.step`` around the
iteration, holding ``engine.fill`` (slot admission), ``engine.stage``
(host arrays, transfers, key draws) and one ``engine.launch.<program>``
per jitted call, which carries the rows it computes (``rows``,
``pad_rows``, ``decode_rows``, ``prefill_rows``); ``engine.drain`` around
the blocking device->host token copy. A model whose expert layers run the
grouped matmul (``models.moe.held_experts``) also returns, from every
launch, the rows routed to each held expert and the rows the matmul
computed, summed over its layers; the drain fetches them with the tokens
and puts them on an ``engine.drain.experts`` span inside ``engine.drain``
(stats ``routed_rows``, ``expert_pad_rows``, ``expert_groups`` — the
(layer, held expert) pairs that had rows, whose weights the matmul read —
``expert_rows``, one count per held expert comma-joined, and
``launches``). With no profiler running a span
costs about a microsecond and touches nothing on the device.
``Engine.counters`` (``serving.metrics.ServingCounters``) keeps the same
counts, cumulative over the engine's lifetime.
"""

from __future__ import annotations

import dataclasses
import time
import traceback
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.models.layers import Ctx
from repro.serving.metrics import ServingCounters, note_compile_event

# default prefill chunk: small enough to bound the decode stall a chunk
# inserts, large enough that the per-chunk dispatch/attention overhead
# amortises (DESIGN.md §13)
DEFAULT_CHUNK_SIZE = 32


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: Optional[List[int]] = None
    # stable request id: the per-request sampling key is derived from it, so
    # a retry submitted under the same rid reproduces its token stream
    # bit-for-bit in off mode (None -> submission index; reproducible only
    # within one session's submission order)
    rid: Optional[str] = None
    # ladder level assigned at admission (sac.DegradeLadder index; 0 = full
    # fidelity). Ignored unless the engine was built with ``ladder=``.
    degrade_level: int = 0
    # absolute deadline on the scheduler's clock (time.perf_counter unless
    # the front-end injects its own); ``step(now=...)`` expires the request
    # wherever it is — queued, mid-prefill or mid-decode
    deadline: Optional[float] = None


@dataclasses.dataclass
class RequestError:
    """Structured per-request failure record (DESIGN.md §16).

    Replaces the PR 6 bare ``None`` sentinel: a failed request's slot in the
    results list (and ``engine.request_errors``) carries the reason, the
    phase it died in (``admit | prefill | decode``), the slot it occupied,
    the tripping layer when the guard assigned one, and whether a retry is
    worth attempting (transient exception: yes; guard hard-fail on a
    persistent analog fault: no).
    """

    reason: str
    phase: str = "decode"
    slot: Optional[int] = None
    layer: Optional[int] = None
    retryable: bool = True
    # replica that produced the failure (PR 10 scale-out): lets serve.py and
    # the router attribute failover causes per-replica. None on single-engine.
    replica: Optional[str] = None

    def __str__(self) -> str:
        where = f"slot={self.slot}" if self.slot is not None else "queued"
        lay = f", layer={self.layer}" if self.layer is not None else ""
        rep = f"{self.replica}:" if self.replica is not None else ""
        return f"[{rep}{self.phase}/{where}{lay}] {self.reason}"


@dataclasses.dataclass
class DegradePolicy:
    """Stateful guard-escalation policy (host side, per (slot, layer)).

    ``pin_after``: after this many hard trips (both in-graph rungs failed)
    of a layer for a slot, pin that (slot, layer) to the digital path for
    the rest of the request (None disables pinning). ``fail_after``: after
    this many *steps* with any hard trip for a slot, declare the request
    failed — its result becomes a ``RequestError`` and the slot recycles
    (None: never fail; keep serving on the digital recompute)."""

    pin_after: Optional[int] = 1
    fail_after: Optional[int] = None


def _validate_requests(requests: List[Request], max_len: int) -> None:
    """Shared request validation for both engines (satellite of PR 6: the
    loop engine used to skip validation entirely and failed deep inside the
    forward on bad shapes)."""
    for i, r in enumerate(requests):
        prompt = np.asarray(r.prompt)
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError(
                f"request {i}: prompt must be a non-empty 1-D token "
                f"array, got shape {prompt.shape}")
        if r.max_new_tokens < 1:
            raise ValueError(
                f"request {i}: max_new_tokens must be >= 1, got "
                f"{r.max_new_tokens}")
        total = prompt.shape[0] + r.max_new_tokens
        if total > max_len:
            raise ValueError(
                f"request {i}: prompt length {prompt.shape[0]} + "
                f"max_new_tokens {r.max_new_tokens} = {total} overflows "
                f"the engine's max_len={max_len}; raise max_len or "
                f"shorten the request")


def _host_snapshot(a: np.ndarray) -> jnp.ndarray:
    """Device copy of a host array the scheduler mutates in place.

    ``jnp.asarray`` may alias a numpy buffer and read it only when the
    asynchronously dispatched program runs; a slot recycled in between
    (``_rk_slot[s] = 0``) would then change the inputs of a step already
    issued. A private copy keeps every step's inputs what they were at
    dispatch.
    """
    return jnp.asarray(np.array(a))


def _request_uid(r: Request, fallback: int) -> int:
    """Stable 31-bit uid behind the per-request sampling key."""
    if r.rid:
        return zlib.crc32(str(r.rid).encode()) & 0x7FFFFFFF
    return fallback & 0x7FFFFFFF


def _pow2_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


_COMPILES_WATCHED = False


def _watch_compiles() -> None:
    """Count JAX traces and compiles for ``ServingCounters``: one
    listener per process, however many engines it builds."""
    global _COMPILES_WATCHED
    if not _COMPILES_WATCHED:
        monitoring.register_event_duration_secs_listener(note_compile_event)
        _COMPILES_WATCHED = True


def _jit_cache_size(jitted) -> int:
    """Compiled-trace count behind a ``jax.jit`` callable."""
    return int(jitted._cache_size())


def _apply_attn_impl(cfg: ModelConfig, attn_impl: Optional[str]) -> ModelConfig:
    """Validate-and-apply an ``attn_impl`` override; shared by both engines
    (they used to duplicate the preamble and could drift).

    ``"kernel"`` now covers every decode family: GQA routes through
    ``kernels/decode_attention.py``, MLA through the latent-cache
    ``kernels/mla_decode.py``, and ssm/hybrid recurrence through
    ``kernels/ssm_scan.py`` (DESIGN.md §11/§15) — the old loud rejection of
    ssm/MLA is gone because there is no longer a silent einsum fallback to
    mislabel. Unknown strings still fail here, at engine construction,
    rather than deep inside the first jitted forward."""
    if attn_impl is None:
        return cfg
    if attn_impl not in ("einsum", "kernel"):
        raise ValueError(
            f"attn_impl must be 'einsum' or 'kernel', got {attn_impl!r}")
    return dataclasses.replace(cfg, attn_impl=attn_impl)


def _resolve_deploy(deploy: Optional[bool], mode: str) -> bool:
    """None -> auto (deploy for sim-mode serving); True requires sim."""
    if deploy is None:
        return mode == "sim"
    if deploy and mode != "sim":
        raise ValueError(
            f"deploy=True only affects cim_mode='sim' (got mode '{mode}'): "
            "pre-quantized weight planes are the sim-mode inference fast "
            "path; off/qat would silently ignore them")
    return bool(deploy)


def _maybe_deploy(cfg: ModelConfig, params: Any, deployed: bool,
                  fault: Any = None, guard: Any = False) -> Any:
    if not deployed:
        return params
    from repro.core.deploy import deploy as deploy_params
    return deploy_params(cfg, params, fault=fault, guard=guard)


def _sample_tokens(logits: jnp.ndarray, temps: jnp.ndarray,
                   keys: jnp.ndarray) -> jnp.ndarray:
    """(B, V) logits + (B,) temps + (B, 2) per-request keys -> (B,) int32.

    Each row samples under its own key (``fold_in(request key, token
    index)``, derived by the caller) so sampled streams depend only on the
    request identity and position — never on batch composition or on the
    engine's per-step key chain. Argmax rows (temp<=0) ignore the keys
    entirely: greedy streams are independent of the key plumbing.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe = jnp.where(temps > 0, temps, 1.0)
    scaled = logits.astype(jnp.float32) / safe[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def _row_sample_keys(rkeys: jnp.ndarray, tok_idx: jnp.ndarray) -> jnp.ndarray:
    """(B, 2) request keys + (B,) token indices -> (B, 2) sampling keys."""
    return jax.vmap(jax.random.fold_in)(rkeys, tok_idx)


# terminal request outcomes (acceptance vocabulary of the overload soak);
# "shed" is assigned by the front-end, which never submits a shed request
OUTCOMES = ("completed", "failed", "cancelled", "deadline_expired", "shed")


class Engine:
    """Fused slot-batched engine: one jitted step advances all slots."""

    # right-padded prefill (chunked or bucketed) is masked out by the
    # per-row causal/validity mask for attention caches. Exact-length
    # prefill (no chunking, no bucketing) elsewhere: recurrent SSM state
    # would absorb the pad tokens, and MoE expert capacity scales with the
    # per-forward token count (both padding *and* chunk boundaries would
    # change keep/drop routing decisions vs the whole prompt).
    _BUCKETED_FAMILIES = ("dense", "vlm")

    def __init__(self, cfg: ModelConfig, params: Any, max_slots: int = 4,
                 max_len: int = 512, cim_mode: Optional[str] = None,
                 seed: int = 0, drain_every: int = 64,
                 attn_impl: Optional[str] = None,
                 deploy: Optional[bool] = None,
                 chunk_size: Optional[int] = None,
                 record_ttft: bool = False,
                 fused_step: Optional[bool] = None,
                 fuse_layer: Optional[bool] = None,
                 guard: Any = None,
                 degrade: Optional[DegradePolicy] = None,
                 fault: Any = None,
                 fault_slots: Any = None,
                 pin_slots: Any = None,
                 ladder: Any = None,
                 drift: Any = None,
                 calib: Any = None,
                 replica: Optional[str] = None,
                 device: Any = None):
        # replica label (PR 10 scale-out): stamped onto every RequestError
        # this engine produces so the router/serve.py can attribute failover
        # causes; None for a standalone engine.
        self.replica = replica
        # whole-replica failure state (core.faults.ReplicaFaultSpec): a
        # killed engine simulates device loss — step/drain raise, undrained
        # device-side tokens are gone; a wedged engine simulates a hung
        # launch — step "succeeds" but makes no progress. The router detects
        # both and migrates in-flight requests (serving/router.py).
        self.dead: Optional[str] = None
        self.wedged = False
        if cfg.family == "encdec":
            raise ValueError("encdec serving needs per-request encoder "
                             "frames; the token-only engines don't carry them")
        # attn_impl="kernel" flips the fused decode step (and bucketed
        # prefill) onto the length-aware Pallas paths — O(len[b]) per slot
        # instead of O(max_len) (DESIGN.md §11/§15). None defers to
        # cfg.attn_impl; "einsum" is the dense reference path.
        cfg = _apply_attn_impl(cfg, attn_impl)
        # fuse_layer=True routes decode-shaped dense blocks through the
        # per-layer megakernel (kernels/fused_step.py, DESIGN.md §15)
        if fuse_layer is not None and fuse_layer:
            cfg = dataclasses.replace(cfg, fuse_layer=True)
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.drain_every = drain_every
        self.record_ttft = record_ttft
        self.ttft_s: List[Optional[float]] = []
        self.key = jax.random.PRNGKey(seed)
        # per-request sampling keys fold off a base derived only from the
        # seed — never from the consumed per-step chain — so they are stable
        # across generate() calls and engine restarts with the same seed
        self._sample_base = jax.random.fold_in(jax.random.PRNGKey(seed),
                                               0x5A17)
        self._bucketed = cfg.family in self._BUCKETED_FAMILIES
        # chunk_size=None -> auto: chunked prefill (DESIGN.md §13) for EVERY
        # family. The old exact-length carve-outs are gone: recurrent
        # ssm/hybrid state now carries across chunks exactly (the SSD scan
        # is seeded from the cached state and the final chunk's right-pad is
        # a provable state no-op under dt=0 masking via ``ctx.prefill_valid``
        # — models/ssm.py), and MoE serving routes dropless (capacity =
        # every token kept), so routing no longer depends on the per-forward
        # token count. chunk_size=0 forces the legacy whole-prompt path (the
        # prefill_bench baseline; still exact-length for non-bucketed
        # families).
        if chunk_size is not None and chunk_size < 0:
            raise ValueError(f"chunk_size must be >= 0, got {chunk_size}")
        if chunk_size is None:
            chunk_size = DEFAULT_CHUNK_SIZE
        self.chunk_size = int(chunk_size)
        # the cache is over-allocated to the next chunk multiple so a final
        # padded chunk's cache write can never clamp back onto live keys
        # (chunk writes always start at a multiple of chunk_size)
        self._alloc_len = (-(-max_len // self.chunk_size) * self.chunk_size
                           if self.chunk_size else max_len)
        mode = cim_mode if cim_mode is not None else cfg.cim.mode
        if cfg.fuse_layer:
            # refused here, not skipped per call: a megakernel that cannot
            # run would otherwise serve silently on the per-layer path
            from repro.kernels.fused_step import check_fused_layer
            check_fused_layer(cfg, max_slots, self._alloc_len,
                              sim=mode == "sim")
        # deploy=None auto-deploys pre-quantized weight planes for sim-mode
        # serving (core.deploy, DESIGN.md §12): weights are programmed once
        # per engine like the macro's weight-stationary array, instead of
        # re-quantized per token per layer. Bit-identical outputs; greedy
        # tokens are unchanged (tested). deploy=False serves the PR 3 path.
        self.deployed = _resolve_deploy(deploy, mode)
        # robustness wiring (DESIGN.md §14): guard=True -> default GuardSpec;
        # the checksum column rides on the deployed plane, so the guard is a
        # sim-mode + deployed feature; stuck-at faults also act at deploy
        if guard is True:
            from repro.core.guard import GuardSpec
            guard = GuardSpec()
        self.guard = guard or None
        if self.guard is not None:
            if mode != "sim" or not self.deployed:
                raise ValueError(
                    "guard requires cim_mode='sim' with deployed weight "
                    "planes — the ABFT checksum column is attached at "
                    "deploy time (core.deploy) and compares the *analog* "
                    "column sum (DESIGN.md §14)")
            if cfg.family not in ("dense", "vlm", "moe", "ssm"):
                raise ValueError(
                    f"guard trip export rides the stacked layer scan; "
                    f"family '{cfg.family}' is not wired for it")
        # load-adaptive vote-degradation ladder (DESIGN.md §16): per-row
        # reduced-vote admission, modelled as extra output-referred readout
        # noise in layers.dense. Mutually exclusive with the guard (guard
        # escalation needs per-call blame and its dense path bypasses the
        # ladder noise) and with the dense megakernel (fuse_layer decode
        # bypasses layers.dense entirely, so a ladder level would silently
        # become bookkeeping-only).
        self.ladder = ladder
        if self.ladder is not None:
            if self.guard is not None:
                raise ValueError(
                    "ladder and guard are mutually exclusive: guarded dense "
                    "bypasses the per-row degraded-vote noise path")
            if cfg.fuse_layer:
                raise ValueError(
                    "ladder requires fuse_layer=False: the per-layer "
                    "megakernel bypasses layers.dense, where the per-row "
                    "degraded-vote noise is applied")
        # temporal drift + online calibration (DESIGN.md §17): drift is a
        # core.drift.DriftSpec evaluated at the engine's monotonic step
        # counter; calib=True -> default CalibPolicy running the background
        # probe/canary schedule of core.calibrate.
        if calib is True:
            from repro.core.calibrate import CalibPolicy
            calib = CalibPolicy()
        self.drift = drift or None
        self.calib = calib or None
        if self.drift is not None:
            if mode != "sim":
                raise ValueError(
                    "drift requires cim_mode='sim': temporal drift acts on "
                    "the analog readout chain (dequant epilogue, DESIGN.md "
                    "§17) — there is nothing to drift on the digital path")
            if cfg.fuse_layer:
                raise ValueError(
                    "drift requires fuse_layer=False: the per-layer "
                    "megakernel bypasses the layers.dense dequant epilogue "
                    "where drift (and its trim correction) is applied")
        if self.calib is not None:
            if self.drift is None:
                raise ValueError(
                    "calib requires drift=: background calibration "
                    "estimates trims against the temporal drift model")
            if not self.deployed:
                raise ValueError(
                    "calib requires deployed weight planes: the trim width "
                    "is the widest deployed macro plane (core.calibrate)")
        self.fault = fault
        self.fault_slots = frozenset(int(s) for s in (fault_slots or ()))
        # pin_slots: operator knob — serve these slots on the digital path
        # from step 0 (the ladder's final rung, applied preemptively; also
        # the bit-exact fault-free twin of a hard-faulted slot, since the
        # batch shares one per-tensor activation scale — DESIGN.md §14)
        self.pin_slots = frozenset(int(s) for s in (pin_slots or ()))
        if self.pin_slots and self.guard is None:
            raise ValueError("pin_slots requires guard: the digital bypass "
                             "is routed by the guarded dense")
        self.degrade = degrade if degrade is not None else (
            DegradePolicy() if self.guard is not None else None)
        self.guard_trip_counts = np.zeros(cfg.n_layers, np.int64)
        self.guard_hard_counts = np.zeros(cfg.n_layers, np.int64)
        self.request_errors: List[Optional[RequestError]] = []
        # the GuardSpec itself is threaded into deploy so the checksum plane
        # layout (segments) matches what guarded_dense will check against
        self.params = _maybe_deploy(cfg, params, self.deployed, fault=fault,
                                    guard=self.guard)
        # device=None serves on the default device. A replica of a pool is
        # given its own device: params, cache, token and key state are
        # committed there, so every program of this engine runs on it (host
        # inputs follow the committed arguments)
        self.device = device
        place = ((lambda t: t) if device is None
                 else (lambda t: jax.device_put(t, device)))
        self.params = place(self.params)

        # drift clock + background calibration controller. The step counter
        # is monotonic for the engine's lifetime (macro age — begin() does
        # NOT reset it); benches/tests may assign ``drift_step`` to jump the
        # trajectory. The controller's probe keys chain off CalibPolicy.seed
        # only, so enabling it never perturbs the token PRNG streams.
        self.drift_step = 0
        self.drift_events: List[Dict[str, Any]] = []
        self.drift_degraded = False
        self._drift_pin_all = False
        self._drift_ctl = None
        if self.calib is not None:
            from repro.core.calibrate import DriftController, max_plane_width
            from repro.core.sac import get_policy
            pol = get_policy(cfg.cim.policy)
            probe_spec = pol.mlp if pol.mlp is not None else pol.attn
            if probe_spec is None:
                raise ValueError(
                    "calib needs at least one CIM-routed class in the SAC "
                    "policy to define the probe operating point")
            n_cols = max_plane_width(self.params)
            self._drift_ctl = DriftController(
                probe_spec, self.drift, self.calib, n_cols,
                use_kernel=cfg.cim.use_kernel)

        # allocated once; recycled for the lifetime of the engine
        self.caches = place(tf.init_caches(cfg, max_slots, self._alloc_len))
        self.last_tok = place(jnp.zeros((max_slots,), jnp.int32))
        self.key = place(self.key)
        deployed = self.deployed
        guard_on = self.guard is not None
        gspec, fspec = self.guard, self.fault
        ladder_votes = (tuple(self.ladder.votes)
                        if self.ladder is not None else ())

        drift_spec = self.drift
        # expert layers on the grouped-matmul path report their rows
        # (held_experts) from every program
        moe_stats = (cfg.moe is not None and
                     Ctx.make(cfg, mode=mode).spec_for("moe_expert") is None)
        self._moe_stats = moe_stats
        no_rows = ((jnp.zeros((cfg.moe.held,), jnp.int32), jnp.int32(0),
                    jnp.int32(0)) if moe_stats else ())

        def make_ctx(kctx, pin, frow, lvl=None, dstate=None):
            ctx = Ctx.make(cfg, kctx, mode=mode, deployed=deployed,
                           guard=gspec, fault=fspec)
            if moe_stats:
                ctx.moe_log = []
            ctx.pin_layers = pin
            ctx.fault_rows = frow
            if ladder_votes and lvl is not None:
                ctx.degrade_levels = ladder_votes
                ctx.degrade_rows = lvl
            if drift_spec is not None:
                ctx.drift = drift_spec
                ctx.drift_state = dstate
            return ctx

        # whether every program addresses the stacked attention cache in
        # place by (layer, row), by program name: noted when the program
        # is traced, read by each launch's span and counters
        inplace = self._inplace = {}
        stacked = tf.cache_in_place(self.caches)

        def slot_forward(params, caches, tokens, slot, reset, n_valid, ctx):
            """Run ``tokens`` (1, S) through slot ``slot`` of the stacked
            cache and leave the slot's length at its start + ``n_valid``;
            ``reset`` first restarts the slot (a new request).

            An attention cache is addressed in place: the forward runs on
            the whole stack with the slot as its one row, writes only the
            chunk's own rows, and a restart is length 0 — the previous
            occupant's rows lie past the length, where the kernels' length
            masks and ``_cached_mask`` never read. A cache with recurrent
            ``conv``/``state`` leaves (or the hybrid's) is worked on as the
            slot's batch-1 slice instead, zero-wiped on a restart: a
            1-token prompt hits the SSM *decode* branch, which reads that
            state, and the previous occupant's must not leak in."""
            if stacked:
                row = jnp.reshape(slot, (1,)).astype(jnp.int32)
                start = jnp.where(reset, 0, tf._cache_len(cfg, caches)[row])
                caches = tf.set_cache_lens(caches, start, rows=row)
                logits, caches = tf.forward(params, {"tokens": tokens}, cfg,
                                            ctx, caches, rows=row)
                return logits, tf.set_cache_lens(caches, start + n_valid,
                                                 rows=row)
            ctx.cache_sliced = True
            sl = jax.tree.map(
                lambda t: jnp.where(reset, jnp.zeros_like(t), t),
                tf.take_slot(caches, slot))
            start = tf._cache_len(cfg, sl)            # (1,) written keys
            logits, sl = tf.forward(params, {"tokens": tokens}, cfg, ctx, sl)
            sl = tf.set_cache_lens(sl, start + n_valid)
            return logits, tf.put_slot(caches, sl, slot)

        def prefill_fn(params, caches, last_tok, tokens, true_len, slot,
                       temp, key, rkey, lvl, dstate=None, pin=None,
                       frow=None):
            """Prefill one request into its slot of the stacked cache."""
            # the split mirrors the legacy (kctx, ksamp) draw so the CIM
            # noise context consumes the per-step chain unchanged; sampling
            # now keys off the request identity instead of ksamp
            kctx, _ = jax.random.split(key)
            ctx = make_ctx(kctx, pin, frow, lvl=jnp.reshape(lvl, (1,)),
                           dstate=dstate)
            ctx.prefill_valid = jnp.reshape(true_len, (1,))
            logits, caches = slot_forward(params, caches, tokens, slot, True,
                                          true_len, ctx)
            inplace["prefill"] = stacked and not ctx.cache_sliced
            # last *valid* position of the (possibly right-padded) prompt
            last = jax.lax.dynamic_index_in_dim(logits, true_len - 1, axis=1,
                                                keepdims=False)    # (1, V)
            tok = _sample_tokens(last, jnp.full((1,), temp, jnp.float32),
                                 jax.random.fold_in(rkey, 0)[None])[0]
            out = (caches, last_tok.at[slot].set(tok), tok)
            if guard_on:
                out = out + (ctx.guard_trips, ctx.guard_hard)   # (L, 1) each
            if moe_stats:
                out = out + (ctx.moe_rows,)
            return out

        def chunk_core(params, caches, prev_tok, tokens, reset, valid,
                       is_final, slot, temp, key, rkey, lvl,
                       dstate=None, pin=None, frow=None):
            """Advance slot ``slot``'s prefill by one fixed-shape chunk.

            ``tokens``: (1, chunk_size), right-padded; ``valid`` of them are
            real. ``reset`` restarts the slot on the first chunk (the
            recycled-slot hygiene of ``slot_forward``); ``is_final``
            commits the sampled first token as the returned ``keep``. One
            shape -> exactly one compiled trace for every prompt length.
            Shared by the per-call ``prefill_chunk`` and the fused ``_step``.
            """
            kctx, _ = jax.random.split(key)
            ctx = make_ctx(kctx, pin, frow, lvl=jnp.reshape(lvl, (1,)),
                           dstate=dstate)
            # state-carrying blocks (ssm conv/SSD) must treat the chunk's
            # right-pad as absent, not as zero tokens (models/ssm.py)
            ctx.prefill_valid = jnp.reshape(valid, (1,))
            # the forward writes the full padded chunk; only `valid` of it
            # is real — the pad keys land beyond the slot's length and the
            # per-row validity mask never exposes them (the next chunk
            # overwrites them in place)
            logits, caches = slot_forward(params, caches, tokens, slot, reset,
                                          valid, ctx)
            last = jax.lax.dynamic_index_in_dim(logits, valid - 1, axis=1,
                                                keepdims=False)   # (1, V)
            tok = _sample_tokens(last, jnp.full((1,), temp, jnp.float32),
                                 jax.random.fold_in(rkey, 0)[None])[0]
            keep = jnp.where(is_final, tok, prev_tok)
            return caches, keep, tok, ctx

        def prefill_chunk_fn(params, caches, last_tok, tokens, reset, valid,
                             is_final, slot, temp, key, rkey, lvl,
                             dstate=None, pin=None, frow=None):
            caches, keep, tok, ctx = chunk_core(
                params, caches, last_tok[slot], tokens, reset, valid,
                is_final, slot, temp, key, rkey, lvl, dstate, pin, frow)
            inplace["prefill_chunk"] = stacked and not ctx.cache_sliced
            out = (caches, last_tok.at[slot].set(keep), tok)
            if guard_on:
                out = out + (ctx.guard_trips, ctx.guard_hard)
            if moe_stats:
                out = out + (ctx.moe_rows,)
            return out

        def decode_core(params, caches, last_tok, active, temps, key,
                        rkeys, tok_idx, lvls, dstate=None, pin=None,
                        frow=None):
            """One fused step: every active slot emits its next token."""
            kctx, _ = jax.random.split(key)
            ctx = make_ctx(kctx, pin, frow, lvl=lvls, dstate=dstate)
            logits, new_caches = tf.forward(
                params, {"tokens": last_tok[:, None]}, cfg, ctx, caches)
            toks = _sample_tokens(logits[:, -1], temps,
                                  _row_sample_keys(rkeys, tok_idx))
            toks = jnp.where(active, toks, last_tok)
            new_caches = tf.mask_cache_advance(new_caches, caches, active)
            return new_caches, toks, ctx

        def decode_fn(params, caches, last_tok, active, temps, key,
                      rkeys, tok_idx, lvls, dstate=None, pin=None,
                      frow=None):
            new_caches, toks, ctx = decode_core(
                params, caches, last_tok, active, temps, key, rkeys,
                tok_idx, lvls, dstate, pin, frow)
            inplace["decode"] = stacked and not ctx.cache_sliced
            out = (new_caches, toks)
            if guard_on:
                out = out + (ctx.guard_trips, ctx.guard_hard)
            if moe_stats:
                out = out + (ctx.moe_rows,)
            return out

        n_slots = max_slots

        def draw_keys_fn(key, mask):
            """The per-call PRNG chain — ``key, k = split(key)`` once per
            True row of ``mask``, zeros elsewhere — as ONE jitted dispatch.

            ``fused_iteration`` used to draw its per-slot + decode keys with
            up to ``max_slots + 1`` sequential host-side ``split`` calls
            plus a ``jnp.stack`` (~1.4 ms of dispatch per fused iteration on
            the 2-core container — more than a whole chunk forward). The
            scan below is bit-identical to that sequential chain, so the
            fused and per-call paths still consume the same PRNG stream.
            """
            def body(k, m):
                nk, sub = jax.random.split(k)
                return (jnp.where(m, nk, k),
                        jnp.where(m, sub, jnp.zeros_like(sub)))

            return jax.lax.scan(body, key, mask)

        def step_fn(params, caches, last_tok, chunk_toks, flags, temps,
                    keys, rkeys, dstate=None):
            """One whole scheduler iteration as ONE jitted program.

            Collapses the per-iteration dispatch tail — up to ``max_slots``
            ``_prefill_chunk`` launches plus one ``_decode`` launch — into a
            single launch (DESIGN.md §15). The chunk advances run in a
            ``lax.fori_loop`` over the prefilling slots alone, in ascending
            slot order (their indices and count derived on the device from
            ``flags``), so a slot that is not prefilling costs nothing: one
            traced chunk body, not ``max_slots`` unrolled copies (the
            unrolled version quadrupled the compile and therefore cold
            TTFT), each chunk on the whole stacked cache with its slot as
            the row (``slot_forward``). A vmap over slots was tried and
            rejected — it runs the chunk body for EVERY lane, and the
            discarded lanes' compute cost more than the dispatch it saved.
            The batch decode then runs under ONE ``lax.cond(do_decode,
            ...)`` — skipping the whole decode forward on pure-prefill
            iterations, which the whole-prompt baseline never pays (a
            *static* do_decode would split ``_step`` into two compiled
            variants, breaking the 1-trace witness). Sequencing, math and
            RNG match the legacy per-call path, so the token streams match
            bit for bit.

            chunk_toks: (S, 1, chunk); flags: (S, 7) int32 — columns are
            [reset, valid, final, prefilling, act_after, tok_idx, level],
            packed into one host->device transfer (separate ``jnp.asarray``
            calls cost ~60 us of dispatch each); temps: (S,) f32; keys:
            (S+1, 2) raw PRNG keys — row ``s`` feeds slot ``s``'s chunk,
            the last row feeds the decode (zeros where unused); rkeys:
            (S, 2) per-request sampling keys.
            """
            ctxs = []
            pre = flags[:, 3] != 0
            order = jnp.nonzero(pre, size=n_slots)[0].astype(jnp.int32)

            def chunk(i, carry):
                caches, last_tok, ptoks, rows = carry
                s = order[i]
                f = flags[s]
                caches, keep, tok, ctx = chunk_core(
                    params, caches, last_tok[s], chunk_toks[s], f[0] != 0,
                    f[1], f[2] != 0, s, temps[s], keys[s], rkeys[s], f[6],
                    dstate)
                ctxs.append(ctx)
                if moe_stats:
                    rows = jax.tree.map(jnp.add, rows, ctx.moe_rows)
                return (caches, last_tok.at[s].set(keep),
                        ptoks.at[s].set(tok), rows)

            caches, last_tok, ptoks, rows = jax.lax.fori_loop(
                0, jnp.sum(pre.astype(jnp.int32)), chunk,
                (caches, last_tok, jnp.zeros((n_slots,), jnp.int32), no_rows))

            active = flags[:, 4] != 0

            def dec(ops):
                caches, last_tok = ops
                caches, last_tok, ctx = decode_core(
                    params, caches, last_tok, active, temps, keys[n_slots],
                    rkeys, flags[:, 5], flags[:, 6], dstate)
                ctxs.append(ctx)
                return caches, last_tok, ctx.moe_rows if moe_stats else ()

            caches, last_tok, dec_rows = jax.lax.cond(
                jnp.any(active), dec, lambda ops: ops + (no_rows,),
                (caches, last_tok))
            inplace["step"] = stacked and not any(c.cache_sliced
                                                  for c in ctxs)
            if not moe_stats:
                return caches, last_tok, ptoks
            rows = jax.tree.map(jnp.add, rows, dec_rows)
            return caches, last_tok, ptoks, rows

        # donate only the cache: last_tok/toks arrays stay referenced by the
        # pending-drain token log until device_get, so they must not alias
        self._programs = {
            "prefill": jax.jit(prefill_fn, donate_argnums=(1,)),
            "prefill_chunk": jax.jit(prefill_chunk_fn, donate_argnums=(1,)),
            "decode": jax.jit(decode_fn, donate_argnums=(1,)),
            "step": jax.jit(step_fn, donate_argnums=(1,)),
        }
        self._built: set = set()
        self._prefill = self._programs["prefill"]
        self._prefill_chunk = self._programs["prefill_chunk"]
        self._decode = self._programs["decode"]
        self._step = self._programs["step"]
        self._draw_keys = jax.jit(draw_keys_fn)
        # fused_step=None -> auto: collapse each scheduler iteration into
        # the single _step launch whenever prefill is chunked and the guard
        # is off (guard escalation needs per-slot host-side blame, which the
        # all-or-nothing fused launch cannot assign). An engine whose _step
        # raises at run time falls back to the per-call path for its
        # lifetime; one that cannot trace or compile it raises (_build).
        if fused_step is None:
            fused_step = self.guard is None and self.chunk_size > 0
        elif fused_step and (self.guard is not None or self.chunk_size == 0):
            raise ValueError(
                "fused_step=True requires chunked prefill (chunk_size > 0) "
                "and no guard: the single-launch step has no per-slot "
                "failure isolation and no whole-prompt admission path")
        self._fused_step = bool(fused_step)
        self._fused_ok = True
        # the first error that made the fused iteration fall back
        self.fused_step_error: Optional[str] = None
        # cumulative work counts over the engine's lifetime; launches and
        # iterations are the dispatch witness of serving_bench
        self.counters = ServingCounters()
        _watch_compiles()
        self._frow_host = np.array([s in self.fault_slots
                                    for s in range(self.max_slots)])
        self.begin()

    # ------------------------------------------------------------------ API
    @property
    def prefill_traces(self) -> int:
        """Distinct prefill programs traced: 1 for chunked prefill, one per
        power-of-two bucket for the whole-prompt path."""
        return sum(_jit_cache_size(self._programs[n])
                   for n in ("prefill", "prefill_chunk", "step"))

    # -------------------------------------------- incremental session API
    def begin(self) -> None:
        """Reset scheduler state for a fresh session (also called by
        ``__init__`` and ``generate``). The device-side cache is NOT
        touched: admission hygiene (a new request restarts its slot,
        ``slot_forward``) guarantees a recycled slot is token-clean
        regardless of what the previous session left in it."""
        S = self.max_slots
        self._reqs: List[Request] = []
        self._req_index: Dict[int, int] = {}
        self._queue: List[Request] = []
        self._slots: List[Optional[Request]] = [None] * S
        self._counts = [0] * S
        self._offsets = [0] * S       # chunked-prefill tokens written
        self._decoding = [False] * S  # prefill done, slot in decode
        # emitted tokens stay on device until drained:
        # ("p", scalar_dev_tok, req_idx) | ("d", (B,) dev_toks, per-slot idx)
        self._pend: List[Tuple[str, Any, Any]] = []
        # each launch's expert rows, on device until drained with the tokens
        self._pend_moe: List[Any] = []
        # host-side degradation state, per (slot, layer); reset on recycle
        self._pinned = np.zeros((S, self.cfg.n_layers), bool)
        for s in self.pin_slots:
            self._pinned[s] = True
        self._hard_counts = np.zeros((S, self.cfg.n_layers), np.int64)
        self._trip_counts = np.zeros((S, self.cfg.n_layers), np.int64)
        self._fail_steps = np.zeros(S, np.int64)
        # per-request guard outcome, captured when the slot retires
        # (ri -> {"trips", "hard", "hard_layers"}) — the front-end copies
        # it into the request's MetricsLog record
        self.guard_report: Dict[int, Dict[str, Any]] = {}
        self._rk_slot = np.zeros((S, 2), np.uint32)   # per-slot request key
        self._lvl_slot = np.zeros(S, np.int32)        # per-slot ladder level
        self._rkeys: List[np.ndarray] = []            # per-request key
        self._levels: List[int] = []                  # per-request level
        self.status: List[str] = []                   # per-request lifecycle
        self.request_errors = []
        self.ttft_s = []
        self._t0 = time.perf_counter()
        self._turnover = False

    def submit(self, r: Request) -> int:
        """Enqueue one request; returns its index in this session.

        The request's sampling key is fixed here — ``fold_in(seed-derived
        base, crc32(rid))`` — so two submissions with the same ``rid``
        (e.g. a front-end retry) draw identical per-token keys.
        """
        _validate_requests([r], self.max_len)
        ri = len(self._reqs)
        self._reqs.append(r)
        self._req_index[id(r)] = ri
        r.out_tokens = []
        self._queue.append(r)
        self.status.append("queued")
        self.request_errors.append(None)
        self.ttft_s.append(None)
        uid = _request_uid(r, ri)
        self._rkeys.append(np.asarray(
            jax.random.fold_in(self._sample_base, uid), np.uint32))
        lvl = 0
        if self.ladder is not None:
            lvl = min(max(int(r.degrade_level), 0), self.ladder.n_levels - 1)
        self._levels.append(lvl)
        return ri

    def cancel(self, r: Request, outcome: str = "cancelled") -> bool:
        """Withdraw a queued or running request between steps.

        A running request's slot is freed host-side only: the next
        occupant's admission restart (length 0, or a zero-wipe of
        recurrent state; ``slot_forward``) makes the recycle token-clean,
        so no device work is needed — the slot-recycling machinery does
        the cancellation for free. Tokens already emitted stay in
        ``r.out_tokens`` as the partial stream. Returns False if the
        request is unknown or already terminal."""
        if outcome not in OUTCOMES[1:]:
            raise ValueError(f"cancel outcome must be one of {OUTCOMES[1:]}")
        ri = self._req_index.get(id(r))
        if ri is None or self.status[ri] not in ("queued", "running"):
            return False
        if self.status[ri] == "queued":
            self._queue.remove(r)
        else:
            s = next(i for i, o in enumerate(self._slots) if o is r)
            self._capture_guard(s)
            self._free_slot(s)
            self._turnover = True
        self.status[ri] = outcome
        return True

    def expire_deadlines(self, now: float) -> int:
        """Cancel every request (queued, mid-prefill or mid-decode) whose
        ``deadline`` has passed on the caller's clock; returns the count."""
        n = 0
        live = list(self._queue) + [r for r in self._slots if r is not None]
        for r in live:
            if r.deadline is not None and now >= r.deadline:
                if self.cancel(r, outcome="deadline_expired"):
                    n += 1
        return n

    def has_work(self) -> bool:
        return bool(self._queue) or any(r is not None for r in self._slots)

    @property
    def free_slots(self) -> int:
        """Slots with no occupant AND no staged request waiting for one —
        the front-end's admission headroom signal."""
        return (sum(r is None for r in self._slots) - len(self._queue))

    def result_of(self, r: Request):
        """Terminal result: token list, RequestError, or None if live."""
        ri = self._req_index.get(id(r))
        if ri is None:
            return None
        st = self.status[ri]
        if st == "failed":
            return self.request_errors[ri]
        if st in ("queued", "running"):
            return None
        return r.out_tokens

    def status_of(self, r: Request) -> Optional[str]:
        """Lifecycle state of a submitted request (None if unknown):
        queued | running | completed | failed | cancelled | deadline_expired."""
        ri = self._req_index.get(id(r))
        return None if ri is None else self.status[ri]

    def error_of(self, r: Request) -> Optional[RequestError]:
        ri = self._req_index.get(id(r))
        return None if ri is None else self.request_errors[ri]

    def step(self, now: Optional[float] = None) -> bool:
        """One scheduler iteration: expire deadlines (when ``now`` is
        given), admit from the queue, advance every prefilling slot by one
        chunk, run the batch decode. Returns True if any slot did work."""
        if self.dead is not None:
            raise RuntimeError(
                f"replica {self.replica or '?'} dead: {self.dead}")
        if self.wedged:
            # a hung launch: the call "succeeds" but nothing advances —
            # only the router's no-progress watchdog can tell
            return True
        with TraceAnnotation("engine.step"):
            if now is not None:
                self.expire_deadlines(now)
            self._fill_slots()
            if not any(r is not None for r in self._slots):
                return False
            self.counters.iterations += 1
            self._turnover = False
            if (self._fused_step and self._fused_ok
                    and self._fused_iteration()):
                if self._turnover:
                    self._fill_slots()
            else:
                self._percall_iteration()
            if self.drift is not None:
                # background calibration/watchdog (at most ONE bounded
                # probe launch — no decode stall), then advance the
                # macro's clock
                self._drift_tick()
            if len(self._pend) >= self.drain_every:
                self.drain_pending()
            return True

    def kill(self, reason: str = "device lost") -> None:
        """Simulate whole-replica device loss (DESIGN.md §18).

        Every subsequent ``step``/``drain_pending`` raises; tokens emitted
        on-device but not yet drained are gone (exactly what losing the
        device means). In-flight requests are NOT failed here — the router
        migrates them to healthy replicas and their deterministic per-rid
        sampling keys replay the stream bit-for-bit.
        """
        self.dead = reason
        self._pend.clear()
        self._pend_moe.clear()

    def wedge(self) -> None:
        """Simulate a wedged launch queue: steps no-op without erroring."""
        self.wedged = True

    def unwedge(self) -> None:
        self.wedged = False

    def drain_pending(self) -> None:
        """Move emitted tokens device→host into ``out_tokens`` lists."""
        if self.dead is not None:
            raise RuntimeError(
                f"replica {self.replica or '?'} dead: {self.dead}")
        if not self._pend and not self._pend_moe:
            return
        n = 0
        with TraceAnnotation("engine.drain"):
            vals, moe = jax.device_get(([e[1] for e in self._pend],
                                        self._pend_moe))
            if moe:
                self._note_expert_rows(moe)
            for (kind, _, meta), v in zip(self._pend, vals):
                if kind == "p":
                    self._reqs[meta].out_tokens.append(int(v))
                    n += 1
                else:
                    for s, ri in enumerate(meta):
                        if ri is not None:
                            self._reqs[ri].out_tokens.append(int(v[s]))
                            n += 1
            self._pend.clear()
        self.counters.drains += 1
        self.counters.tokens_drained += n

    def _note_expert_rows(self, moe: List[Any]) -> None:
        """Count the drained launches' expert rows (``held_experts``) and
        mark them on the drain's trace."""
        per_expert = np.sum([np.asarray(r) for r, _, _ in moe], axis=0)
        computed = int(sum(int(c) for _, c, _ in moe))
        groups = int(sum(int(g) for _, _, g in moe))
        routed = int(per_expert.sum())
        c = self.counters
        c.expert_rows = (per_expert + (c.expert_rows or 0)).tolist()
        c.expert_routed_rows += routed
        c.expert_pad_rows += computed - routed
        c.expert_groups += groups
        with TraceAnnotation("engine.drain.experts", routed_rows=routed,
                             expert_pad_rows=computed - routed,
                             expert_groups=groups,
                             expert_rows=",".join(map(str, per_expert)),
                             launches=len(moe)):
            self._pend_moe.clear()

    def generate(self, requests: List[Request]) -> List[Any]:
        """Run all requests to completion; returns generated token lists.

        Exactly ``begin()`` + submit-all + ``step()``-until-done, so the
        batch API and the front-end's incremental session consume identical
        PRNG streams and produce identical tokens.

        Per-request failure contract (DESIGN.md §14/§16): a request aborted
        by a per-slot exception during prefill, by a per-slot exception
        during decode (isolated via solo re-probing — the rest of the batch
        advances), or by the guard's ``fail_after`` escalation yields a
        structured ``RequestError`` at its position — callers never see an
        exception for a single bad request, and the remaining slots finish
        unaffected (``self.request_errors`` carries the same objects).
        """
        self._validate(requests)
        self.begin()
        for r in requests:
            self.submit(r)
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > 100_000:
                raise RuntimeError("serving engine ran away")
        self.drain_pending()
        out = []
        for r in requests:
            ri = self._req_index[id(r)]
            out.append(self.request_errors[ri]
                       if self.status[ri] == "failed" else r.out_tokens)
        return out

    # ----------------------------------------------- drift + calibration
    def _dstate(self):
        """The traced drift state for this step's jitted calls: (step,
        trim_gain, trim_off) — trims are None without a controller. One
        pytree structure per engine config, so time never retraces."""
        if self.drift is None:
            return None
        if self._drift_ctl is None:
            return (jnp.asarray(self.drift_step, jnp.int32), None, None)
        return self._drift_ctl.trimmed_state(self.drift_step)

    def _drift_tick(self) -> None:
        """Run the calibration/watchdog schedule for this step and advance
        the drift clock. An "escalate" event (the trim model can no longer
        hold the macro in spec) pins every (slot, layer) to the digital
        path when the guard is armed — the PR 6 machinery as the ladder's
        last rung — or flags the engine degraded otherwise."""
        ctl = self._drift_ctl
        if ctl is not None:
            for e in ctl.tick(self.drift_step):
                e = dict(e)
                if e["kind"] == "escalate":
                    if self.guard is not None:
                        self._drift_pin_all = True
                        self._pinned[:, :] = True
                        e["action"] = "pin_digital"
                    else:
                        self.drift_degraded = True
                        e["action"] = "flag_degraded"
                self.drift_events.append(e)
        self.drift_step += 1

    def take_drift_events(self) -> List[Dict[str, Any]]:
        """Drain accumulated calibration/watchdog events (front-end tick)."""
        evs, self.drift_events = self.drift_events, []
        return evs

    @property
    def calibrations(self) -> int:
        return 0 if self._drift_ctl is None else self._drift_ctl.calibrations

    @property
    def watchdog_trips(self) -> int:
        return (0 if self._drift_ctl is None
                else self._drift_ctl.watchdog_trips)

    # ------------------------------------------------- scheduler internals
    def _free_slot(self, s: int) -> None:
        self._slots[s] = None
        self._decoding[s] = False
        self._counts[s] = 0
        self._offsets[s] = 0
        self._rk_slot[s] = 0
        self._lvl_slot[s] = 0
        self._reset_slot_guard(s)

    def _reset_slot_guard(self, s: int) -> None:
        # a drift escalation pins the whole engine digital — recycling a
        # slot must not silently un-pin it
        self._pinned[s] = (s in self.pin_slots) or self._drift_pin_all
        self._hard_counts[s] = 0
        self._trip_counts[s] = 0
        self._fail_steps[s] = 0

    def _capture_guard(self, s: int) -> None:
        """Snapshot the retiring slot's guard counters for its request."""
        if self.guard is None:
            return
        r = self._slots[s]
        if r is None:
            return
        ri = self._req_index[id(r)]
        self.guard_report[ri] = {
            "trips": int(self._trip_counts[s].sum()),
            "hard": int(self._hard_counts[s].sum()),
            "hard_layers": np.nonzero(self._hard_counts[s])[0].tolist(),
        }

    def guard_report_of(self, r: Request) -> Optional[Dict[str, Any]]:
        """Per-request guard outcome ({"trips", "hard", "hard_layers"}) or
        None (unknown request / guard off / still running)."""
        ri = self._req_index.get(id(r))
        return None if ri is None else self.guard_report.get(ri)

    def replica_of(self, r: Request) -> Optional[str]:
        """Replica label serving this request (the engine's own label; the
        router overrides this with the replica it dispatched to)."""
        return self.replica

    def _fail_request(self, s: int, err: RequestError) -> None:
        if err.replica is None:
            err.replica = self.replica
        r = self._slots[s]
        ri = self._req_index[id(r)]
        self.status[ri] = "failed"
        self.request_errors[ri] = err
        self._capture_guard(s)
        self._free_slot(s)

    def _finish_request(self, s: int) -> None:
        ri = self._req_index[id(self._slots[s])]
        self.status[ri] = "completed"
        self._capture_guard(s)
        self._free_slot(s)
        self._turnover = True

    def _note_guard(self, trips, hard, slot_cols) -> List[int]:
        """Fold one step's (L, B) guard counters into the host state.

        slot_cols: [(slot, column-in-B)] mapping for this call (prefill
        reports a single batch-1 column; decode reports all slots).
        Returns slots whose request just crossed ``fail_after``.
        """
        t, h = jax.device_get((trips, hard))
        t = np.asarray(t)
        h = np.asarray(h)
        self.guard_trip_counts += t.sum(axis=1).astype(np.int64)
        self.guard_hard_counts += h.sum(axis=1).astype(np.int64)
        dead = []
        pol = self.degrade
        for s, col in slot_cols:
            # per-slot (slot, layer) trip attribution, surfaced in the
            # per-request guard report (serving/metrics.py)
            self._trip_counts[s] += t[:, col].astype(np.int64)
            hcol = h[:, col]
            if not hcol.any():
                continue
            self._hard_counts[s, hcol > 0] += 1
            if pol is not None and pol.pin_after is not None:
                self._pinned[s] |= self._hard_counts[s] >= pol.pin_after
            if pol is not None and pol.fail_after is not None:
                self._fail_steps[s] += 1
                if self._fail_steps[s] >= pol.fail_after:
                    dead.append(s)
        return dead

    def _guard_err(self, s: int, phase: str) -> RequestError:
        layers_hit = np.nonzero(self._hard_counts[s])[0]
        return RequestError(
            reason=f"guard hard-fail during {phase}", phase=phase, slot=s,
            layer=int(layers_hit[0]) if layers_hit.size else None,
            retryable=False)

    def _note_first_token(self, r: Request, tok) -> None:
        if self.record_ttft:
            jax.block_until_ready(tok)
            self.ttft_s[self._req_index[id(r)]] = (
                time.perf_counter() - self._t0)

    def _guard_args(self, s: int):
        """(pin, frow) closure extras: batch-1 row ``s`` views."""
        if self.guard is None:
            return ()
        return (_host_snapshot(self._pinned[s:s + 1]),
                jnp.asarray(self._frow_host[s:s + 1]))

    def _guard_batch_args(self):
        if self.guard is None:
            return ()
        return (_host_snapshot(self._pinned), jnp.asarray(self._frow_host))

    def _admit(self, s: int, r: Request) -> None:
        ri = self._req_index[id(r)]
        self.status[ri] = "running"
        self._rk_slot[s] = self._rkeys[ri]
        self._lvl_slot[s] = self._levels[ri]
        self._reset_slot_guard(s)

    def _launch(self, program: str, decode: int = 0, idle: int = 0,
                prefill: int = 0, pad: int = 0) -> TraceAnnotation:
        """Count one launch of ``program`` and return its span. The rows
        come from the host arrays that staged the launch: slots decoded,
        decode rows of slots not decoding, prompt tokens carried and
        their padding. ``cache_inplace`` is 1 where the program, as
        traced, addressed every attention cache leaf in place by (layer,
        row), and 0 where it sliced a layer or a slot out and wrote it
        back (``transformer.cache_in_place``)."""
        c = self.counters
        c.launches += 1
        c.decode_rows += decode
        c.decode_idle_rows += idle
        c.prefill_rows += prefill
        c.prefill_pad_rows += pad
        inplace = int(self._inplace.get(program, False))
        c.cache_inplace_launches += inplace
        c.cache_slice_launches += 1 - inplace
        return TraceAnnotation(f"engine.launch.{program}",
                               rows=decode + prefill, pad_rows=idle + pad,
                               decode_rows=decode, prefill_rows=prefill,
                               cache_inplace=inplace)

    def _note_moe(self, out: tuple) -> None:
        """Keep a launch's expert rows (its last output) for the drain."""
        if self._moe_stats:
            self._pend_moe.append(out[-1])

    def _fill_slots(self) -> None:
        with TraceAnnotation("engine.fill"):
            for s in range(self.max_slots):
                while self._slots[s] is None and self._queue:
                    r = self._queue.pop(0)
                    self._admit(s, r)
                    if self.chunk_size > 0:
                        # chunked admit costs nothing here: the prompt
                        # streams through the main loop one chunk per step,
                        # interleaved with the other slots' decode steps
                        self._slots[s] = r
                        self._offsets[s] = 0
                        self._counts[s] = 0
                        self._decoding[s] = False
                        continue
                    self._prefill_whole(s, r)

    def _prefill_whole(self, s: int, r: Request) -> None:
        """Prefill ``r``'s whole prompt into free slot ``s`` (the
        ``chunk_size=0`` path), padded to its power-of-two bucket."""
        with TraceAnnotation("engine.stage"):
            prompt = np.asarray(r.prompt, np.int32)
            true_len = prompt.shape[0]
            bucket = (min(_pow2_bucket(true_len), self.max_len)
                      if self._bucketed else true_len)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :true_len] = prompt
            # per-slot isolation: a prefill failure (bad request reaching
            # the forward, guard plumbing, OOM on an oversized bucket)
            # fails *this* request, not the batch; the next occupant's
            # zero-reset re-initialises the slot
            self._slots[s] = r
            args = (self.params, self.caches, self.last_tok,
                    jnp.asarray(padded), true_len, s,
                    float(r.temperature), self._next_key(),
                    _host_snapshot(self._rk_slot[s]),
                    np.int32(self._lvl_slot[s]), self._dstate(),
                    *self._guard_args(s))
        self._build("prefill", args, variant=bucket)
        with self._launch("prefill", prefill=true_len,
                          pad=bucket - true_len):
            try:
                out = self._prefill(*args)
            except Exception as e:     # noqa: BLE001
                self._fail_request(s, RequestError(
                    reason=f"prefill failed: {e!r}", phase="prefill",
                    slot=s))
                return
        self.caches, self.last_tok, tok = out[:3]
        self._note_moe(out)
        self._slots[s] = None
        if self.guard is not None:
            dead = self._note_guard(out[3], out[4], [(s, 0)])
            if dead:
                self._slots[s] = r
                self._fail_request(s, self._guard_err(s, "prefill"))
                return
        ri = self._req_index[id(r)]
        self._pend.append(("p", tok, ri))
        self._note_first_token(r, tok)
        self._slots[s] = r
        if r.max_new_tokens > 1:
            self._counts[s] = 1
            self._decoding[s] = True
        else:
            self._finish_request(s)

    def _prefill_chunks(self) -> bool:
        """One chunk of progress for every still-prefilling slot;
        returns True if any slot finished its prompt."""
        guard_on = self.guard is not None
        finished = False
        for s, r in enumerate(self._slots):
            if r is None or self._decoding[s]:
                continue
            with TraceAnnotation("engine.stage"):
                prompt = np.asarray(r.prompt, np.int32)
                off = self._offsets[s]
                valid = min(self.chunk_size, prompt.shape[0] - off)
                chunk = np.zeros((1, self.chunk_size), np.int32)
                chunk[0, :valid] = prompt[off:off + valid]
                is_final = off + valid >= prompt.shape[0]
                args = (self.params, self.caches, self.last_tok,
                        jnp.asarray(chunk), jnp.asarray(off == 0),
                        jnp.asarray(valid, jnp.int32),
                        jnp.asarray(is_final), s, float(r.temperature),
                        self._next_key(), _host_snapshot(self._rk_slot[s]),
                        np.int32(self._lvl_slot[s]), self._dstate(),
                        *self._guard_args(s))
            self._build("prefill_chunk", args)
            with self._launch("prefill_chunk", prefill=int(valid),
                              pad=self.chunk_size - int(valid)):
                try:
                    out = self._prefill_chunk(*args)
                except Exception as e:     # noqa: BLE001
                    self._fail_request(s, RequestError(
                        reason=f"prefill chunk failed: {e!r}",
                        phase="prefill", slot=s))
                    finished = True        # slot freed -> refill
                    continue
            self.caches, self.last_tok, tok = out[:3]
            self._note_moe(out)
            if guard_on:
                dead = self._note_guard(out[3], out[4], [(s, 0)])
                if dead:
                    self._fail_request(s, self._guard_err(s, "prefill"))
                    finished = True
                    continue
            self._offsets[s] = off + valid
            if is_final:
                self._pend.append(("p", tok, self._req_index[id(r)]))
                self._note_first_token(r, tok)
                if r.max_new_tokens > 1:
                    self._decoding[s] = True
                    self._counts[s] = 1
                else:
                    self._finish_request(s)
                finished = True
        return finished

    def _slot_state(self):
        act = np.array([r is not None and self._decoding[s]
                        for s, r in enumerate(self._slots)])
        tmp = np.array([float(r.temperature) if r is not None else 0.0
                        for r in self._slots], np.float32)
        return act, jnp.asarray(act), jnp.asarray(tmp)

    def _isolate_decode(self, act_host, temps, step_key, tok_idx):
        """Assign per-slot blame for a failed batch decode (DESIGN.md §16).

        The batch decode program is all-or-nothing: when it raises there is
        no per-row error to read. Re-run the SAME compiled program once per
        active slot under a solo active mask (the mask is a traced argument
        — no recompile) and the SAME step key: each surviving row advances
        exactly one token. In off mode the survivors' tokens are
        bit-identical to what the batch step would have produced (per-row
        logits are batch-invariant and the sampling key depends only on
        (request id, token index)); in sim mode they are statistically
        equivalent (the batch-global activation scale sees the already-
        advanced rows). Slots whose solo probe still raises are returned
        for the caller to fail with a retryable decode RequestError.
        Best-effort by construction: if the original failure consumed the
        donated cache buffer, the probes fail too and every active request
        is failed rather than the engine wedging or the batch dying.
        """
        guard_on = self.guard is not None
        toks = self.last_tok
        dead: List[Tuple[int, Exception]] = []
        for s in range(self.max_slots):
            if not act_host[s]:
                continue
            solo = np.zeros(self.max_slots, bool)
            solo[s] = True
            try:
                with self._launch("decode", decode=1,
                                  idle=self.max_slots - 1):
                    out = self._decode(
                        self.params, self.caches, toks, jnp.asarray(solo),
                        temps, step_key, _host_snapshot(self._rk_slot),
                        jnp.asarray(tok_idx),
                        _host_snapshot(self._lvl_slot), self._dstate(),
                        *self._guard_batch_args())
                self.caches, toks = out[:2]
                self._note_moe(out)
                if guard_on:
                    self._note_guard(out[2], out[3], [(s, s)])
            except Exception as e:         # noqa: BLE001
                dead.append((s, e))
        self.last_tok = toks
        return toks, dead

    def _percall_iteration(self) -> None:
        """The legacy multi-launch iteration body: per-slot chunk advances,
        then one batch decode — now with per-slot decode failure isolation
        (the fused path recovers it by falling back here)."""
        guard_on = self.guard is not None
        if self._prefill_chunks():
            # a slot finished prefilling (or freed at max_new==1): admit
            # the next request into the free slot; the membership read
            # below puts a finished slot in this iteration's decode step
            self._fill_slots()
        with TraceAnnotation("engine.stage"):
            act_host, active, temps = self._slot_state()
            n_dec = int(act_host.sum())
            if n_dec:
                tok_idx = np.array(self._counts, np.int32)
                step_key = self._next_key()
                args = (self.params, self.caches, self.last_tok, active,
                        temps, step_key, _host_snapshot(self._rk_slot),
                        jnp.asarray(tok_idx), _host_snapshot(self._lvl_slot),
                        self._dstate(), *self._guard_batch_args())
        if not n_dec:
            if self._turnover:
                self._fill_slots()
            return
        dead_errs: Dict[int, RequestError] = {}
        gdead: List[int] = []
        failed = False
        self._build("decode", args)
        with self._launch("decode", decode=n_dec,
                          idle=self.max_slots - n_dec):
            try:
                out = self._decode(*args)
                self.caches, toks = out[:2]
                self._note_moe(out)
                if guard_on:
                    gdead = self._note_guard(
                        out[2], out[3],
                        [(s, s) for s in range(self.max_slots)
                         if act_host[s]])
                self.last_tok = toks
            except Exception:              # noqa: BLE001
                failed = True
        if failed:
            toks, probed = self._isolate_decode(act_host, temps, step_key,
                                               tok_idx)
            for s, e in probed:
                dead_errs[s] = RequestError(
                    reason=f"decode step failed: {e!r}", phase="decode",
                    slot=s)
        self._pend.append(
            ("d", toks,
             [self._req_index[id(r)]
              if act_host[s] and s not in dead_errs else None
              for s, r in enumerate(self._slots)]))
        for s in range(self.max_slots):
            r = self._slots[s]
            if r is None or not act_host[s]:
                continue
            if s in dead_errs:
                self._fail_request(s, dead_errs[s])
                self._turnover = True
                continue
            if s in gdead:
                self._fail_request(s, self._guard_err(s, "decode"))
                self._turnover = True
                continue
            self._counts[s] += 1
            if self._counts[s] >= r.max_new_tokens:
                self._finish_request(s)
        if self._turnover:
            self._fill_slots()

    def _fused_iteration(self) -> bool:
        """One whole scheduler iteration through the single-launch
        ``_step`` program (DESIGN.md §15): every still-prefilling slot
        advances by one chunk AND the batch decode runs, in one jitted
        dispatch. Token streams (and the PRNG draw order) are identical
        to the per-call path. Returns False to route the iteration to
        the per-call body instead: permanently if the step raises (the
        fallback recovers per-slot failure isolation; the first error is
        kept in ``fused_step_error`` and every fallback counts in
        ``counters.fused_fallbacks``), or just for this
        iteration when no slot is prefilling (pure decode is already a
        single ``_decode`` launch)."""
        if all(r is None or self._decoding[s]
               for s, r in enumerate(self._slots)):
            # pure-decode iteration: the per-call path is already a
            # single ``_decode`` launch, with no chunk tokens or flags to
            # stage — route it there (this is NOT the failure fallback;
            # the next mixed iteration fuses)
            return False
        n_slots = self.max_slots
        with TraceAnnotation("engine.stage"):
            chunk_toks = np.zeros((n_slots, 1, self.chunk_size), np.int32)
            resets = np.zeros(n_slots, bool)
            valids = np.zeros(n_slots, np.int32)
            finals = np.zeros(n_slots, bool)
            prefilling = np.zeros(n_slots, bool)
            act_after = np.zeros(n_slots, bool)
            tok_idx = np.zeros(n_slots, np.int32)
            for s, r in enumerate(self._slots):
                if r is None:
                    continue
                if self._decoding[s]:
                    act_after[s] = True
                    tok_idx[s] = self._counts[s]
                    continue
                prompt = np.asarray(r.prompt, np.int32)
                off = self._offsets[s]
                valid = min(self.chunk_size, prompt.shape[0] - off)
                chunk_toks[s, 0, :valid] = prompt[off:off + valid]
                resets[s] = off == 0
                valids[s] = valid
                # a slot finishing its prompt this iteration joins this
                # same iteration's decode (matching the per-call scheduler)
                finals[s] = off + valid >= prompt.shape[0]
                prefilling[s] = True
                if finals[s] and r.max_new_tokens > 1:
                    act_after[s] = True
                    tok_idx[s] = 1   # first decode token after the prefill
            do_decode = bool(act_after.any())
            temps_now = np.array(
                [float(r.temperature) if r is not None else 0.0
                 for r in self._slots], np.float32)
            # one packed (S, 7) transfer instead of seven small ones, and
            # one jitted key-chain dispatch instead of up to S+1
            # sequential splits + a stack — per-iteration host dispatch
            # used to exceed the cost of a chunk forward (see
            # draw_keys_fn). The key order (prefilling slots ascending,
            # then the decode) matches the per-call path, so both consume
            # the same PRNG stream.
            flags = np.stack(
                [resets.astype(np.int32), valids,
                 finals.astype(np.int32), prefilling.astype(np.int32),
                 act_after.astype(np.int32), tok_idx,
                 self._lvl_slot.astype(np.int32)], axis=1)
            key_mask = np.append(prefilling, do_decode)
            self.key, key_rows = self._draw_keys(self.key,
                                                 jnp.asarray(key_mask))
            meta_p = [self._req_index[id(self._slots[s])]
                      if prefilling[s] and finals[s] else None
                      for s in range(n_slots)]
            meta_d = [self._req_index[id(self._slots[s])] if act_after[s]
                      else None for s in range(n_slots)]
            args = (self.params, self.caches, self.last_tok,
                    jnp.asarray(chunk_toks), jnp.asarray(flags),
                    jnp.asarray(temps_now), key_rows,
                    _host_snapshot(self._rk_slot), self._dstate())
        n_dec = int(act_after.sum())
        n_valid = int(valids.sum())
        self._build("step", args)
        with self._launch(
                "step", decode=n_dec, idle=n_slots - n_dec if do_decode
                else 0, prefill=n_valid,
                pad=int(prefilling.sum()) * self.chunk_size - n_valid):
            try:
                out = self._step(*args)
            except Exception as e:         # noqa: BLE001
                self._fused_ok = False
                self.counters.fused_fallbacks += 1
                if self.fused_step_error is None:
                    self.fused_step_error = "".join(
                        traceback.format_exception_only(type(e), e)).strip()
                return False
        self.caches, toks, ptoks = out[:3]
        self._note_moe(out)
        self.last_tok = toks
        if any(m is not None for m in meta_p):
            self._pend.append(("d", ptoks, meta_p))
        for s in range(n_slots):
            if not prefilling[s]:
                continue
            self._offsets[s] += int(valids[s])
            if finals[s]:
                r = self._slots[s]
                self._note_first_token(r, ptoks)
                if r.max_new_tokens > 1:
                    self._decoding[s] = True
                    self._counts[s] = 1
                else:
                    self._finish_request(s)
        if do_decode:
            self._pend.append(("d", toks, meta_d))
            for s in range(n_slots):
                if meta_d[s] is None or self._slots[s] is None:
                    continue
                self._counts[s] += 1
                if self._counts[s] >= self._slots[s].max_new_tokens:
                    self._finish_request(s)
        return True

    # ------------------------------------------------------------- helpers
    def _validate(self, requests: List[Request]) -> None:
        _validate_requests(requests, self.max_len)

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def _build(self, name: str, args: tuple, variant: Any = None) -> None:
        """Trace and compile program ``name`` for ``args`` once per engine.

        Runs ahead of the call sites' failure isolation, so an error from
        tracing, lowering or compiling propagates to the caller instead of
        becoming a ``RequestError`` or the per-call fallback: a program the
        compiler refuses is a broken engine, not a bad request. The jit
        call that follows reuses the compiled executable. ``variant`` keys
        programs compiled per shape (the whole-prompt prefill buckets).
        """
        if (name, variant) in self._built:
            return
        self._programs[name].lower(*args).compile()
        self._built.add((name, variant))


class LoopEngine:
    """Frozen seed engine: per-slot batch-1 caches, one decode dispatch per
    slot per token, host sync per sampled token. Reference/baseline only —
    only the shared RequestError failure contract was retrofitted; the token
    math and PRNG draws of the healthy path are untouched.

    Known seed quirk (kept frozen): a request with ``max_new_tokens == 1``
    emits 2 tokens — the slot is occupied unconditionally after prefill and
    the limit is only checked after the first decode. The fused ``Engine``
    honors the limit exactly, so fused-vs-loop equality holds for
    ``max_new_tokens >= 2``."""

    def __init__(self, cfg: ModelConfig, params: Any, max_slots: int = 4,
                 max_len: int = 512, cim_mode: Optional[str] = None,
                 seed: int = 0, attn_impl: Optional[str] = None,
                 deploy: Optional[bool] = None, drift: Any = None,
                 calib: Any = None):
        if drift is not None or calib:
            raise ValueError(
                "LoopEngine has no drift/calibration path — temporal drift "
                "injection and background calibration are fused-Engine "
                "features (use Engine; DESIGN.md §17)")
        cfg = _apply_attn_impl(cfg, attn_impl)
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        mode = cim_mode if cim_mode is not None else cfg.cim.mode
        self.deployed = _resolve_deploy(deploy, mode)
        self.params = _maybe_deploy(cfg, params, self.deployed)
        self.request_errors: List[Optional[RequestError]] = []
        deployed = self.deployed

        def prefill_fn(params, batch, caches, key):
            ctx = Ctx.make(cfg, key, mode=mode, deployed=deployed)
            logits, caches = tf.forward(params, batch, cfg, ctx, caches)
            return logits[:, -1], caches

        def decode_fn(params, tokens, caches, key):
            ctx = Ctx.make(cfg, key, mode=mode, deployed=deployed)
            logits, caches = tf.forward(params, {"tokens": tokens}, cfg, ctx, caches)
            return logits[:, -1], caches

        # donate the (freshly allocated) prefill cache too: without it the
        # reference engine double-buffers every slot cache on prefill —
        # XLA must keep the zero-filled input alive while writing the
        # prefilled output — which skews the loop-vs-fused memory baseline
        self._prefill = jax.jit(prefill_fn, donate_argnums=(2,))
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))

    # ------------------------------------------------------------------ API
    def generate(self, requests: List[Request]) -> List[Any]:
        """Run all requests to completion; returns generated token lists.

        Shares the fused engine's failure contract: a per-slot prefill or
        decode exception yields a ``RequestError`` at that request's
        position (mirrored in ``self.request_errors``) and frees the slot;
        the loop engine's per-slot dispatch makes the decode isolation
        trivial — no probing needed."""
        _validate_requests(requests, self.max_len)
        cfg = self.cfg
        queue = list(requests)
        for r in queue:
            r.out_tokens = []
        results: List[Any] = [None] * len(requests)
        req_index = {id(r): i for i, r in enumerate(requests)}
        self.request_errors = [None] * len(requests)

        # one cache per slot (batch=1 caches, concatenated logically)
        slots: List[Optional[Request]] = [None] * self.max_slots
        caches = [tf.init_caches(cfg, 1, self.max_len) for _ in range(self.max_slots)]
        last_tok = [0] * self.max_slots
        steps = 0

        def fail(s: int, r: Request, phase: str, e: Exception) -> None:
            ri = req_index[id(r)]
            err = RequestError(reason=f"{phase} failed: {e!r}", phase=phase,
                               slot=s)
            self.request_errors[ri] = err
            results[ri] = err
            slots[s] = None

        def try_fill_slots():
            for s in range(self.max_slots):
                if slots[s] is None and queue:
                    r = queue.pop(0)
                    slots[s] = r
                    fresh = tf.init_caches(cfg, 1, self.max_len)
                    try:
                        logits, caches[s] = self._prefill(
                            self.params,
                            {"tokens": jnp.asarray(r.prompt)[None]},
                            fresh, self._next_key())
                    except Exception as e:     # noqa: BLE001
                        fail(s, r, "prefill", e)
                        continue
                    last_tok[s] = self._sample(logits[0], r.temperature)
                    r.out_tokens.append(int(last_tok[s]))

        try_fill_slots()
        while any(s is not None for s in slots):
            # ragged per-slot decode loop — the dispatch pattern the fused
            # Engine replaces with one batch-axis program
            for s in range(self.max_slots):
                r = slots[s]
                if r is None:
                    continue
                try:
                    logits, caches[s] = self._decode(
                        self.params, jnp.asarray([[last_tok[s]]], jnp.int32),
                        caches[s], self._next_key())
                except Exception as e:         # noqa: BLE001
                    fail(s, r, "decode", e)
                    continue
                tok = self._sample(logits[0], r.temperature)
                r.out_tokens.append(int(tok))
                last_tok[s] = tok
                if len(r.out_tokens) >= r.max_new_tokens:
                    results[req_index[id(r)]] = r.out_tokens
                    slots[s] = None
            try_fill_slots()
            steps += 1
            if steps > 10_000:
                raise RuntimeError("serving engine ran away")
        return results

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def _build(self, name: str, args: tuple, variant: Any = None) -> None:
        """Trace and compile program ``name`` for ``args`` once per engine.

        Runs ahead of the call sites' failure isolation, so an error from
        tracing, lowering or compiling propagates to the caller instead of
        becoming a ``RequestError`` or the per-call fallback: a program the
        compiler refuses is a broken engine, not a bad request. The jit
        call that follows reuses the compiled executable. ``variant`` keys
        programs compiled per shape (the whole-prompt prefill buckets).
        """
        if (name, variant) in self._built:
            return
        self._programs[name].lower(*args).compile()
        self._built.add((name, variant))

    def _sample(self, logits: jnp.ndarray, temperature: float) -> int:
        if temperature <= 0:
            return int(jnp.argmax(logits))
        self.key, k = jax.random.split(self.key)
        return int(jax.random.categorical(k, logits / temperature))
