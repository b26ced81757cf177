"""Building blocks: CIM-aware dense, norms, RoPE, SwiGLU, embeddings.

Parameters are plain pytrees; every init returns ``(params, axes)`` where
``axes`` mirrors the params tree with logical-axis-name tuples used by the
sharding rules. Every matmul goes through ``dense()`` which carries a *role*
(attn_qkv / mlp_in / ...) so the SAC policy can pick the macro operating
point per layer — the paper's software-analog co-design as a first-class
framework feature.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.core.cim import CIMSpec, cim_dense, vote_drop_extra_std_int
from repro.core.sac import Policy, get_policy
from repro.distributed.sharding import shard

Params = Dict[str, Any]


@dataclasses.dataclass
class Ctx:
    """Per-apply execution context: CIM mode, SAC policy, RNG stream.

    ``deployed`` asserts the params tree carries pre-quantized weight planes
    (``core.deploy.deploy``): sim-mode ``dense`` then *requires* a plane for
    every CIM-routed role instead of silently falling back to per-call
    weight quantization — a missing plane is a deploy/policy mismatch, not a
    slow path.

    Robustness fields (DESIGN.md §14): ``guard`` routes every deployed
    CIM dense with a ``wc<bits>`` checksum plane through
    ``core.guard.guarded_dense``; ``fault`` threads a runtime
    ``core.faults.FaultSpec`` into each layer's CIMSpec (stuck-at planes
    act earlier, at deploy time). ``fault_rows`` / ``pin_rows`` are (B,)
    bool batch-row masks (transient-disturbance targets / engine-pinned
    digital rows); ``trip_log`` / ``hard_log`` are per-layer scratch lists
    the guard appends (B,) trip counts to — ``transformer._scan_blocks``
    drains them into the (L, B) ``guard_trips`` / ``guard_hard`` outputs.
    """

    cfg: ModelConfig
    mode: str = "off"                 # off | qat | sim
    policy: Optional[Policy] = None
    key: Optional[jax.Array] = None
    counter: int = 0
    deployed: bool = False
    guard: Optional[Any] = None       # core.guard.GuardSpec
    fault: Optional[Any] = None       # core.faults.FaultSpec (runtime part)
    drift: Optional[Any] = None       # core.drift.DriftSpec (DESIGN.md §17)
    drift_state: Optional[Any] = None  # (step, trim_gain, trim_off) traced
    # pytree — the drift evaluation time + current calibration trims; threaded
    # per call so advancing time/trims never retraces the jitted closures
    fault_rows: Optional[jnp.ndarray] = None   # (B,) bool
    pin_rows: Optional[jnp.ndarray] = None     # (B,) bool, set per layer
    pin_layers: Optional[jnp.ndarray] = None   # (B, L) bool
    trip_log: Optional[list] = None
    hard_log: Optional[list] = None
    guard_trips: Optional[jnp.ndarray] = None  # (L, B) int32, set by scan
    guard_hard: Optional[jnp.ndarray] = None   # (L, B) int32
    prefill_valid: Optional[jnp.ndarray] = None  # (B,) int32 valid tokens in
    # this prefill call (rest of the fixed-shape chunk is pad) — consumed by
    # state-carrying blocks (ssm conv/SSD) that cannot mask pads via an
    # attention length the way cached attention does
    degrade_levels: tuple = ()            # static ladder: vote count per level
    # (index 0 is None = full votes); mirrors sac.DegradeLadder.votes. Sim
    # mode adds the per-row analytically-equivalent extra output noise of the
    # reduced vote count (core.cim.vote_drop_extra_std_int, DESIGN.md §16)
    degrade_rows: Optional[jnp.ndarray] = None  # (B,) int32 ladder level/row
    moe_log: Optional[list] = None      # per-layer scratch: held_experts
    # appends (routed rows per held expert, rows its grouped matmul
    # computed, held experts with rows); transformer._scan_blocks sums it
    # over layers into moe_rows
    moe_rows: Optional[Tuple[jnp.ndarray, ...]] = None
    # set at trace time where a cached forward sliced a layer out of the
    # stacked cache and wrote it back, instead of addressing the stack in
    # place by (layer, row) (transformer._scan_blocks)
    cache_sliced: bool = False

    @classmethod
    def make(cls, cfg: ModelConfig, key: Optional[jax.Array] = None,
             mode: Optional[str] = None, deployed: bool = False,
             guard: Optional[Any] = None,
             fault: Optional[Any] = None) -> "Ctx":
        mode = cfg.cim.mode if mode is None else mode
        policy = get_policy(cfg.cim.policy) if mode != "off" else None
        return cls(cfg=cfg, mode=mode, policy=policy, key=key,
                   deployed=deployed, guard=guard, fault=fault)

    def next_key(self) -> Optional[jax.Array]:
        if self.key is None:
            return None
        self.counter += 1
        return jax.random.fold_in(self.key, self.counter)

    def spec_for(self, role: str) -> Optional[CIMSpec]:
        if self.mode == "off" or self.policy is None:
            return None
        return self.policy.spec_for_role(role)


def _init_dense(key, d_in: int, d_out: int, axes: Tuple[str, str],
                bias: bool = False, dtype=jnp.float32, scale: float = 1.0):
    w = jax.random.normal(key, (d_in, d_out), dtype) * (scale / jnp.sqrt(d_in))
    p: Params = {"w": w}
    a: Params = {"w": axes}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
        a["b"] = (axes[1],)
    return p, a


def dense(ctx: Ctx, p: Params, x: jnp.ndarray, role: str) -> jnp.ndarray:
    """y = x @ w (+ b), executed per the CIM context and SAC role.

    Sim mode with a deployed weight plane (``p["wq"]``/``p["ws"]``, see
    ``core.deploy``) skips the per-call weight abs-max/quantize entirely —
    only the activation side is quantized per call; the result is
    bit-identical to the on-the-fly path. ``cfg.cim.use_kernel`` further
    routes the deployed matmul through the fused-activation-quant Pallas
    path (``kernels.ops.cim_matmul_deployed`` — in-kernel xq, int8 weight
    stream, threefry readout noise) instead of the jnp behavioural model.
    """
    spec = ctx.spec_for(role)
    if spec is None:
        y = jnp.einsum("...k,kn->...n", x, p["w"].astype(x.dtype))
    else:
        # thread the runtime fault model into the operating point (static:
        # FaultSpec is frozen/hashable, so jit sees one spec per config)
        if ctx.fault is not None:
            spec = dataclasses.replace(spec, fault=ctx.fault)
        # temporal drift rides the same way (DriftSpec is frozen/hashable);
        # the evaluation step + trims travel as the traced ``dstate`` pytree
        dstate = None
        if ctx.drift is not None and ctx.mode == "sim":
            spec = dataclasses.replace(spec, drift=ctx.drift)
            dstate = ctx.drift_state
        k = ctx.next_key()
        xs = _act_scale(ctx, x, spec)
        if (ctx.guard is not None and ctx.mode == "sim"
                and f"wc{spec.w_bits}" in p):
            from repro.core.guard import guarded_dense
            y = guarded_dense(ctx, p, x, spec, k, xs)
            if "b" in p:
                y = y + p["b"].astype(x.dtype)
            return y
        # the plane key carries the deployed w_bits, so a tree deployed
        # under a different policy can never be consumed at the wrong
        # bit-width — the lookup just misses
        wq = p.get(f"wq{spec.w_bits}") if ctx.mode == "sim" else None
        if ctx.deployed and ctx.mode == "sim" and wq is None:
            raise ValueError(
                f"deployed sim-mode dense has no pre-quantized weight plane "
                f"for role '{role}' at w_bits={spec.w_bits} — run "
                "core.deploy.deploy() with the same SAC policy the serving "
                "context resolves")
        if wq is not None and ctx.cfg.cim.use_kernel:
            from repro.kernels import ops as kops
            y = kops.cim_matmul_deployed(x, wq, p[f"ws{spec.w_bits}"], spec,
                                         k, x_scale=xs,
                                         dstate=dstate).astype(x.dtype)
        elif wq is not None:
            y = cim_dense(x, None, spec, k, mode="sim", x_scale=xs,
                          w_scale=p[f"ws{spec.w_bits}"], wq=wq,
                          dstate=dstate)
        else:
            y = cim_dense(x, p["w"].astype(x.dtype), spec, k, mode=ctx.mode,
                          x_scale=xs, dstate=dstate)
        y = _degrade_noise(ctx, p, x, y, spec, k, xs)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def _degrade_noise(ctx: Ctx, p: Params, x: jnp.ndarray, y: jnp.ndarray,
                   spec: CIMSpec, k: Optional[jax.Array], xs):
    """Per-row degraded-vote noise for the overload ladder (DESIGN.md §16).

    Rows admitted above ladder level 0 run their CB majority votes at the
    level's reduced count; behaviourally that is extra output-referred
    Gaussian noise with the analytically-derived sigma
    (``core.cim.vote_drop_extra_std_int``), scaled from integer product
    units to output units by the dequant scales exactly like the QAT noise
    path. The noise key is folded off the layer key (``0xD364``) so the
    main readout-noise stream is bit-identical with and without a ladder,
    and level-0 rows are selected via ``where`` (not ``+0.0``) so they stay
    bit-for-bit identical to a ladder-free engine.

    Sim mode only: in off mode the ladder is pure admission bookkeeping
    (there is no analog noise to degrade), which is also what makes off-mode
    retry streams reproducible across ladder levels.
    """
    if (ctx.degrade_rows is None or not ctx.degrade_levels
            or ctx.mode != "sim" or k is None):
        return y
    kdim = x.shape[-1]
    table = [vote_drop_extra_std_int(spec, kdim, v)
             for v in ctx.degrade_levels]
    if not any(s > 0.0 for s in table):
        return y
    ws = p.get(f"ws{spec.w_bits}")
    if ws is None:
        ws = quant.abs_max_scale(p["w"].astype(jnp.float32), spec.w_bits)
    if xs is None:
        xs = quant.abs_max_scale(x.astype(jnp.float32), spec.in_bits)
    sig = jnp.take(jnp.asarray(table, jnp.float32), ctx.degrade_rows)
    sig = sig.reshape(sig.shape + (1,) * (y.ndim - 1))
    noise = jax.random.normal(jax.random.fold_in(k, 0xD364), y.shape,
                              jnp.float32)
    return jnp.where(sig > 0.0,
                     (y.astype(jnp.float32) + sig * xs * ws * noise)
                     .astype(y.dtype),
                     y)


def _act_scale(ctx: Ctx, x: jnp.ndarray, spec: CIMSpec):
    """Per-layer Vref fit: clip activations at k*rms instead of abs-max."""
    k = ctx.cfg.cim.act_clip_sigmas
    if k <= 0:
        return None
    rms = jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)))) + 1e-8
    return k * rms / quant.qmax(spec.in_bits)


# ----------------------------------------------------------------- norms

def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"g": jnp.ones((d,), dtype)}, {"g": ("embed",)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * p["g"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.float32):
    return (
        {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
        {"g": ("embed",), "b": ("embed",)},
    )


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"] + p["b"]).astype(x.dtype)


# ------------------------------------------------------------------ RoPE

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1·mscale·ln(factor) + 1`` (1 unscaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_ramp(head_dim: int, theta: float, sc) -> np.ndarray:
    """Per frequency pair, 1 where YaRN keeps the original frequency
    (fast rotations) falling to 0 where it interpolates (slow ones)."""
    def corr(rot):
        return (head_dim * math.log(sc.original_max_position_embeddings
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(sc.beta_fast)), 0)
    high = min(math.ceil(corr(sc.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(head_dim // 2, dtype=np.float32)
    return 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies of the ``head_dim // 2`` rotary pairs; with a
    ``RopeScaling`` the YaRN blend of the original and the
    ``factor``-interpolated frequencies."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    keep = jnp.asarray(_yarn_ramp(head_dim, theta, scaling))
    return freqs / scaling.factor * (1.0 - keep) + freqs * keep


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,). ``scaling``: an optional
    ``RopeScaling`` (YaRN frequencies and cos/sin factor)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)             # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    if cos.ndim == 2:                                  # (S, D/2) -> broadcast B
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]  # (B, S, 1, D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ MLP

def init_swiglu(key, d: int, f: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    p1, a1 = _init_dense(k1, d, f, ("embed", "mlp"), dtype=dtype)
    p2, a2 = _init_dense(k2, d, f, ("embed", "mlp"), dtype=dtype)
    p3, a3 = _init_dense(k3, f, d, ("mlp", "embed"), dtype=dtype)
    return {"gate": p1, "up": p2, "down": p3}, {"gate": a1, "up": a2, "down": a3}


def swiglu(ctx: Ctx, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = dense(ctx, p["gate"], x, "mlp_in")
    u = dense(ctx, p["up"], x, "mlp_in")
    h = jax.nn.silu(g) * u
    h = shard(h, "batch", "seq", "mlp")
    return dense(ctx, p["down"], h, "mlp_out")


def init_gelu_mlp(key, d: int, f: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    p1, a1 = _init_dense(k1, d, f, ("embed", "mlp"), bias=True, dtype=dtype)
    p2, a2 = _init_dense(k2, f, d, ("mlp", "embed"), bias=True, dtype=dtype)
    return {"up": p1, "down": p2}, {"up": a1, "down": a2}


def gelu_mlp(ctx: Ctx, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.gelu(dense(ctx, p["up"], x, "mlp_in"))
    h = shard(h, "batch", "seq", "mlp")
    return dense(ctx, p["down"], h, "mlp_out")


# ------------------------------------------------------------- embeddings

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32):
    e = jax.random.normal(key, (vocab, d), dtype) * 0.02
    return {"e": e}, {"e": ("vocab", "embed")}


def embed(p: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    return p["e"].astype(dtype)[tokens]


def unembed(ctx: Ctx, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Logits head (digital per SAC: role 'head' maps to None)."""
    return jnp.einsum("...d,vd->...v", x, p["e"].astype(x.dtype))


def sinusoidal_positions(pos, d: int) -> jnp.ndarray:
    """pos: int or (S,) array of positions -> (S, d) embeddings."""
    if isinstance(pos, int):
        pos = jnp.arange(pos)
    pos = pos.astype(jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
