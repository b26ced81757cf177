"""Pure-jnp oracles for the Pallas kernels (tests assert allclose vs these),
plus the pre-batching reference implementations of the SAR engine.

Three families live here:

  * ``cim_matmul_*_ref`` — same-construction oracles for the Pallas
    behavioural kernel. ``cim_matmul_prng_ref`` reproduces the kernel's
    in-kernel Threefry noise bit-for-bit (same (seed, tile, row, col)
    counter contract, see ``repro.core.prng``); it is also the CPU fallback
    path of ``ops.cim_matmul``.
  * ``sar_convert_votes_ref`` / ``cim_matmul_bit_exact_loop`` — the original
    materialised-vote SAR model and per-(tile, plane) conversion loop. They
    define the distribution the fast analytic engine must match
    (tests/test_adc.py checks both the end-to-end code statistics and the
    per-decision probabilities against ``adc.decision_prob``/
    ``majority_prob``) and serve as the baseline in
    benchmarks/kernel_bench.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.adc import ADCSpec, dac_bit_weights
from repro.core.prng import tile_gaussian


def _dnl_shift_frozen(v: jnp.ndarray, spec: ADCSpec) -> jnp.ndarray:
    """Pre-PR static per-code threshold scatter, inlined so the frozen
    baselines below cannot drift if adc.py's live copy ever changes."""
    if spec.sigma_dnl <= 0.0:
        return v
    table = spec.sigma_dnl * jax.random.normal(
        jax.random.PRNGKey(spec.mismatch_seed + 1), (spec.codes,)
    )
    idx = jnp.clip(jnp.floor(v).astype(jnp.int32), 0, spec.codes - 1)
    return v + table[idx]


# ---------------------------------------------------------------------------
# behavioural matmul oracles
# ---------------------------------------------------------------------------


def cim_matmul_ref(
    xq: jnp.ndarray,
    wq: jnp.ndarray,
    noise: jnp.ndarray | None,
    sigma: float,
    macro_rows: int = 1024,
) -> jnp.ndarray:
    """K-tiled CIM matmul with explicit per-tile additive readout error.

    Args:
      xq:    (M, K) int8/int32 quantized activations.
      wq:    (K, N) int8/int32 quantized weights.
      noise: (T, M, N) float32 unit-variance readout noise per K-tile
             (T = ceil(K / macro_rows)), or None for the noiseless path.
      sigma: output-referred error std per K-tile, integer product units
             (from ``repro.core.cim.output_noise_std_int_per_tile``).

    Returns:
      (M, N) float32 macro estimate of xq @ wq.
    """
    m, k = xq.shape
    _, n = wq.shape
    t = -(-k // macro_rows)
    kp = t * macro_rows
    xp = jnp.pad(xq.astype(jnp.int32), ((0, 0), (0, kp - k)))
    wp = jnp.pad(wq.astype(jnp.int32), ((0, kp - k), (0, 0)))
    y = jnp.zeros((m, n), jnp.float32)
    for ti in range(t):
        xs = xp[:, ti * macro_rows : (ti + 1) * macro_rows]
        ws = wp[ti * macro_rows : (ti + 1) * macro_rows, :]
        s = jnp.dot(xs, ws, preferred_element_type=jnp.int32).astype(jnp.float32)
        if noise is not None:
            s = s + sigma * noise[ti]
        y = y + s
    return y


def cim_matmul_prng_ref(
    xq: jnp.ndarray,
    wq: jnp.ndarray,
    seed: jnp.ndarray | int | None,
    sigma: float,
    macro_rows: int = 1024,
    scale: jnp.ndarray | float | None = None,
) -> jnp.ndarray:
    """Same-construction oracle for the in-kernel-PRNG Pallas matmul.

    Mirrors ``cim_matmul_pallas`` operation for operation: per K-tile, the
    exact int32 dot plus ``sigma`` times the Threefry/Box-Muller noise keyed
    on (seed, tile) and countered by the *global* (row, col); f32 tile
    accumulation in the same order; scalar ``scale`` epilogue. Because the
    noise contract never references block sizes, this oracle needs no
    knowledge of bm/bn — agreement with any blocking is part of the test.
    """
    m, k = xq.shape
    _, n = wq.shape
    t = -(-k // macro_rows)
    kp = t * macro_rows
    xp = jnp.pad(xq.astype(jnp.int32), ((0, 0), (0, kp - k)))
    wp = jnp.pad(wq.astype(jnp.int32), ((0, kp - k), (0, 0)))

    use_noise = seed is not None and sigma > 0.0
    if use_noise:
        sv = jnp.asarray(seed, jnp.int32).reshape(-1).astype(jnp.uint32)
        s0 = sv[0]
        s1 = sv[1] if sv.shape[0] > 1 else jnp.uint32(0)
        zeros = jnp.zeros((m, n), jnp.uint32)
        r_ids = jnp.arange(m, dtype=jnp.uint32)[:, None] + zeros
        c_ids = jnp.arange(n, dtype=jnp.uint32)[None, :] + zeros

    y = jnp.zeros((m, n), jnp.float32)
    for ti in range(t):
        xs = xp[:, ti * macro_rows : (ti + 1) * macro_rows]
        ws = wp[ti * macro_rows : (ti + 1) * macro_rows, :]
        s = jnp.dot(xs, ws, preferred_element_type=jnp.int32).astype(jnp.float32)
        if use_noise:
            s = s + sigma * tile_gaussian(s0, s1, jnp.uint32(ti), r_ids, c_ids)
        y = y + s
    if scale is not None:
        y = y * jnp.asarray(scale, jnp.float32).reshape(-1)[0]
    return y


def quantize_ref(x: jnp.ndarray, scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Symmetric quantization oracle (matches kernels.ops fused quant)."""
    q = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / scale), -q, q).astype(jnp.int8)


def cim_matmul_fused_ref(
    x: jnp.ndarray,
    wq: jnp.ndarray,
    x_scale: jnp.ndarray | float,
    seed: jnp.ndarray | int | None,
    sigma: float,
    macro_rows: int = 1024,
    scale: jnp.ndarray | float | None = None,
    in_bits: int = 6,
) -> jnp.ndarray:
    """Bit-exact oracle for ``cim_matmul_fused_pallas`` (fused act quant).

    The kernel's prologue quantization is the same elementwise
    round/clip chain applied here up front (``quantize_ref`` against the
    scalar ``x_scale``), so fused-kernel == quantize-then-``prng_ref`` holds
    value for value; the noise contract is unchanged (global (row, col)
    counters — blocking-invariant).
    """
    xs = jnp.asarray(x_scale, jnp.float32).reshape(())
    xq = quantize_ref(x.astype(jnp.float32), xs, in_bits).astype(jnp.int32)
    return cim_matmul_prng_ref(xq, wq, seed, sigma, macro_rows, scale)


# ---------------------------------------------------------------------------
# SAR references
# ---------------------------------------------------------------------------


def sar_convert_votes_ref(
    v: jnp.ndarray, key: jax.Array, spec: ADCSpec, cb: bool
) -> jnp.ndarray:
    """Original materialised-vote SAR model (pre-PR implementation, verbatim).

    Draws every comparator vote explicitly — ``(votes,) + v.shape`` Gaussian
    + glitch samples per fine decision — and majority-votes the signs. The
    analytic engine must match this distribution (not stream); kept as the
    ground-truth model and as the benchmark baseline.
    """
    w = dac_bit_weights(spec)
    vshape = v.shape
    v = _dnl_shift_frozen(v.reshape(-1), spec)

    def decide(level, subkey, votes, sigma, fine):
        k1, k2, k3 = jax.random.split(subkey, 3)
        noise = sigma * jax.random.normal(k1, (votes,) + v.shape)
        if fine:
            glitch = jax.random.uniform(k2, (votes,) + v.shape) < spec.p_glitch
            kick = jax.random.uniform(
                k3, (votes,) + v.shape,
                minval=-spec.glitch_mag, maxval=spec.glitch_mag,
            )
            noise = noise + glitch * kick
        ups = jnp.sum((v[None] - level[None] + noise) > 0.0, axis=0)
        return ups * 2 > votes  # strict majority (>=4 of 6, >0 of 1)

    code = jnp.zeros_like(v, dtype=jnp.int32)
    level = jnp.zeros_like(v)
    for step, b in enumerate(range(spec.adc_bits - 1, -1, -1)):
        fine = b < spec.mv_bits
        votes = spec.mv_votes if (cb and fine) else 1
        sigma = spec.sigma_cmp if fine else spec.coarse_frac * spec.sigma_cmp
        trial_level = level + w[b]
        bit = decide(trial_level, jax.random.fold_in(key, step), votes, sigma, fine)
        code = code + bit.astype(jnp.int32) * (1 << b)
        level = jnp.where(bit, trial_level, level)
    return code.reshape(vshape)


def cim_matmul_bit_exact_loop(
    xq: jnp.ndarray, wq: jnp.ndarray, key: jax.Array, spec
) -> jnp.ndarray:
    """Original per-(K-tile, plane) conversion loop (pre-PR engine, verbatim).

    ``T * w_bits`` sequential ``sar_convert_votes_ref`` conversions. Slow to
    trace and to run — exists to validate the batched engine statistically
    and to anchor the kernel_bench speedup numbers.
    """
    from repro.core import quant

    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2
    rows = spec.macro_rows
    t = -(-k // rows)
    kp = t * rows
    xq = jnp.pad(xq, ((0, 0), (0, kp - k)))
    wq = jnp.pad(wq, ((0, kp - k), (0, 0)))

    qx = quant.qmax(spec.in_bits)
    adc = spec.effective_adc()
    half = 2.0 ** (spec.adc_bits - 1)
    gain = spec.analog_gain(rows=k)
    pw = quant.plane_weights(spec.w_bits)
    wplanes = quant.unsigned_bitplanes(wq, spec.w_bits)

    x_drive = xq.astype(jnp.float32) / qx

    y = jnp.zeros((m, n), jnp.float32)
    for ti in range(t):
        xs = jax.lax.dynamic_slice_in_dim(x_drive, ti * rows, rows, axis=1)
        for j in range(spec.w_bits):
            ws = jax.lax.dynamic_slice_in_dim(wplanes[j], ti * rows, rows, axis=0)
            s = xs @ ws.astype(jnp.float32)
            v = gain * spec.attenuation * s + half
            v = jnp.clip(v, 0.0, 2.0 ** spec.adc_bits - 1.0)
            code = sar_convert_votes_ref(
                v, jax.random.fold_in(key, ti * spec.w_bits + j), adc, spec.cb
            )
            s_hat = (code.astype(jnp.float32) - half) / (gain * spec.attenuation)
            y = y + pw[j].astype(jnp.float32) * s_hat * qx
    return y


def flash_attention_ref(q, k, v, causal: bool = True, start=None):
    """Plain softmax attention oracle for the flash kernel.

    q: (BH, S, D); k, v: (BH, T, D) -> (BH, S, D), f32 softmax.

    ``start: (BH,)`` gives per-row absolute offsets (``_cached_mask``
    semantics, prefill against a partially-filled slot cache): query i of
    row b sits at absolute position start[b]+i and may attend key j iff
    j <= start[b]+i (causal) and j < start[b]+S (slot validity — recycled
    slots keep stale keys beyond the row's length).
    """
    import jax
    sq, tk = q.shape[1], k.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bsd,btd->bst", q, k).astype(jnp.float32) * scale
    kj = jnp.arange(tk)[None, :]
    if start is not None:
        if not causal:
            raise ValueError("start offsets require causal attention")
        qi = jnp.arange(sq)[None, :, None] + start[:, None, None]  # (BH,S,1)
        mask = (kj[None] <= qi) & (kj[None] < (start[:, None, None] + sq))
        s = jnp.where(mask, s, -1e30)
    elif causal:
        qi = jnp.arange(sq)[:, None]
        s = jnp.where(kj <= qi, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bst,btd->bsd", p, v)


def decode_attention_ref(q, k, v, lens, ks=None, vs=None):
    """Ragged single-token GQA decode oracle for the Pallas decode kernel.

    q: (B, H, D); k, v: (B, T, KV·D) lane-dense slot cache; lens: (B,)
    valid-key counts (including the current token's freshly written key).
    ``ks``/``vs`` (B, T, KV, 1) dequantise an int8 cache. Rows with
    lens == 0 return exactly zero (matching the kernel's
    empty-accumulator output).
    """
    b, h, d = q.shape
    t, kv_heads = k.shape[1], k.shape[2] // d
    g = h // kv_heads
    kf = k.reshape(b, t, kv_heads, d).astype(jnp.float32)
    vf = v.reshape(b, t, kv_heads, d).astype(jnp.float32)
    if ks is not None:
        kf = kf * ks
        vf = vf * vs
    qr = q.reshape(b, kv_heads, g, d).astype(jnp.float32)
    logits = jnp.einsum("bkgd,btkd->bkgt", qr, kf) / jnp.sqrt(
        jnp.float32(d))
    valid = jnp.arange(t)[None, :] < lens[:, None]             # (B, T)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, vf)
    out = jnp.where(lens[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, h, d).astype(q.dtype)


def flash_gqa_ref(q, k, v, start=None, ks=None, vs=None):
    """GQA-native flash-prefill oracle (``kernels.flash_gqa_attention``).

    q: (B, S, H, D); k, v: (B, T, KV·D) lane-dense slot cache, optionally
    int8 with ``ks``/``vs`` (B, T, KV, 1) scales. ``start: (B,)`` gives the
    ``_cached_mask`` semantics — query i of row b sits at absolute
    position start[b]+i and may attend key j iff j <= start[b]+i (causal)
    and j < start[b]+S (freshly written prefix; recycled slots keep stale
    keys beyond the row's length and must never expose them).
    """
    b, s, h, d = q.shape
    t, kv_heads = k.shape[1], k.shape[2] // d
    g = h // kv_heads
    kf = k.reshape(b, t, kv_heads, d).astype(jnp.float32)
    vf = v.reshape(b, t, kv_heads, d).astype(jnp.float32)
    if ks is not None:
        kf = kf * ks
        vf = vf * vs
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    qr = q.reshape(b, s, kv_heads, g, d).astype(jnp.float32)
    logits = jnp.einsum("bskgd,btkd->bkgst", qr, kf) / jnp.sqrt(
        jnp.float32(d))
    qi = jnp.arange(s)[None, :, None] + start[:, None, None]     # (B, S, 1)
    kj = jnp.arange(t)[None, None, :]
    mask = (kj <= qi) & (kj < (start[:, None, None] + s))        # (B, S, T)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, vf)
    return out.reshape(b, s, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# fault-injection oracles (DESIGN.md §14)
# ---------------------------------------------------------------------------
#
# Every structural fault in ``core.faults`` is a deterministic function of
# (FaultSpec.seed, position). The oracles below reconstruct each realisation
# independently (different code shape, same draw contract) so a test failure
# means the *contract* drifted, not that two call sites share a bug.


def stuck_bit_plane_ref(wq: jnp.ndarray, bits: int, rate: float,
                        key: jax.Array) -> jnp.ndarray:
    """Independent reconstruction of ``core.faults.stuck_bit_plane``.

    Same draws (fold_in(key, bit) -> split -> two uniforms) but applied by
    masked clear/set on the unsigned view instead of plane reassembly.
    """
    if rate <= 0.0:
        return wq
    u = jnp.mod(wq.astype(jnp.int32), 2 ** bits)
    for i in range(bits):
        ki = jax.random.fold_in(key, i)
        km, kv = jax.random.split(ki)
        stuck = jax.random.uniform(km, wq.shape) < rate
        val = (jax.random.uniform(kv, wq.shape) < 0.5).astype(jnp.int32)
        forced = (u & ~(1 << i)) | (val << i)
        u = jnp.where(stuck, forced, u)
    signed = jnp.where(u >= 2 ** (bits - 1), u - 2 ** bits, u)
    return signed.astype(wq.dtype)


def sar_convert_fault_ref(v: jnp.ndarray, key: jax.Array, spec: ADCSpec,
                          cb: bool, fault) -> jnp.ndarray:
    """Bit-for-bit oracle for ``adc.sar_convert(..., fault=...)``.

    Reconstructs the analytic SAR loop with the two conversion-level faults
    spelled out per conversion: the brownout mask selects the
    ``brownout_votes`` majority probability for browned conversions, and
    stuck-ADC columns (global column index = last axis) overwrite the final
    code. Uses the live ``decision_prob``/``majority_prob`` (the probability
    math is oracled separately in tests/test_adc.py) but draws its own
    threefry streams.
    """
    from repro.core.adc import _dnl_shift, decision_prob, majority_prob
    from repro.core.faults import DOMAIN_FAULT
    from repro.core.prng import (
        DOMAIN_SAR, key_words, threefry2x32, uniform_from_bits,
    )

    w = dac_bit_weights(spec)
    vshape = v.shape
    vf = _dnl_shift(v.reshape(-1), spec)
    k0, k1 = key_words(key)
    k0 = k0 ^ jnp.uint32(DOMAIN_SAR)
    idx = jax.lax.iota(jnp.uint32, vf.shape[0])

    brown = None
    if fault is not None and fault.brownout_rate > 0.0 and cb:
        bbits, _ = threefry2x32(
            k0 ^ jnp.uint32(DOMAIN_FAULT), k1 ^ jnp.uint32(fault.seed),
            idx, jnp.uint32(0xB0))
        brown = uniform_from_bits(bbits) < fault.brownout_rate

    n_coarse = spec.adc_bits - spec.mv_bits
    code = jnp.zeros_like(vf, dtype=jnp.int32)
    level = jnp.zeros_like(vf)
    for step in range(spec.adc_bits):
        fine = step >= n_coarse
        sigma = spec.sigma_cmp if fine else spec.coarse_frac * spec.sigma_cmp
        p_glitch = spec.p_glitch if fine else 0.0
        votes = (spec.mv_votes if cb else 1) if fine else 1
        b = spec.adc_bits - 1 - step
        trial = level + w[b]
        bits, _ = threefry2x32(k0, k1, idx, jnp.uint32(step))
        u = uniform_from_bits(bits)
        p1 = decision_prob(vf - trial, sigma, p_glitch, spec.glitch_mag)
        p = majority_prob(p1, votes)
        if brown is not None and votes > 1:
            p = jnp.where(brown, majority_prob(p1, fault.brownout_votes), p)
        bit = u < p
        code = code + bit.astype(jnp.int32) * (1 << b)
        level = jnp.where(bit, trial, level)
    code = code.reshape(vshape)
    if fault is not None and fault.adc_stuck_rate > 0.0 and code.ndim >= 1:
        sbits, _ = threefry2x32(
            jnp.uint32(fault.seed) ^ jnp.uint32(DOMAIN_FAULT), jnp.uint32(3),
            jnp.arange(vshape[-1], dtype=jnp.uint32), jnp.uint32(0))
        stuck = uniform_from_bits(sbits) < fault.adc_stuck_rate
        code = jnp.where(stuck, jnp.int32(fault.adc_stuck_code), code)
    return code


def apply_output_faults_ref(y: jnp.ndarray, fault, sigma, stuck_value,
                            brownout_extra_std,
                            key=None) -> jnp.ndarray:
    """Bit-for-bit oracle for ``core.faults.apply_output_faults``.

    Reconstructs the per-column realisations (gain: fold_in(seed-key, 1);
    offset: fold_in(seed-key, 2); stuck cols: threefry(seed ^ DOMAIN_FAULT,
    3) over the global column index) and applies them in one fused
    expression in the same physical order: gain -> offset -> brownout
    surrogate -> stuck replacement.
    """
    from repro.core.faults import DOMAIN_FAULT
    from repro.core.prng import threefry2x32, uniform_from_bits

    n = y.shape[-1]
    base = jax.random.PRNGKey(fault.seed)
    g = jnp.ones((n,), jnp.float32)
    if fault.col_gain_std > 0.0:
        g = 1.0 + fault.col_gain_std * jax.random.normal(
            jax.random.fold_in(base, 1), (n,))
    off = jnp.zeros((n,), jnp.float32)
    if fault.col_offset_std > 0.0:
        off = (fault.col_offset_std * sigma) * jax.random.normal(
            jax.random.fold_in(base, 2), (n,))
    out = y * g + off
    if fault.brownout_rate > 0.0 and key is not None:
        out = out + brownout_extra_std * jax.random.normal(key, y.shape,
                                                           jnp.float32)
    if fault.adc_stuck_rate > 0.0:
        bits, _ = threefry2x32(
            jnp.uint32(fault.seed) ^ jnp.uint32(DOMAIN_FAULT), jnp.uint32(3),
            jnp.arange(n, dtype=jnp.uint32), jnp.uint32(0))
        stuck = uniform_from_bits(bits) < fault.adc_stuck_rate
        out = jnp.where(stuck, jnp.asarray(stuck_value, jnp.float32), out)
    return out


# ---------------------------------------------------------------------------
# decode-step kernel oracles (MLA latent attention, mamba2 selective scan)
# ---------------------------------------------------------------------------


def mla_decode_attention_ref(
    q_lat: jnp.ndarray,
    q_rope: jnp.ndarray,
    ckv: jnp.ndarray,
    krope: jnp.ndarray,
    lens: jnp.ndarray,
    scale: float,
) -> jnp.ndarray:
    """Dense oracle for ``kernels.mla_decode.mla_decode_attention``.

    Latent-cache MLA decode attention for one query token per row, with the
    up-projections already absorbed by the caller (``models/attention.py``
    folds W_uk into the query and applies W_uv to the returned latent
    context): logits are the sum of the latent and rope channels, masked to
    the first ``lens[b]`` cached positions, and the output is the
    probability-weighted latent cache — shape (B, H, kv_lora). ``ckv`` is
    (B, T, kv_lora) and ``krope`` (B, R, T), as cached.

    ``lens[b] == 0`` rows return exact zeros (mirrors
    ``decode_attention_ref``).
    """
    b, t, _ = ckv.shape
    logits = (
        jnp.einsum("bhl,btl->bht", q_lat, ckv)
        + jnp.einsum("bhd,bdt->bht", q_rope, krope)
    ).astype(jnp.float32) * scale
    valid = jnp.arange(t)[None, :] < lens[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,btl->bhl", probs, ckv.astype(jnp.float32))
    return jnp.where(lens[:, None, None] > 0, out, 0.0)


def mla_prefill_attention_ref(
    q_lat: jnp.ndarray,
    q_rope: jnp.ndarray,
    ckv: jnp.ndarray,
    krope: jnp.ndarray,
    start: jnp.ndarray,
    scale: float,
) -> jnp.ndarray:
    """Dense oracle for ``kernels.mla_prefill.mla_prefill_attention``.

    A chunk of S absorbed queries per row, ``q_lat`` (B, S, H, kv_lora)
    and ``q_rope`` (B, S, H, R), against the latent cache: query i of row
    b sits at position start[b]+i and sees key j iff j <= start[b]+i and
    j < start[b]+S (``_cached_mask`` semantics); ``krope`` is (B, R, T), as
    cached. Returns the probability-weighted latent rows, (B, S, H,
    kv_lora), in float32.
    """
    s, t = q_lat.shape[1], ckv.shape[1]
    f32 = jnp.float32
    logits = (
        jnp.einsum("bshl,btl->bhst", q_lat.astype(f32), ckv.astype(f32))
        + jnp.einsum("bshr,brt->bhst", q_rope.astype(f32),
                     krope.astype(f32))) * scale
    qi = start[:, None, None] + jnp.arange(s)[None, :, None]    # (B, S, 1)
    kj = jnp.arange(t)[None, None, :]
    mask = (kj <= qi) & (kj < start[:, None, None] + s)
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,btl->bshl", probs, ckv.astype(f32))


def ssm_decode_step_ref(
    conv_cache: jnp.ndarray,
    xbc: jnp.ndarray,
    conv_w: jnp.ndarray,
    conv_b: jnp.ndarray,
    dt1: jnp.ndarray,
    a: jnp.ndarray,
    d: jnp.ndarray,
    state: jnp.ndarray,
    d_inner: int,
    ngroups: int,
    d_state: int,
):
    """Oracle for ``kernels.ssm_scan.ssm_decode_step`` — one fused mamba2
    decode step (conv update + gateless SSM state recurrence), mirroring the
    einsum decode branch of ``models/ssm.py`` term for term.

    Args:
      conv_cache: (B, conv_width-1, conv_dim) rolling conv window (past rows).
      xbc:        (B, 1, conv_dim) current in-projection slice.
      conv_w:     (conv_width, conv_dim) depthwise conv weight.
      conv_b:     (conv_dim,) conv bias.
      dt1:        (B, nheads) per-head step size, softplus already applied.
      a:          (nheads,) negative decay rate (-exp(A_log)).
      d:          (nheads,) skip gain.
      state:      (B, nheads, headdim, d_state) SSM state, float32.

    Returns:
      (y, new_conv, new_state): y (B, d_inner) float32 pre-gated-norm
      output, new_conv (B, conv_width-1, conv_dim) advanced window in
      xbc.dtype, new_state (B, nheads, headdim, d_state) float32.
    """
    nheads = a.shape[0]
    headdim = d_inner // nheads
    conv_win = jnp.concatenate([conv_cache.astype(xbc.dtype), xbc], axis=1)
    conv = jnp.einsum("bwc,wc->bc", conv_win, conv_w) + conv_b
    xbc_c = jax.nn.silu(conv)
    xs = xbc_c[:, :d_inner]
    bv = xbc_c[:, d_inner:d_inner + ngroups * d_state]
    cv = xbc_c[:, d_inner + ngroups * d_state:]
    xh = xs.reshape(-1, nheads, headdim).astype(jnp.float32)
    bm = bv.reshape(-1, ngroups, d_state)[:, 0].astype(jnp.float32)
    cm = cv.reshape(-1, ngroups, d_state)[:, 0].astype(jnp.float32)
    da = jnp.exp(dt1.astype(jnp.float32) * a[None, :])
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt1.astype(jnp.float32), xh, bm)
    new_state = state * da[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, cm) + d[None, :, None] * xh
    return (y.reshape(-1, d_inner), conv_win[:, 1:], new_state)


# ---------------------------------------------------------------------------
# temporal drift oracles (DESIGN.md §17)
# ---------------------------------------------------------------------------
#
# ``core.drift`` makes every drift component a deterministic function of
# (DriftSpec.seed, step, column). The oracle below reconstructs the fields
# from the raw Threefry contract (broadcast draws + its own accumulation
# loop) so a mismatch means the *seeding/eval contract* moved, not that two
# call sites share an implementation bug.


def drift_fields_ref(spec, n: int, step):
    """Bit-for-bit reconstruction of ``(drift_gain, drift_offset_z)``.

    Draw contract: threefry key ``(seed ^ DOMAIN_DRIFT, tag)``, counters =
    (column, term) for the KL walk coefficients, (column, 0) for the
    temperature sensitivities, (supply epoch, 0) for supply levels, (0, 0)
    for the temperature phase. Walk coefficients are drawn as one broadcast
    (n, terms) block here (vs per-term vectors in core.drift — Threefry is
    elementwise, so the bits agree) and accumulated in the same term order
    with the same scalar grouping, which f32 requires for bit equality.

    Returns (gain, offset_z), each an (n,) f32 array or None when that
    channel is off.
    """
    import math as _math

    from repro.core import drift as _drift
    from repro.core.prng import (
        gaussian_from_bits, threefry2x32, uniform_from_bits,
    )

    t = jnp.asarray(step, jnp.float32)
    cols = jnp.arange(n, dtype=jnp.uint32)
    hor = float(spec.horizon)
    dkey = jnp.uint32(spec.seed) ^ jnp.uint32(_drift.DOMAIN_DRIFT)

    def draw(tag, c0, c1):
        b0, b1 = threefry2x32(dkey, jnp.uint32(tag),
                              jnp.asarray(c0, jnp.uint32),
                              jnp.asarray(c1, jnp.uint32))
        return gaussian_from_bits(b0, b1)

    def walk(tag):
        jidx = jnp.arange(spec.walk_terms, dtype=jnp.uint32)[None, :]
        z = draw(tag, cols[:, None], jidx)                   # (n, terms)
        acc = jnp.zeros((n,), jnp.float32)
        for j in range(spec.walk_terms):
            w = (j + 0.5) * _math.pi
            acc = acc + z[:, j] * (
                (_math.sqrt(2.0) / w) * jnp.sin((w / hor) * t))
        return acc

    def wave():
        b0, _ = threefry2x32(dkey, jnp.uint32(_drift.TAG_TEMP_PHASE),
                             jnp.uint32(0), jnp.uint32(0))
        phase = (2.0 * _math.pi) * uniform_from_bits(b0)
        return jnp.sin((2.0 * _math.pi / float(spec.temp_period)) * t
                       + phase)

    def supply(tag):
        epoch = (jnp.asarray(step, jnp.int32)
                 // jnp.int32(spec.supply_every)).astype(jnp.uint32)
        return jnp.where(epoch > 0, draw(tag, epoch, jnp.uint32(0)),
                         jnp.float32(0.0))

    def field(walk_std, temp_amp, sup_mag, walk_tag, temp_tag, sup_tag):
        val = jnp.zeros((n,), jnp.float32)
        if walk_std > 0.0:
            val = val + walk_std * walk(walk_tag)
        if temp_amp > 0.0:
            sens = draw(temp_tag, cols, jnp.uint32(0))
            val = val + temp_amp * sens * wave()
        if spec.supply_every > 0 and sup_mag > 0.0:
            val = val + sup_mag * supply(sup_tag)
        return val

    gain = None
    if spec.has_gain():
        gain = 1.0 + field(spec.walk_gain_std, spec.temp_gain_amp,
                           spec.supply_gain_mag, _drift.TAG_WALK_GAIN,
                           _drift.TAG_TEMP_GAIN, _drift.TAG_SUPPLY_GAIN)
    off = None
    if spec.has_offset():
        off = field(spec.walk_offset_std, spec.temp_offset_amp,
                    spec.supply_offset_mag, _drift.TAG_WALK_OFFSET,
                    _drift.TAG_TEMP_OFFSET, _drift.TAG_SUPPLY_OFFSET)
    return gain, off


def apply_drift_ref(y: jnp.ndarray, spec, sigma, dstate) -> jnp.ndarray:
    """Bit-for-bit oracle for ``core.drift.apply_drift`` (drift fields from
    ``drift_fields_ref`` + the same gain -> offset -> trim-inverse order)."""
    if spec is None or dstate is None or not spec.active():
        return y
    step, trim_gain, trim_off = dstate
    n = y.shape[-1]
    gain, off = drift_fields_ref(spec, n, step)
    if gain is not None:
        y = y * gain
    if off is not None:
        y = y + sigma * off
    if trim_gain is not None:
        y = (y - sigma * trim_off[:n]) / trim_gain[:n]
    return y
