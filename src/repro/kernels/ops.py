"""jit'd public wrappers around the Pallas kernels.

``cim_matmul``: quantize -> (kernel | oracle) -> dequantize, with a
straight-through custom VJP so the same op is usable in QAT training. On CPU
(this container) the kernel runs in interpret mode or falls back to the
oracle; on TPU the Pallas path compiles natively.

``cim_matmul_deployed``: the inference fast path (DESIGN.md §12) — the
weight arrives as a *pre-quantized plane* ``(wq int8, ws)`` from
``core.deploy`` and the activation quantization fuses into the kernel
prologue (``cim_matmul_fused_pallas`` / ``ref.cim_matmul_fused_ref``), so a
sim-mode forward runs zero weight-side quantization work and never
materialises ``xq`` in HBM. Serving-only: no VJP (QAT trains on the f32
``w``).

The kernel carries no noise operand: readout error is generated in-kernel
from a single int32 seed (derived from the caller's PRNG key), and the
dequant scale ``x_scale * w_scale`` is fused into the kernel epilogue — the
old separate f32 pass over the (M, N) output is gone.

Per-tile sigma uses ``output_noise_std_int_per_tile(spec, K)``, i.e. the
analog gain is fitted to the true K exactly as in the bit-exact path. (The
old code applied the full-tile sigma ``output_noise_std_int(spec,
macro_rows)`` to every tile, overstating the noise whenever K <
macro_rows — see the regression test in tests/test_kernels.py.)

Inference residuals stay int8: ``cim_matmul``'s forward saves
``(xq, xs, wq, ws)`` and the STE backward dequantizes lazily, so an
inference-only call holds two int8 tensors instead of two f32 copies of the
operands (4x less residual memory; the old code materialised ``fq_x``/
``fq_w`` in the forward unconditionally).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.cim import (
    CIMSpec,
    adc_stuck_value_int,
    brownout_extra_std_int,
    output_noise_std_int,
    output_noise_std_int_per_tile,
)
from repro.core.drift import apply_drift
from repro.core.faults import apply_output_faults
from repro.core.prng import seed_from_key
from repro.kernels import ref
from repro.kernels.cim_matmul import (
    MACRO_ROWS,
    cim_matmul_fused_pallas,
    cim_matmul_pallas,
)


def _backend() -> str:
    return jax.default_backend()


def _use_pallas() -> bool:
    return _backend() == "tpu"


def cim_matmul_int(
    xq: jnp.ndarray,
    wq: jnp.ndarray,
    seed: Optional[jnp.ndarray],
    sigma: float,
    macro_rows: int = MACRO_ROWS,
    scale: Optional[jnp.ndarray] = None,
    force: Optional[str] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
) -> jnp.ndarray:
    """Integer-domain CIM matmul; dispatches kernel vs oracle.

    seed: int32 scalar for the in-kernel PRNG, or None (noiseless path).
    scale: scalar dequant factor applied in the epilogue (None -> 1.0).
    force: None (auto), "pallas", "pallas_interpret", "ref".
    bm/bn: kernel block shape; None auto-selects (decode-shaped M gets a
      skinny tile — the next multiple of 8 rows — instead of a 256-row pad;
      bit-identical under the threefry PRNG).
    """
    mode = force or ("pallas" if _use_pallas() else "ref")
    if mode in ("pallas", "pallas_interpret"):
        return cim_matmul_pallas(
            xq.astype(jnp.int8), wq.astype(jnp.int8), seed, sigma,
            scale=scale, bm=bm, bn=bn, bk=macro_rows,
            interpret=(mode == "pallas_interpret"),
        )
    return ref.cim_matmul_prng_ref(xq, wq, seed, sigma, macro_rows, scale)


def cim_matmul_fused_int(
    x: jnp.ndarray,
    wq: jnp.ndarray,
    x_scale: jnp.ndarray,
    seed: Optional[jnp.ndarray],
    sigma: float,
    in_bits: int,
    macro_rows: int = MACRO_ROWS,
    scale: Optional[jnp.ndarray] = None,
    force: Optional[str] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
) -> jnp.ndarray:
    """Fused act-quant CIM matmul on a deployed int8 weight plane.

    ``x`` is the float (M, K) activation; quantization against the scalar
    ``x_scale`` happens in the kernel prologue (no HBM ``xq``). Dispatches
    ``cim_matmul_fused_pallas`` vs ``ref.cim_matmul_fused_ref``.
    """
    mode = force or ("pallas" if _use_pallas() else "ref")
    if mode in ("pallas", "pallas_interpret"):
        return cim_matmul_fused_pallas(
            x, wq.astype(jnp.int8), x_scale, seed, sigma, in_bits=in_bits,
            scale=scale, bm=bm, bn=bn, bk=macro_rows,
            interpret=(mode == "pallas_interpret"),
        )
    return ref.cim_matmul_fused_ref(x, wq, x_scale, seed, sigma, macro_rows,
                                    scale, in_bits)


def cim_matmul_deployed(
    x: jnp.ndarray,
    wq: jnp.ndarray,
    ws: jnp.ndarray,
    spec: CIMSpec,
    key: Optional[jax.Array],
    x_scale: Optional[jnp.ndarray] = None,
    force: Optional[str] = None,
    dstate=None,
) -> jnp.ndarray:
    """Inference fast path: y ~ macro(x @ (wq * ws)) with fused act quant.

    The weight-side abs-max/round/clip of ``cim_matmul`` is gone — ``wq``
    is the resident plane the macro was programmed with (``core.deploy``).
    Serving-only by design: no custom VJP (QAT differentiates through the
    f32 weight path).

    ``spec.fault`` runtime faults (DESIGN.md §14) apply in the epilogue,
    *outside* the kernel: stuck-at bitcells already live in the deployed
    ``wq`` plane (so the kernel itself needs no fault path and keeps
    bit-identity with its oracle), and the per-column gain/offset drift,
    stuck-ADC replacement and brownout surrogate act on the dequantized
    output with the same realisations as ``cim_matmul_behavioral`` —
    scaled into dequant units by ``x_scale * ws``.
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]).astype(jnp.float32)
    xs = x_scale if x_scale is not None else quant.abs_max_scale(
        x2, spec.in_bits)
    k = x2.shape[1]
    n = wq.shape[1]
    sigma = output_noise_std_int_per_tile(spec, k)
    seed = None
    if key is not None and sigma > 0:
        seed = seed_from_key(key)
    y = cim_matmul_fused_int(
        x2, wq, xs, seed, sigma, spec.in_bits, spec.macro_rows,
        scale=xs * jnp.asarray(ws, jnp.float32), force=force)
    d = spec.drift
    if d is not None and d.active() and dstate is not None:
        # temporal drift (DESIGN.md §17), output-referred in dequant units —
        # same realisation as the behavioral path (gain is multiplicative,
        # the offset rides in z-units of the analytic sigma), applied before
        # the static fault epilogue so stuck-ADC replacement still wins.
        unit = (xs * jnp.asarray(ws, jnp.float32)).reshape(-1)[0]
        y = apply_drift(y, d, output_noise_std_int(spec, k) * unit, dstate)
    f = spec.fault
    if f is not None and f.any_output_fault():
        unit = (xs * jnp.asarray(ws, jnp.float32)).reshape(-1)[0]
        y = apply_output_faults(
            y, f, output_noise_std_int(spec, k) * unit,
            adc_stuck_value_int(spec, k) * unit,
            brownout_extra_std_int(spec, k) * unit,
            key=(None if key is None else jax.random.fold_in(key, 0x0FA1)))
    return y.reshape(orig_shape[:-1] + (n,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def cim_matmul(x, w, spec: CIMSpec, key: Optional[jax.Array]):
    """y ~ macro(x @ w): fused quantize -> tiled int matmul + per-tile ADC
    error + dequant epilogue. Differentiable via STE (gradients flow as if
    the op were the dequantized exact matmul)."""
    y, _ = _cim_matmul_fwd(x, w, spec, key)
    return y


def _cim_matmul_fwd(x, w, spec: CIMSpec, key):
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]).astype(jnp.float32)
    w = w.astype(jnp.float32)
    xq, xs, wq, ws = quant.quantize_operands(x2, w, spec.in_bits, spec.w_bits)
    k = x2.shape[1]
    n = w.shape[1]
    # per-tile sigma with the analog gain fitted to the true K (matches the
    # bit-exact path's per-layer Vref trim, incl. ragged last tiles)
    sigma = output_noise_std_int_per_tile(spec, k)
    seed = None
    if key is not None and sigma > 0:
        seed = seed_from_key(key)
    y = cim_matmul_int(xq, wq, seed, sigma, spec.macro_rows, scale=xs * ws)
    # narrow residuals (int8 at macro bit-widths); the STE backward
    # dequantizes lazily — inference never holds a f32 copy of either
    # operand. storage_dtype guards exotic specs above 8 bits from int8 wrap.
    res = (xq.astype(quant.storage_dtype(spec.in_bits)), xs,
           wq.astype(quant.storage_dtype(spec.w_bits)), ws, orig_shape)
    return y.reshape(orig_shape[:-1] + (n,)), res


def _cim_matmul_bwd(spec, key, res, g):
    xq, xs, wq, ws, orig_shape = res
    fq_x = quant.dequantize(xq, xs)
    fq_w = quant.dequantize(wq, ws)
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    dx = (g2 @ fq_w.T).reshape(orig_shape)
    dw = fq_x.T @ g2
    return dx, dw


cim_matmul.defvjp(_cim_matmul_fwd, _cim_matmul_bwd)
