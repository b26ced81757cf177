"""Pallas TPU kernel for the CR-CIM behavioural matmul, with in-kernel PRNG.

The macro quantizes *partial sums* at ``macro_rows`` (=1024) granularity: each
K-tile's analog sum is read through the 10-bit SAR ADC before digital
accumulation. The kernel fuses, per (bm x bn x bk) block:

    int8 x int8 -> int32 MXU dot  (+)  per-K-tile readout error injection
                                  (+)  dequant scale epilogue

into a single VMEM-resident accumulation. The readout noise is *generated
inside the kernel* from a scalar-prefetched seed and the grid position —
there is no ``(T, M, N)`` noise operand any more, which removes the dominant
HBM stream of the old design (for a 4096^3 int8 matmul: 256 MiB of noise vs
32 MiB of operands).

Two noise constructions (``prng_impl``):

  * ``"threefry"`` (the default on every backend): counter-based
    Threefry-2x32 keyed on (seed, k-tile) with the *global* (row, col) as
    counter, bits -> Box-Muller Gaussian (``repro.core.prng``).
    Bit-reproducible against the pure-jnp oracle ``ref.cim_matmul_prng_ref``
    and invariant to bm/bn, and the same stream the dense megakernel draws.
  * ``"hw"`` (opt-in, compiled TPU only): the TPU on-core PRNG
    (``pltpu.prng_seed`` with the key word and the folded grid position,
    ``prng_random_bits``), same bits -> Gaussian pipeline. Deterministic
    given (seed, grid), but the stream differs from the oracle and depends
    on the block shape, so it is checked by its moments, not bit for bit.
    There is no CPU lowering for these primitives. It stays opt-in until its
    device time is measured against Threefry (DESIGN.md §3).

The dequant epilogue multiplies the f32 accumulator by a scalar ``scale``
(= x_scale * w_scale) held in SMEM, so ``ops.cim_matmul`` no longer runs a
separate elementwise f32 pass over the (M, N) output.

TPU mapping (DESIGN.md §2): bk == macro_rows == 1024 keeps one macro tile per
grid step and is MXU-aligned; bm/bn auto-select (``bm=None``) — 256 for
training/prefill shapes (working set x 256KiB + w 256KiB + acc 256KiB inside
VMEM), but a *decode-shaped* call (M = a handful of serving slots) gets a
skinny tile instead of a 256-row pad (next multiple of 8; Mosaic compiles
8-row int8 blocks for v5e): 8-64x less row work and activation traffic.
Under the threefry PRNG the result is bit-identical across tile shapes
(global (row, col) counter, §3); the "hw" stream seeds on the grid
position, so on compiled TPU re-tiling keeps only statistical
equivalence. Grid iteration order is (m, n, k) with k innermost
("arbitrary" semantics) so the f32 accumulator lives in a VMEM scratch
across the K sweep.

``cim_matmul_fused_pallas`` (DESIGN.md §12) additionally pulls the
activation quantization into the kernel prologue: the float activation block
is rounded/clipped against an SMEM-resident scale right before the MXU dot,
so the int8 ``xq`` never exists in HBM, and the weight side streams the
*deployed* int8 plane (``core.deploy``) — 4x narrower than the f32 weight
the old two-pass pipeline read and re-quantized per call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.prng import tile_gaussian

MACRO_ROWS = 1024
_GOLDEN = -1640531527  # 0x9E3779B9 as int32


def _auto_bm(m: int) -> int:
    """Decode-shaped tile pick: next multiple of 8 >= m, capped at 256.

    A fused decode step runs M = active-slot count (4-8 rows); padding that
    to the training-shaped bm=256 does 8-64x the row work and streams a
    256-row activation block per grid step. Under the threefry PRNG the
    noise counter is the *global* (row, col) (DESIGN.md §3), so shrinking bm
    is bit-invariant; the TPU "hw" stream seeds on the grid position and is
    only *statistically* equivalent across tile shapes.
    """
    return max(8, min(256, -(-m // 8) * 8))


def _auto_bn(n: int) -> int:
    """Next multiple of 128 (lane width) >= n, capped at 256."""
    return max(128, min(256, -(-n // 128) * 128))


def modeled_cost(m: int, k: int, n: int, bm: int | None = None,
                 bn: int | None = None, bk: int = MACRO_ROWS,
                 x_bytes: int = 1, w_bytes: int = 1,
                 out_bytes: int = 4) -> dict:
    """Modeled FLOPs + HBM bytes of one kernel launch at its padded grid.

    Block-DMA traffic model: the x block re-streams once per N-block column,
    the w block once per M-block row, the output writes once. This is the
    cost the benchmarks compare across tile shapes (interpret-mode wall
    clock is emulation — the model is the perf witness, as in
    benchmarks/attention_bench.py). Auto-picked blocks are the ones the
    kernel launches.
    """
    bm = _auto_bm(m) if bm is None else bm
    bn = _auto_bn(n) if bn is None else bn
    gm, gn, gk = -(-m // bm), -(-n // bn), -(-k // bk)
    mp, np_, kp = gm * bm, gn * bn, gk * bk
    flops = 2.0 * mp * kp * np_
    hbm = float(gn * mp * kp * x_bytes + gm * kp * np_ * w_bytes
                + mp * np_ * out_bytes)
    return {"flops": flops, "hbm_bytes": hbm, "bm": bm, "bn": bn}


def _hw_tile_gaussian(seed_ref, i, j, kk, bm, bn):
    """(bm, bn) standard normals from the TPU on-core PRNG.

    Mosaic seeds the core PRNG with at most two words, so the grid position
    is folded into the second one: the linear tile index times an odd
    (golden-ratio) constant, XOR-ed onto the key word.
    """
    from repro.core.prng import gaussian_from_bits

    tile = (i * pl.num_programs(1) + j) * pl.num_programs(2) + kk
    pltpu.prng_seed(seed_ref[0], seed_ref[1] ^ (tile * _GOLDEN))
    bits = pltpu.bitcast(pltpu.prng_random_bits((2 * bm, bn)), jnp.uint32)
    return gaussian_from_bits(bits[:bm], bits[bm:])


def _tile_noise(seed_ref, i, j, kk, bm, bn, prng_impl):
    """(bm, bn) readout-noise normals per the §3 seeding contract."""
    if prng_impl == "hw":
        return _hw_tile_gaussian(seed_ref, i, j, kk, bm, bn)
    s0 = seed_ref[0].astype(jnp.uint32)
    s1 = seed_ref[1].astype(jnp.uint32)
    row0 = (i * bm).astype(jnp.uint32)
    col0 = (j * bn).astype(jnp.uint32)
    r_ids = row0 + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 0)
    c_ids = col0 + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 1)
    return tile_gaussian(s0, s1, kk.astype(jnp.uint32), r_ids, c_ids)


def _kernel(seed_ref, x_ref, w_ref, scale_ref, o_ref, acc_ref, *,
            sigma: float, n_k: int, bm: int, bn: int, prng_impl: str):
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU int8 dot with int32 accumulate; the partial sum of one macro tile
    # is exactly representable in f32 (< 2^24), so the f32 accumulator is
    # exact for the deterministic part.
    s = jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.int32
    ).astype(jnp.float32)
    if sigma > 0.0:
        s = s + sigma * _tile_noise(seed_ref, i, j, kk, bm, bn, prng_impl)
    acc_ref[...] = acc_ref[...] + s

    @pl.when(kk == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...] * scale_ref[0]


def _fused_kernel(seed_ref, x_ref, w_ref, qp_ref, o_ref, acc_ref, *,
                  sigma: float, n_k: int, bm: int, bn: int, qmax: int,
                  prng_impl: str):
    """Fused-activation-quant variant: the float activation block is
    quantized in the kernel prologue (round/clip against the SMEM-resident
    x_scale), so ``xq`` never exists as a separate HBM tensor. Weight blocks
    stream as the resident int8 plane. ``qp_ref`` = [x_scale, out_scale]."""
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xq = jnp.clip(jnp.round(x_ref[...] / qp_ref[0]),
                  -qmax, qmax).astype(jnp.int8)
    s = jnp.dot(xq, w_ref[...],
                preferred_element_type=jnp.int32).astype(jnp.float32)
    if sigma > 0.0:
        s = s + sigma * _tile_noise(seed_ref, i, j, kk, bm, bn, prng_impl)
    acc_ref[...] = acc_ref[...] + s

    @pl.when(kk == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...] * qp_ref[1]


def _resolve_blocks(m, n, bm, bn):
    return (_auto_bm(m) if bm is None else bm,
            _auto_bn(n) if bn is None else bn)


def _resolve_seed(seed, sigma):
    if seed is None:
        return jnp.zeros((2,), jnp.int32), 0.0
    seed = jnp.asarray(seed, jnp.int32).reshape(-1)
    assert seed.shape[0] in (1, 2), seed.shape
    if seed.shape[0] == 1:
        seed = jnp.concatenate([seed, jnp.zeros((1,), jnp.int32)])
    return seed, sigma


def _macro_grid_spec(mp, np_, bm, bn, bk, n_k):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, sr: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk, sr: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, sr: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "bm", "bn", "bk", "interpret", "prng_impl"),
)
def cim_matmul_pallas(
    xq: jnp.ndarray,
    wq: jnp.ndarray,
    seed: jnp.ndarray | int | None,
    sigma: float = 0.0,
    scale: jnp.ndarray | float | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int = MACRO_ROWS,
    interpret: bool = False,
    prng_impl: str = "threefry",
) -> jnp.ndarray:
    """CIM behavioural matmul with in-kernel noise. See module docstring.

    Args:
      xq:    (M, K) int8. M, K need not be tile-aligned (padded here).
      wq:    (K, N) int8.
      seed:  int32 seed for the per-tile noise — a scalar or a (2,) vector
             (both words of a JAX PRNG key, see ``prng.seed_from_key``; a
             scalar is zero-extended) — or None (sigma==0 path).
      sigma: per-K-tile output-referred error std (integer product units).
      scale: scalar dequant factor fused into the epilogue (None -> 1.0).
      bm/bn: block shape; None auto-selects — decode-shaped (skinny) M gets
             the next multiple of 8 instead of a 256-row pad, bit-identically
             (the threefry counter is the global coordinate, DESIGN.md §3).
      prng_impl: "threefry" | "hw" (see module docstring).

    Returns: (M, N) float32 of (sum_k tiles + noise) * scale.
    """
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2, (xq.shape, wq.shape)
    bm, bn = _resolve_blocks(m, n, bm, bn)
    n_k = -(-k // bk)
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, n_k * bk

    xq = jnp.pad(xq, ((0, mp - m), (0, kp - k)))
    wq = jnp.pad(wq, ((0, kp - k), (0, np_ - n)))
    seed, sigma = _resolve_seed(seed, sigma)
    scale = (
        jnp.ones((1,), jnp.float32)
        if scale is None
        else jnp.asarray(scale, jnp.float32).reshape(1)
    )

    out = pl.pallas_call(
        functools.partial(
            _kernel, sigma=float(sigma), n_k=n_k, bm=bm, bn=bn,
            prng_impl=prng_impl,
        ),
        grid_spec=_macro_grid_spec(mp, np_, bm, bn, bk, n_k),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seed, xq, wq, scale)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "in_bits", "bm", "bn", "bk", "interpret",
                     "prng_impl"),
)
def cim_matmul_fused_pallas(
    x: jnp.ndarray,
    wq: jnp.ndarray,
    x_scale: jnp.ndarray | float,
    seed: jnp.ndarray | int | None,
    sigma: float = 0.0,
    in_bits: int = 6,
    scale: jnp.ndarray | float | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int = MACRO_ROWS,
    interpret: bool = False,
    prng_impl: str = "threefry",
) -> jnp.ndarray:
    """Fused activation quant + CIM matmul on a resident int8 weight plane.

    ``x`` is the *float* activation (M, K); its symmetric quantization at
    ``in_bits`` against the scalar ``x_scale`` happens in the kernel
    prologue per VMEM block, so the int8 ``xq`` never round-trips HBM as a
    separate tensor (the two-pass quantize -> matmul pipeline collapses to
    one kernel). ``wq`` is the deployed int8 plane (``core.deploy``) — the
    weight stream is 4x narrower than the f32 weight the old path re-read
    and re-quantized per call. Bit-exact oracle:
    ``ref.cim_matmul_fused_ref`` (and equal to quantizing first and calling
    ``cim_matmul_pallas`` — the prologue computes the identical round/clip).

    Returns: (M, N) float32 of (sum_k tiles + noise) * scale.
    """
    m, k = x.shape
    k2, n = wq.shape
    assert k == k2, (x.shape, wq.shape)
    # the prologue casts the quantized block to int8 for the MXU dot
    assert in_bits <= 8, f"fused act quant is int8-bound, got in_bits={in_bits}"
    bm, bn = _resolve_blocks(m, n, bm, bn)
    n_k = -(-k // bk)
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, n_k * bk

    x = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, kp - k)))
    wq = jnp.pad(wq, ((0, kp - k), (0, np_ - n)))
    seed, sigma = _resolve_seed(seed, sigma)
    out_scale = jnp.float32(1.0) if scale is None else scale
    qp = jnp.stack([jnp.asarray(x_scale, jnp.float32).reshape(()),
                    jnp.asarray(out_scale, jnp.float32).reshape(())])

    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, sigma=float(sigma), n_k=n_k, bm=bm, bn=bn,
            qmax=2 ** (in_bits - 1) - 1, prng_impl=prng_impl,
        ),
        grid_spec=_macro_grid_spec(mp, np_, bm, bn, bk, n_k),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seed, x, wq, qp)
    return out[:m, :n]
