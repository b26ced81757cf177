"""Latent-cache MLA prefill-attention Pallas TPU kernel.

The chunked-prefill counterpart of ``kernels/mla_decode.py``: a chunk of
``S`` queries per row against the shared latent cache, *absorbed* as in
decode (W_uk folded into the query by the caller, W_uv applied to the
latent output outside), so the cache is read as stored and never
up-projected to per-head keys and values:

  * every head attends the same latent keys, so the heads become rows of
    one query operand: row ``r`` of ``(S·H, L)`` is position ``r // H``,
    head ``r % H``. A block of ``block_q`` positions is one dense
    ``(block_q·H, L) x (L, block_k)`` score dot plus the rope channel's
    ``(block_q·H, R) x (R, block_k)``, and the same latent block is the
    value operand of the weighted sum (one load, two uses).
  * grid ``(B, q_blocks, kv_blocks)`` with the per-row offsets ``start``
    scalar-prefetched: query ``i`` of row ``b`` sits at ``start[b] + i``
    and sees keys ``j <= start[b] + i``. Key blocks past a query block's
    causal frontier are skipped (``pl.when``) and their index maps clamp
    to the frontier block, so no DMA is issued for them: the cache is read
    up to each row's live length, not to its allocated end.
  * online softmax in VMEM scratch across the key sweep; bf16 operands,
    float32 accumulation.
  * the caches may be the whole layer stack ``(L, R, T, W)``: the
    scalar-prefetched ``layer`` and per-row ``rows`` (the slot a chunk
    fills) address it in the index maps, as in ``mla_decode``.

Validated against the einsum branch in interpret mode
(tests/test_mla_moe_lite.py); CPU callers get ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (NEG_INF, _pick_block_k,
                                            stack_operands)

ROWS = 512          # query rows (positions x heads) per block


def _kernel(start_ref, layer_ref, rows_ref, ql_ref, qr_ref, ckv_ref,
            kr_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
            block_q: int, block_k: int, heads: int, n_k: int,
            s_valid: int):
    b = pl.program_id(0)
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start_b = start_ref[b]
    q_abs_max = start_b + jnp.minimum((qb + 1) * block_q, s_valid) - 1

    @pl.when(kb * block_k <= q_abs_max)
    def _compute():
        rows = block_q * heads
        qi = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0) // heads
        kj = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        # causal at start+i; keys past the chunk (recycled-slot junk) hidden
        mask = (kj <= qi + start_b) & (kj < start_b + s_valid)
        ckv = ckv_ref[0]                                   # (bk, L)
        s = (jnp.dot(ql_ref[0], ckv.T, preferred_element_type=jnp.float32)
             + jnp.dot(qr_ref[0], kr_ref[0],
                       preferred_element_type=jnp.float32)) * scale
        s = jnp.where(mask, s, NEG_INF)                    # (rows, bk)
        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(kb == n_k - 1)
    def _done():
        denom = jnp.maximum(l_ref[0], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_k", "interpret"))
def mla_prefill_attention(
    q_lat: jnp.ndarray,
    q_rope: jnp.ndarray,
    ckv: jnp.ndarray,
    krope: jnp.ndarray,
    start: jnp.ndarray,
    scale: float,
    layer: jnp.ndarray | int | None = None,
    rows: jnp.ndarray | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Causal chunk attention against the latent cache.

    Args:
      q_lat:  (B, S, H, L) queries with W_uk absorbed (L = kv_lora rank).
      q_rope: (B, S, H, R) rope-channel queries.
      ckv:    (B, T, L) latent cache, the chunk's own rows already written,
              or its layer stack (n_layers, R_c, T, L).
      krope:  (B, R, T) shared rope-channel key cache, positions minor
              (``attention.init_mla_cache``), or its stack.
      start:  (B,) int32 position of each row's first query.
      scale:  static softmax scale (``attention.mla_softmax_scale``).
      layer:  the layer of a stacked cache (a traced scalar).
      rows:   (B,) int32 cache row of each query row; default ``0..B-1``.
      block_k: key block; shrunk to a divisor of T.
      interpret: force Pallas interpret mode; default auto (True off-TPU).

    Returns:
      (B, S, H, L) latent context rows in q_lat.dtype — apply W_uv outside.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, lat = q_lat.shape
    (ckv, krope), layer, rows = stack_operands(b, (ckv, krope), layer, rows)
    t = ckv.shape[2]
    rope_hd = q_rope.shape[-1]
    bq = max(1, min(ROWS // h, s))
    bq = 1 << (bq.bit_length() - 1)            # a power of two <= S
    sq = -(-s // bq) * bq
    n_q = sq // bq
    bk = _pick_block_k(t, block_k)
    n_k = t // bk
    pad = ((0, 0), (0, sq - s), (0, 0), (0, 0))
    ql = jnp.pad(q_lat, pad).reshape(b, sq * h, lat)
    qr = jnp.pad(q_rope, pad).reshape(b, sq * h, rope_hd)
    start = start.astype(jnp.int32)

    def q_map(bi, qi, kb, *_):
        return (bi, qi, 0)

    def kv_map(bi, qi, kb, st, layer_pref, rows_pref):
        last = (st[bi] + jnp.minimum((qi + 1) * bq, s) - 1) // bk
        return (layer_pref[0], rows_pref[bi], jnp.minimum(kb, last), 0)

    def kr_map(*idx):
        # the rope keys are cached (R, T) per row, positions minor
        lay, row, kb, _ = kv_map(*idx)
        return (lay, row, 0, kb)

    qrows = bq * h
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, qrows, lat), q_map),
            pl.BlockSpec((1, qrows, rope_hd), q_map),
            pl.BlockSpec((None, 1, bk, lat), kv_map),
            pl.BlockSpec((None, 1, rope_hd, bk), kr_map),
        ],
        out_specs=pl.BlockSpec((1, qrows, lat), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, qrows), jnp.float32),    # running max
            pltpu.VMEM((1, qrows), jnp.float32),    # denominator
            pltpu.VMEM((qrows, lat), jnp.float32),  # latent accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=bq, block_k=bk,
                          heads=h, n_k=n_k, s_valid=s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq * h, lat), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mla_prefill",
    )(start, layer, rows, ql, qr, ckv, krope)
    return out.reshape(b, sq, h, lat)[:, :s]
