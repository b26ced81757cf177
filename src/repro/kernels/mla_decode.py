"""Latent-cache MLA decode-attention Pallas TPU kernel.

The absorbed MLA decode step (``models/attention.py``, DESIGN.md §8) never
materialises per-head K/V: the caller folds W_uk into the query so scores
are taken directly against the shared latent cache ``ckv: (B, T, kv_lora)``
plus the small rope channel ``krope: (B, T, rope_hd)``, and the attention
output is the probability-weighted *latent* rows (W_uv applied outside).
The einsum path still pays O(max_len) for the dead cache tail every decode
step; this kernel is the latent-cache analogue of
``kernels/decode_attention.py``:

  * grid ``(B, kv_blocks)`` with ``lens: (B,)`` scalar-prefetched; blocks at
    or past ``ceil(lens[b]/block_k)`` are skipped via ``pl.when`` and their
    ckv/krope index maps clamp to the last live block (no dead-tail DMA).
  * online softmax over the block sweep with VMEM scratch; since the same
    ``ckv`` block is both the score operand and the value operand, each
    block is loaded once and used twice — the one-pass structure the MLA
    paper's "absorbed" decode is designed for.
  * heads are jointly resident: scores are one ``(H, L) x (L, bk)`` plus one
    ``(H, R) x (R, bk)`` MXU dot per block (L = kv_lora, R = rope_hd); no
    per-KV-head grouping is needed because MLA shares one latent cache
    across all heads.

``lens[b]`` counts valid cached positions *including* the current token;
``lens[b] == 0`` rows return exactly zero. The caches may be the whole
layer stack ``(L, R, T, W)``, read at a scalar-prefetched ``layer`` and
per-query ``rows`` as ``kernels/decode_attention.py`` reads its stack;
the rope keys are cached ``(R, T)`` per row and read as ``(R, bk)`` blocks.
Validated against ``ref.mla_decode_attention_ref`` and the einsum branch
in interpret mode (tests/test_megakernel.py); CPU callers get
``interpret=True`` automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (NEG_INF, _pick_block_k,
                                            stack_operands)


def _kernel(lens_ref, layer_ref, rows_ref, ql_ref, qr_ref, ckv_ref,
            kr_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
            block_k: int, n_kb: int):
    b = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_live = lens_ref[b]

    @pl.when(kb * block_k < n_live)
    def _compute():
        kj = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)
        valid = kj < n_live
        ql = ql_ref[0].astype(jnp.float32)                    # (H, L)
        qr = qr_ref[0].astype(jnp.float32)                    # (H, R)
        ckv = ckv_ref[0].astype(jnp.float32)                  # (bk, L)
        kr = kr_ref[0].astype(jnp.float32)                    # (R, bk)
        s = (jnp.dot(ql, ckv.T, preferred_element_type=jnp.float32)
             + jnp.dot(qr, kr, preferred_element_type=jnp.float32)) * scale
        s = jnp.where(valid[None, :], s, NEG_INF)             # (H, bk)
        m_prev = m_ref[0]                                     # (H,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, ckv, preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(kb == n_kb - 1)
    def _done():
        denom = jnp.maximum(l_ref[0], 1e-30)[:, None]         # (H, 1)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def mla_decode_attention(
    q_lat: jnp.ndarray,
    q_rope: jnp.ndarray,
    ckv: jnp.ndarray,
    krope: jnp.ndarray,
    lens: jnp.ndarray,
    scale: float,
    layer: jnp.ndarray | int | None = None,
    rows: jnp.ndarray | None = None,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Length-aware single-token MLA attention against the latent cache.

    Args:
      q_lat:  (B, H, L) query with W_uk absorbed (L = kv_lora rank).
      q_rope: (B, H, R) rope-channel query (R = rope head dim).
      ckv:    (B, T, L) compressed KV latent cache (scores *and* values),
              or its layer stack (n_layers, R_c, T, L).
      krope:  (B, R, T) shared rope-channel key cache, positions minor
              (``attention.init_mla_cache``), or its stack.
      lens:   (B,) int32 valid cached positions including the current token;
              ``lens[b] == 0`` yields a zero output row.
      scale:  static softmax scale (``attention.mla_softmax_scale``: the
              caller knows the pre-absorption head dims and the rope
              scaling; the kernel cannot recover them from L).
      layer:  the layer of a stacked cache (a traced scalar).
      rows:   (B,) int32 cache row of each query; default ``0..B-1``.
      block_k: latent-cache block size; shrunk to a divisor of T.
      interpret: force Pallas interpret mode; default auto (True off-TPU).

    Returns:
      (B, H, L) latent context rows in q_lat.dtype — apply W_uv outside.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, lat = q_lat.shape
    (ckv, krope), layer, rows = stack_operands(b, (ckv, krope), layer, rows)
    t = ckv.shape[2]
    rope_hd = q_rope.shape[-1]
    bk = _pick_block_k(t, block_k)
    n_kb = t // bk
    lens = lens.astype(jnp.int32)

    def kv_map(bi, kb, lens_pref, layer_pref, rows_pref):
        last = jnp.maximum((lens_pref[bi] - 1) // bk, 0)
        return (layer_pref[0], rows_pref[bi], jnp.minimum(kb, last), 0)

    def kr_map(*idx):
        # the rope keys are cached (R, T) per row, positions minor
        lay, row, kb, _ = kv_map(*idx)
        return (lay, row, 0, kb)

    def row_map(bi, kb, *_):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_kb),
        in_specs=[
            pl.BlockSpec((1, h, lat), row_map),              # q_lat
            pl.BlockSpec((1, h, rope_hd), row_map),          # q_rope
            pl.BlockSpec((None, 1, bk, lat), kv_map),        # ckv
            pl.BlockSpec((None, 1, rope_hd, bk), kr_map),    # krope
        ],
        out_specs=pl.BlockSpec((1, h, lat), row_map),
        scratch_shapes=[
            pltpu.VMEM((1, h), jnp.float32),         # running max
            pltpu.VMEM((1, h), jnp.float32),         # denominator
            pltpu.VMEM((h, lat), jnp.float32),       # latent accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=bk, n_kb=n_kb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lat), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mla_decode",
    )(lens, layer, rows, q_lat, q_rope, ckv, krope)
