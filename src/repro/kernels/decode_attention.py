"""Ragged, length-aware GQA decode-attention Pallas TPU kernel.

One query token per sequence against the stacked slot cache (serving
engine decode, DESIGN.md §10/§11), stored lane-dense as ``(B, T, KV·D)``:
KV head ``h`` is lanes ``[h·D, (h+1)·D)`` of each key's row. The dense
einsum path computes scores over the *entire* ``(B, max_len)`` cache
every step and masks the dead tail away — O(max_len) FLOPs and HBM
traffic per token even when a slot holds a 3-token prompt. This kernel
makes decode cost scale with the live context instead:

  * grid ``(B, kv_blocks)`` with the per-sequence key counts ``lens: (B,)``
    scalar-prefetched (SMEM): KV blocks at or past ``ceil(lens[b]/block_k)``
    are skipped via ``pl.when`` (no MXU work) *and* their k/v BlockSpec
    index maps clamp to the last live block, so the revisited block index
    issues no new HBM->VMEM DMA — traffic is O(lens[b]), not O(max_len).
  * online softmax: running max / denominator / accumulator live in VMEM
    scratch across the ``kv_blocks`` sweep (``arbitrary`` semantics), the
    output is normalised and written once at the final block.
  * GQA head grouping happens in-kernel: the ``(H, D)`` query block is
    sliced per KV head into ``(G, D)`` groups and the ``(block_k, KV·D)``
    K/V block into its heads' lane ranges, so every score/value product
    is a dense ``(G, D) x (D, block_k)`` MXU dot — no host-side head
    replication of the cache. The block is read as stored: a minor dim
    of KV·D fills the TPU's 128-lane tiles, where a minor (KV, D) pair
    (2 x 64 at qwen2-0.5b) would not and XLA would keep the cache
    sequence-minor, relaid out around every kernel call.
  * int8 KV stays int8 in HBM: ``ks``/``vs`` per-key scales ride the same
    block pipeline and dequantisation happens on the VMEM-resident block
    right before the dot (the einsum fallback used to materialise a full
    f32 copy of the cache every step).

``lens[b]`` counts *valid keys including the current token* (callers pass
``cache_len + 1`` — the query's own key is written before attention).
``lens[b] == 0`` rows (never-touched slots) produce exactly zero output.

The cache operands may be the whole layer stack ``(L, R, T, KV·D)`` the
serving engine keeps: a scalar-prefetched ``layer`` and per-query ``rows``
pick the layer and the cache row each query reads, inside the BlockSpec
index maps, so no slice of the stack is materialised (DESIGN.md §10).
A plain ``(B, T, KV·D)`` cache is the one-layer stack read in row order.

Validated against ``ref.decode_attention_ref`` and the einsum path in
interpret mode (tests/test_decode_attention.py); CPU callers get
``interpret=True`` automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _pick_block_k(t: int, block_k: int) -> int:
    """Largest divisor of T that is <= block_k: never pad the cache (a pad
    would copy the whole (B, T, KV·D) cache every decode step — the exact
    traffic this kernel removes), so block_k must divide T. A plain
    gcd(T, block_k) would collapse to 1-2 for any odd-ish T (e.g. T=258 ->
    2); scanning down from min(block_k, T) keeps blocks MXU-sized for any
    cache length."""
    bk = min(block_k, t)
    while t % bk:
        bk -= 1
    return bk


def stack_operands(b: int, caches, layer, rows):
    """The cache operands as layer stacks, and the (layer, rows) that
    address them: a plain ``(B, T, ...)`` cache becomes a one-layer stack
    (a free reshape) read at layer 0, rows ``0..B-1``. Shared by the four
    attention kernels."""
    if caches[0].ndim == 3:
        caches = tuple(None if c is None else c[None] for c in caches)
        layer = 0
    elif layer is None:
        raise ValueError("a stacked (L, R, T, W) cache needs its layer")
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    rows = (jnp.arange(b, dtype=jnp.int32) if rows is None
            else jnp.asarray(rows, jnp.int32).reshape(b))
    return caches, layer, rows


def _kernel(lens_ref, layer_ref, rows_ref, *refs, scale: float,
            block_k: int, kv_heads: int, group: int, d: int, n_kb: int,
            int8: bool):
    if int8:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
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
        for h in range(kv_heads):
            q = q_ref[0, h * group:(h + 1) * group, :]       # (G, D)
            k = k_ref[0, :, h * d:(h + 1) * d]               # (bk, D)
            v = v_ref[0, :, h * d:(h + 1) * d]
            if int8:
                k = k.astype(jnp.float32) * ks_ref[0, :, h, :]
                v = v.astype(jnp.float32) * vs_ref[0, :, h, :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid[None, :], s, NEG_INF)        # (G, bk)
            m_prev = m_ref[h]                                # (G,)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(kb == n_kb - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]    # (KV, G, 1)
        o = acc_ref[...] / denom                             # (KV, G, D)
        o_ref[0] = o.reshape(kv_heads * group, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lens: jnp.ndarray,
    ks: jnp.ndarray | None = None,
    vs: jnp.ndarray | None = None,
    layer: jnp.ndarray | int | None = None,
    rows: jnp.ndarray | None = None,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Length-aware single-token GQA attention against a slot cache.

    Args:
      q:    (B, H, D) query for the one new token per sequence.
      k, v: (B, T, KV·D) slot cache, or the layer stack (L, R, T, KV·D),
            KV head ``h`` in lanes ``[h·D, (h+1)·D)`` (f32/bf16, or int8
            with ``ks``/``vs``). ``H % KV == 0``; group size
            ``G = H // KV``.
      lens: (B,) int32 — valid keys per row *including* the current token
            (i.e. ``cache_len + 1`` after the decode-step cache write).
            Keys at positions >= lens[b] are never read; lens[b] == 0
            yields a zero output row.
      ks, vs: (B, T, KV, 1) or (L, R, T, KV, 1) f32 per-key dequant
            scales (int8 cache only).
      layer: the layer of a stacked cache (a traced scalar).
      rows:  (B,) int32 cache row of each query; default ``0..B-1``.
      block_k: KV block size; shrunk to a divisor of T (never pads the
            cache).
      interpret: force Pallas interpret mode; default auto (True off-TPU).

    Returns:
      (B, H, D) attention output in q.dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    (k, v, ks, vs), layer, rows = stack_operands(b, (k, v, ks, vs), layer,
                                                 rows)
    t, width = k.shape[2:]
    if width % d:
        raise ValueError(f"cache width {width} not a multiple of D={d}")
    kv_heads = width // d
    if h % kv_heads:
        raise ValueError(f"H={h} not a multiple of KV={kv_heads}")
    group = h // kv_heads
    int8 = ks is not None
    scale = 1.0 / (d ** 0.5)
    bk = _pick_block_k(t, block_k)
    n_kb = t // bk
    lens = lens.astype(jnp.int32)

    def kv_map(bi, kb, lens_pref, layer_pref, rows_pref):
        # clamp dead-tail blocks onto the last live block: the repeated
        # block index elides the DMA, making traffic O(lens) not O(T)
        last = jnp.maximum((lens_pref[bi] - 1) // bk, 0)
        return (layer_pref[0], rows_pref[bi], jnp.minimum(kb, last), 0)

    def scale_map(*idx):
        return kv_map(*idx) + (0,)

    def row_map(bi, kb, *_):
        return (bi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, d), row_map),              # q
        pl.BlockSpec((None, 1, bk, width), kv_map),    # k
        pl.BlockSpec((None, 1, bk, width), kv_map),    # v
    ]
    operands = [q, k, v]
    if int8:
        in_specs += [
            pl.BlockSpec((None, 1, bk, kv_heads, 1), scale_map),  # ks
            pl.BlockSpec((None, 1, bk, kv_heads, 1), scale_map),  # vs
        ]
        operands += [ks, vs]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, group), jnp.float32),      # running max
            pltpu.VMEM((kv_heads, group), jnp.float32),      # denominator
            pltpu.VMEM((kv_heads, group, d), jnp.float32),   # accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=bk,
                          kv_heads=kv_heads, group=group, d=d, n_kb=n_kb,
                          int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, layer, rows, *operands)
