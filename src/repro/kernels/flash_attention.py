"""Flash-attention Pallas TPU kernel (chunked online softmax).

§Perf cells A/B identified the f32 attention-score traffic as the dominant
memory term at s=4096+ — scores (b, h, s, t) never fit VMEM and cost
O(s*t) HBM traffic per pass. This kernel never materialises them: the grid
walks (batch*heads, q_blocks, k_blocks) with the k sweep innermost, keeping
the running max/denominator/accumulator in VMEM scratch (online softmax),
so HBM traffic drops from O(s*t) to O(s*d + t*d) per head.

TPU mapping: block_q x d and block_k x d tiles are MXU-aligned (128
multiples); the two dots per step (q@k^T and p@v) hit the MXU; the
rescaling is VPU elementwise on (block_q,) vectors.

Causal masking is applied in-kernel via block-relative iota, and k blocks
strictly above the causal frontier of their q block are *pruned*: the body
is gated off with ``pl.when`` (no MXU work — the ~2x the original
docstring left as future work) and the k/v BlockSpec index maps clamp the
block index onto the frontier block, so the revisited index issues no new
HBM->VMEM DMA. Pruning is bit-exact: a fully-masked block contributes
p = exp(-inf - m) = 0 to the accumulator and leaves m/l unchanged.

Per-row ``start`` offsets (``attention._cached_mask`` semantics) support
prefill against a partially filled slot cache: query i of row b sits at
absolute position start[b]+i, attends keys j <= start[b]+i and
j < start[b]+s (slot validity — recycled slots keep stale keys beyond the
row's length). ``start`` is scalar-prefetched (SMEM) so both the in-kernel
masks and the pruning frontier are per-row dynamic.

Validated against ``ref.flash_attention_ref`` in interpret mode
(tests/test_kernels.py); ``return_block_counts=True`` additionally returns
the per-(row, q-block) count of k blocks actually computed, which the
pruning tests assert against the closed-form ceil((qi_max+1)/block_k).

Two kernels live here:

  * ``flash_attention`` — the MHA-shaped ``(BH, S, D)`` kernel above
    (training/cross-attention shapes; heads pre-folded into rows).
  * ``flash_gqa_attention`` — the GQA-native prefill kernel (DESIGN.md
    §13): queries stay ``(B, S, H, D)`` and K/V stream straight from the
    lane-dense ``(B, T, KV·D)`` slot cache. Head grouping happens in-kernel
    (the ``(block_q, G, D)`` query block collapses to a ``(block_q·G, D)``
    MXU operand per KV head, read against that head's lane range of the
    K/V block, exactly as ``decode_attention`` does for S=1) and
    an int8 cache is dequantised on the VMEM-resident block — the G-fold
    ``jnp.repeat`` + up-front dequant copies the old prefill wrapper paid
    per chunk are gone. The cache may be the whole layer stack
    ``(L, R, T, KV·D)``, read at a scalar-prefetched layer and per-row
    cache rows (the slot a chunk fills) inside the index maps, so no
    slice of the stack is materialised. ``flash_gqa_modeled_cost``
    records the eliminated KV-stream bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _pick_block_k, stack_operands

NEG_INF = -1e30


def _kernel(start_ref, q_ref, k_ref, v_ref, o_ref, *rest,
            scale: float, causal: bool, bounded: bool, count: bool,
            block_q: int, block_k: int, n_k: int, t_valid: int,
            s_valid: int):
    if count:
        counts_ref, m_ref, l_ref, acc_ref, cnt_ref = rest
    else:
        m_ref, l_ref, acc_ref, cnt_ref = rest
        counts_ref = None
    b = pl.program_id(0)
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[0] = 0

    start_b = start_ref[b]
    if causal:
        # last absolute query position this q block can hold — k blocks
        # strictly beyond it are fully masked and skipped (causal pruning)
        q_abs_max = start_b + jnp.minimum((qb + 1) * block_q, s_valid) - 1
        live = kb * block_k <= q_abs_max
    else:
        live = kb * block_k < t_valid

    @pl.when(live)
    def _compute():
        cnt_ref[0] += 1
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        qi = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kj = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kj < t_valid                            # padded keys -> 0
        if bounded:                                    # slot validity
            mask &= kj < start_b + s_valid
        if causal:
            mask &= kj <= qi + start_b
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == n_k - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        if count:
            counts_ref[0, 0] = cnt_ref[0]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret",
                     "return_block_counts"))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    start: jnp.ndarray | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    return_block_counts: bool = False,
):
    """q: (BH, S, D); k, v: (BH, T, D) -> (BH, S, D). Softmax over T.

    ``start: (BH,)`` int32 per-row absolute offsets (requires ``causal``):
    query i of row b attends keys j <= start[b]+i and j < start[b]+S.
    ``return_block_counts`` additionally returns (BH, n_q_blocks) int32 —
    how many k blocks each q block actually computed (pruning witness).
    ``interpret`` defaults to auto (True on non-TPU backends).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bh, s, d = q.shape
    _, t, _ = k.shape
    bounded = start is not None
    if bounded and not causal:
        raise ValueError("per-row start offsets require causal attention")
    scale = 1.0 / (d ** 0.5)
    sq = -(-s // block_q) * block_q
    tk = -(-t // block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, sq - s), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, tk - t), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, tk - t), (0, 0)))
    start_arr = (jnp.zeros((bh,), jnp.int32) if start is None
                 else start.astype(jnp.int32))

    n_q = sq // block_q
    n_k = tk // block_k

    def q_map(b, i, j, st):
        return (b, i, 0)

    def kv_map(b, i, j, st):
        if causal:
            # clamp pruned blocks onto the causal-frontier block: the
            # repeated block index elides the DMA
            last = (st[b] + jnp.minimum((i + 1) * block_q, s) - 1) // block_k
            j = jnp.minimum(j, last)
        return (b, j, 0)

    out_shapes = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), q_map)]
    if return_block_counts:
        out_shapes.append(jax.ShapeDtypeStruct((bh, n_q), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1), lambda b, i, j, st: (b, i)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          bounded=bounded, count=return_block_counts,
                          block_q=block_q, block_k=block_k, n_k=n_k,
                          t_valid=t, s_valid=s),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(start_arr, qp, kp, vp)
    out = outs[0][:, :s, :]
    if return_block_counts:
        return out, outs[1]
    return out


# ---------------------------------------------------------------------------
# GQA-native flash prefill (DESIGN.md §13)
# ---------------------------------------------------------------------------


def _gqa_blocks(s: int, t: int, block_q: int, block_k: int):
    """Resolved (block_q, block_k) for a GQA flash launch: q pads up to a
    small power-of-two block, k shrinks to a divisor of T (padding the
    cache would copy it). ONE definition shared by the kernel and
    ``flash_gqa_modeled_cost`` so the recorded cost model can never drift
    from the launch configuration the kernel actually runs."""
    bq = min(block_q, max(8, 1 << (max(s, 1) - 1).bit_length()))
    return bq, _pick_block_k(t, block_k)


def _gqa_kernel(start_ref, layer_ref, rows_ref, *refs, scale: float,
                int8: bool, count: bool, block_q: int, block_k: int,
                n_k: int, group: int, kv_heads: int, d: int, s_valid: int):
    if int8:
        q_ref, k_ref, v_ref, ks_ref, vs_ref = refs[:5]
        rest = refs[5:]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        ks_ref = vs_ref = None
        rest = refs[3:]
    if count:
        o_ref, counts_ref, m_ref, l_ref, acc_ref, cnt_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, cnt_ref = rest
        counts_ref = None
    b = pl.program_id(0)
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    rows = block_q * group

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[0] = 0

    start_b = start_ref[b]
    # causal frontier of this q block (last absolute query position it can
    # hold); k blocks strictly beyond it are pruned — same contract as the
    # MHA kernel, shared by every head of the row
    q_abs_max = start_b + jnp.minimum((qb + 1) * block_q, s_valid) - 1

    @pl.when(kb * block_k <= q_abs_max)
    def _compute():
        cnt_ref[0] += 1
        # row r of a head's (block_q*G, D) query block holds query position
        # r // G, grouped head r % G — one dense MXU operand per KV head, no
        # cache head-replication
        qi = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0) // group
        kj = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        # _cached_mask semantics: causal at start[b]+i, keys beyond the
        # freshly written prefix (recycled-slot junk) never exposed
        mask = (kj <= qi + start_b) & (kj < start_b + s_valid)
        for h in range(kv_heads):
            q = q_ref[0, h]                            # (bq*G, D)
            k = k_ref[0, :, h * d:(h + 1) * d]         # (bk, D)
            v = v_ref[0, :, h * d:(h + 1) * d]
            if int8:
                k = k.astype(jnp.float32) * ks_ref[0, :, h, :]
                v = v.astype(jnp.float32) * vs_ref[0, :, h, :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                          # (bq*G,)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])            # (bq*G, bk)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(kb == n_k - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        if count:
            for h in range(kv_heads):
                counts_ref[0, h, 0] = cnt_ref[0]


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret",
                     "return_block_counts"))
def flash_gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    start: jnp.ndarray | None = None,
    ks: jnp.ndarray | None = None,
    vs: jnp.ndarray | None = None,
    layer: jnp.ndarray | int | None = None,
    rows: jnp.ndarray | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    return_block_counts: bool = False,
):
    """GQA-native causal flash prefill against a slot cache.

    Args:
      q:    (B, S, H, D) queries for the S freshly written tokens per row.
      k, v: (B, T, KV·D) slot cache, or the layer stack (L, R, T, KV·D),
            KV head ``h`` in lanes ``[h·D, (h+1)·D)`` (f32/bf16, or int8
            with ``ks``/``vs``).
            ``H % KV == 0``; group size ``G = H // KV``. Streamed in cache
            layout — never head-replicated, never padded
            (``block_k`` is shrunk to a divisor of T; padding would copy
            the whole cache per chunk).
      start: (B,) int32 per-row absolute offsets (``_cached_mask``
            semantics): query i of row b sits at position start[b]+i,
            attends keys j <= start[b]+i and j < start[b]+S. None = zeros.
      ks, vs: (B, T, KV, 1) or (L, R, T, KV, 1) f32 per-key dequant
            scales (int8 cache only) — dequantisation happens on the
            VMEM-resident block in-kernel.
      layer: the layer of a stacked cache (a traced scalar).
      rows: (B,) int32 cache row of each query row; default ``0..B-1``.
      block_q, block_k: tile sizes; block_q pads the (small) q operand,
            block_k shrinks to a divisor of T.
      interpret: force Pallas interpret mode; default auto (True off-TPU).
      return_block_counts: additionally return (B, KV, n_q_blocks) int32
            counts of k blocks actually computed (pruning witness).

    Returns:
      (B, S, H, D) attention output in q.dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    (k, v, ks, vs), layer, rows = stack_operands(b, (k, v, ks, vs), layer,
                                                 rows)
    t, width = k.shape[2:]
    if width % d:
        raise ValueError(f"cache width {width} not a multiple of D={d}")
    kv_heads = width // d
    if h % kv_heads:
        raise ValueError(f"H={h} not a multiple of KV={kv_heads}")
    if (ks is None) != (vs is None):
        raise ValueError("int8 cache needs both ks and vs scales")
    group = h // kv_heads
    int8 = ks is not None
    scale = 1.0 / (d ** 0.5)
    bq, bk = _gqa_blocks(s, t, block_q, block_k)
    sq = -(-s // bq) * bq
    n_q = sq // bq
    n_k = t // bk
    # (B, S, H, D) -> (B, KV, S*G, D): a KV head's G query heads become
    # rows of one operand, so the block's minor dims are (bq*G, D) — Mosaic
    # tiles the last two dims, and a (G, D) head-slice block is not tileable
    qp = jnp.pad(q, ((0, 0), (0, sq - s), (0, 0), (0, 0)))
    qp = qp.reshape(b, sq, kv_heads, group, d).transpose(0, 2, 1, 3, 4)
    qp = qp.reshape(b, kv_heads, sq * group, d)
    start_arr = (jnp.zeros((b,), jnp.int32) if start is None
                 else start.astype(jnp.int32))

    def q_map(bi, qi, kb, *_):
        return (bi, 0, qi, 0)

    def kv_map(bi, qi, kb, st, layer_pref, rows_pref):
        # clamp pruned blocks onto the causal-frontier block: the repeated
        # block index elides the DMA (same trick as the MHA kernel)
        last = (st[bi] + jnp.minimum((qi + 1) * bq, s) - 1) // bk
        return (layer_pref[0], rows_pref[bi], jnp.minimum(kb, last), 0)

    def scale_map(*idx):
        return kv_map(*idx) + (0,)

    in_specs = [
        pl.BlockSpec((1, kv_heads, bq * group, d), q_map),
        pl.BlockSpec((None, 1, bk, width), kv_map),
        pl.BlockSpec((None, 1, bk, width), kv_map),
    ]
    operands = [qp, k, v]
    if int8:
        in_specs += [
            pl.BlockSpec((None, 1, bk, kv_heads, 1), scale_map),
            pl.BlockSpec((None, 1, bk, kv_heads, 1), scale_map),
        ]
        operands += [ks, vs]

    out_shapes = [jax.ShapeDtypeStruct((b, kv_heads, sq * group, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, kv_heads, bq * group, d), q_map)]
    if return_block_counts:
        out_shapes.append(jax.ShapeDtypeStruct((b, kv_heads, n_q), jnp.int32))
        out_specs.append(pl.BlockSpec((1, kv_heads, 1),
                                      lambda bi, qi, kb, *_: (bi, 0, qi)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((kv_heads, bq * group), jnp.float32),     # running max
            pltpu.VMEM((kv_heads, bq * group), jnp.float32),     # denominator
            pltpu.VMEM((kv_heads, bq * group, d), jnp.float32),  # accumulator
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(_gqa_kernel, scale=scale, int8=int8,
                          count=return_block_counts, block_q=bq, block_k=bk,
                          n_k=n_k, group=group, kv_heads=kv_heads, d=d,
                          s_valid=s),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(start_arr, layer, rows, *operands)
    out = outs[0].reshape(b, kv_heads, sq, group, d).transpose(0, 2, 1, 3, 4)
    out = out.reshape(b, sq, h, d)[:, :s]
    if return_block_counts:
        return out, outs[1]
    return out


def flash_gqa_modeled_cost(b: int, s: int, t: int, h: int, kv_heads: int,
                           d: int, start: int = 0, block_q: int = 128,
                           block_k: int = 128, kv_bytes: int = 4) -> dict:
    """Modeled per-launch KV-stream HBM bytes: GQA-native vs the replicated
    MHA wrapper it replaces.

    Both paths prune identically (visited k blocks per q block =
    ceil((start + qi_max + 1)/block_k)), so the differentiator is what each
    visited block streams: the native kernel reads the cache block once per
    KV head at its storage width (``kv_bytes`` = 1 for int8, + the f32
    scale per key), while the old wrapper first materialised a dequantised
    (int8 only) + G-fold head-replicated f32 copy of the whole cache
    (``materialize_bytes_replicated`` — modeled as one fused pass: read
    the stored cache once, write the (B, T, H, D) f32 copy once) and then
    streamed f32 blocks once per *query* head. Interpret-mode wall clock
    is emulation — this model is the perf witness (attention_bench
    precedent); benchmarks/prefill_bench.py cross-checks the materialise
    term against XLA cost_analysis of the replicate step.
    """
    group = h // kv_heads
    bq, bk = _gqa_blocks(s, t, block_q, block_k)
    n_q, n_k = -(-s // bq), t // bk
    visited = sum(min(n_k, (start + min((i + 1) * bq, s) - 1) // bk + 1)
                  for i in range(n_q))
    cols = visited * bk                          # KV columns streamed / head
    int8 = kv_bytes == 1
    scale_bytes = 4 if int8 else 0               # f32 scale per int8 key
    native = 2.0 * b * kv_heads * cols * (d * kv_bytes + scale_bytes)
    replicated = 2.0 * b * h * cols * d * 4      # f32 blocks, per query head
    # the wrapper's up-front copy, one fused dequant+repeat pass per k/v:
    # read the stored cache (+ scales) once, write G-fold f32 once
    materialize = 2.0 * b * t * kv_heads * (
        d * kv_bytes + scale_bytes + group * d * 4)
    return {
        "block_q": bq, "block_k": bk, "visited_blocks": visited,
        "kv_stream_bytes_native": native,
        "kv_stream_bytes_replicated": replicated,
        "materialize_bytes_replicated": materialize,
        "kv_stream_ratio": replicated / native,
        "total_ratio": (replicated + materialize) / native,
    }
