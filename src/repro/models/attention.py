"""Attention: GQA (llama-family), MLA (deepseek-v2), cross-attention, KV caches.

All paths support three phases:
  * train    — full causal self-attention, no cache
  * prefill  — causal, returns a filled cache
  * decode   — one query token against the cache (functional update)

KV caches are plain pytrees so they shard/checkpoint like params. GQA cache:
{"k": (B, S, KV·D), "v": ..., "len": (B,)} — lane-dense, KV head ``h`` in
lanes ``[h·D, (h+1)·D)`` of each position's row (DESIGN.md §10), the one
layout every writer and both attention kernels share; MLA caches the
*compressed* c_kv (B, S, kv_lora) + shared k_rope (B, rope_hd, S),
positions minor — the arch's serving-memory win — and up-projects per
step.

``len`` is *per sequence*: every cached row advances independently, which is
what lets the serving engine fuse ragged continuous-batching slots into one
batch-axis decode program (DESIGN.md §10). The attention mask combines
per-row causality with per-row key validity.

A layer's cache arrives in one of two forms. *Addressed*: the whole layer
stack ``(L, R, T, W)`` with the layer ``layer`` and the cache row of each
batch row ``rows`` (None: rows ``0..B-1``), as the stacked-block scan and
the serving engine hand it over; the new rows are written into the stack
in place (one ``dynamic_update_slice``, scatter or, for the positions-minor
rope keys of a decode step, ``write_columns`` kernel) and the kernels read
their layer and rows through their index maps, so no slice of the stack
is made. *Plain*: one layer's ``(B, T, W)`` leaves (the hybrid and encdec
forwards), written as their one-layer stack.

GQA cached attention runs one of two implementations, selected by
``cfg.attn_impl`` (DESIGN.md §11):

  * ``"einsum"`` (default) — dense masked softmax over the whole cache;
    the reference path, bit-stable across batch shapes.
  * ``"kernel"`` — decode (S==1) through the length-aware Pallas kernel
    (``kernels.decode_attention``, O(len[b]) per row instead of
    O(max_len)); prefill (S>1) through the GQA-native causal-block-pruned
    flash kernel (``kernels.flash_gqa_attention``) with per-row start
    offsets — the cache streams as stored (no head replication, int8
    dequantised in-kernel). Interpret mode off-TPU.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (Ctx, Params, _init_dense, apply_rope, dense,
                                 rmsnorm, yarn_mscale)
from repro.distributed.sharding import shard
from repro.kernels.cache_write import write_columns
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_gqa_attention
from repro.kernels.mla_decode import mla_decode_attention
from repro.kernels.mla_prefill import mla_prefill_attention

NEG_INF = -1e30


# ----------------------------------------------------------------- GQA

def init_gqa(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    pq, aq = _init_dense(ks[0], d, h * hd, ("embed", "heads"), bias=cfg.qkv_bias, dtype=dtype)
    pk, ak = _init_dense(ks[1], d, kv * hd, ("embed", "kv_heads"), bias=cfg.qkv_bias, dtype=dtype)
    pv, av = _init_dense(ks[2], d, kv * hd, ("embed", "kv_heads"), bias=cfg.qkv_bias, dtype=dtype)
    po, ao = _init_dense(ks[3], h * hd, d, ("heads", "embed"), dtype=dtype)
    return {"q": pq, "k": pk, "v": pv, "o": po}, {"q": aq, "k": ak, "v": av, "o": ao}


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict[str, Any]:
    """K/V stored ``(batch, max_len, KV·D)``: the heads merged into one
    minor dim, which fills the TPU's (8, 128) tiles (a minor ``(KV, D)``
    pair such as 2 x 64 does not, and XLA then keeps the cache
    sequence-minor and relayouts it around every kernel call). int8
    caches keep their per-(position, head) scales ``(batch, max_len, KV,
    1)``."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    if cfg.kv_cache_int8:
        return {
            "k": jnp.zeros((batch, max_len, kv * hd), jnp.int8),
            "v": jnp.zeros((batch, max_len, kv * hd), jnp.int8),
            "ks": jnp.zeros((batch, max_len, kv, 1), jnp.float32),
            "vs": jnp.zeros((batch, max_len, kv, 1), jnp.float32),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, kv * hd), dtype),
        "v": jnp.zeros((batch, max_len, kv * hd), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def _kv_quant(x: jnp.ndarray):
    """Per (batch, pos, kv-head) symmetric int8: (int8 vals, f32 scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _sdpa(q, k, v, mask) -> jnp.ndarray:
    """q: (B,S,H,D); k,v: (B,T,KV,D); mask: (B,1,S,T) or None -> (B,S,H,D)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(b, s, kvh, g, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def _sdpa_int8(q, kq, ks, vq, vs, mask) -> jnp.ndarray:
    """Int8-KV attention without materialising a dequantised cache copy.

    q: (B,S,H,D); kq, vq: (B,T,KV,D) int8; ks, vs: (B,T,KV,1) f32 scales.
    The per-key scales commute with the head-dim reduction, so they fold
    into the *logits* (k side) and the *probabilities* (v side) — the
    einsum reads the int8 cache directly and the only scale-sized
    intermediates are logit/prob shaped (no (B,T,KV,D) f32 copy of the
    whole cache per decode step; at max_len=4096 that copy alone is 2x the
    int8 cache's entire footprint).
    """
    b, s, h, d = q.shape
    kvh = kq.shape[2]
    g = h // kvh
    qr = q.reshape(b, s, kvh, g, d)
    ks_t = ks[..., 0].transpose(0, 2, 1)[:, :, None, None, :]   # (B,KV,1,1,T)
    vs_t = vs[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    logits = jnp.einsum("bskgd,btkd->bkgst", qr, kq.astype(q.dtype))
    logits = logits.astype(jnp.float32) * ks_t
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1) * vs_t
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(q.dtype),
                     vq.astype(q.dtype))
    return out.reshape(b, s, h, d)


def _causal_mask(s: int, t: int, offset: int = 0) -> jnp.ndarray:
    """(1, 1, s, t) boolean causal mask; query i attends key j <= i+offset."""
    qi = jnp.arange(s)[:, None] + offset
    kj = jnp.arange(t)[None, :]
    return (kj <= qi)[None, None]


def stack_update(stack: jnp.ndarray, update: jnp.ndarray, layer, rows,
                 starts: jnp.ndarray, seq_axis: int = 2) -> jnp.ndarray:
    """Write ``update`` (B, ...) into the layer stack (L, R, ...): row b,
    laid out as one cache row with its S new positions on axis
    ``seq_axis - 1``, lands in layer ``layer``, cache row ``rows[b]``
    (None: ``b``), from position ``starts[b]`` of the stack's axis
    ``seq_axis``. One row is one ``dynamic_update_slice``, several are
    one scatter; both write in place into a donated or loop-carried
    stack. One position of several rows of a positions-minor stack (a
    decode step's rope keys) is one ``write_columns`` kernel instead: XLA
    would relayout a stack small enough to stage on chip for that
    scatter. A plain (B, T, ...) cache is written as its one-layer stack
    ``cache[None]`` at layer 0."""
    b, s = update.shape[0], update.shape[seq_axis - 1]
    update = update.astype(stack.dtype)
    if b > 1 and s == 1 and seq_axis == stack.ndim - 1 == 3:
        return write_columns(stack, update[..., 0], layer, rows, starts)
    if b == 1:
        at = [0] * stack.ndim
        at[0], at[seq_axis] = layer, starts[0]
        at[1] = 0 if rows is None else rows[0]
        return jax.lax.dynamic_update_slice(stack, update[None], at)
    rows = jnp.arange(b) if rows is None else rows
    pos = starts[:, None] + jnp.arange(s)[None]
    # the indexed axes come first in the indexed result, (B, S, ...)
    idx = (layer, rows[:, None]) + (slice(None),) * (seq_axis - 2) + (pos,)
    return stack.at[idx].set(jnp.moveaxis(update, seq_axis - 1, 1),
                             mode="drop", unique_indices=True)


def write_rows(cache: Dict[str, Any], leaf: str, update: jnp.ndarray,
               starts: jnp.ndarray, seq_axis: int = 2) -> jnp.ndarray:
    """Cache leaf ``leaf`` with ``update`` written at each row's
    ``starts``: in place into an addressed stack, else into the plain
    leaf, as its one-layer stack."""
    if "layer" in cache:
        return stack_update(cache[leaf], update, cache["layer"],
                            cache["rows"], starts, seq_axis)
    return stack_update(cache[leaf][None], update, 0, None, starts,
                        seq_axis)[0]


def _view(cache: Dict[str, Any], leaf: str) -> jnp.ndarray:
    """The batch's rows of leaf ``leaf`` for the einsum path: the plain
    leaf, or a read of the addressed stack (never written back)."""
    x = cache[leaf]
    if "layer" not in cache:
        return x
    x = jax.lax.dynamic_index_in_dim(x, cache["layer"], keepdims=False)
    return x if cache["rows"] is None else x[cache["rows"]]


def _at(cache: Dict[str, Any]) -> Dict[str, Any]:
    """The kernels' ``layer``/``rows`` arguments for this cache."""
    return {"layer": cache.get("layer"), "rows": cache.get("rows")}


def _pow2_block(n: int, cap: int = 128, lo: int = 8) -> int:
    """Smallest power-of-two >= n, clipped to [lo, cap] (flash block pick)."""
    return max(lo, min(cap, 1 << (max(n, 1) - 1).bit_length()))


def _flash_prefill(q, k_c, v_c, start, ks=None, vs=None, layer=None,
                   rows=None) -> jnp.ndarray:
    """Chunked/bucketed prefill through the GQA-native flash kernel
    (attn_impl="kernel", DESIGN.md §13).

    q: (B,S,H,D); k_c, v_c: (B,T,KV·D) slot cache, or the layer stack
    addressed by ``layer``/``rows``, streamed *as stored* —
    head grouping happens in-kernel (the G-fold ``jnp.repeat`` copy the
    old MHA-shaped wrapper paid per prefill is gone) and an int8 cache
    (``ks``/``vs`` scales) dequantises on the VMEM-resident block, so the
    cache never round-trips HBM at f32. Per-row ``start`` offsets give the
    causal-block-pruned continued-prefill path for any chunk of the
    prompt.
    """
    s = q.shape[1]
    t = k_c.shape[-2]
    return flash_gqa_attention(q, k_c, v_c, start=start.astype(jnp.int32),
                               ks=ks, vs=vs, layer=layer, rows=rows,
                               block_q=_pow2_block(s),
                               block_k=_pow2_block(t))


def _cached_mask(start: jnp.ndarray, s: int, t: int) -> jnp.ndarray:
    """(B, 1, s, t) decode/prefill mask for per-sequence cache lengths.

    Query i of row b sits at absolute position start[b]+i; it may attend key
    slot j iff j is causal (j <= start[b]+i) *and* j holds a written key
    (j < start[b]+s). Causality implies validity here, but the validity term
    is kept explicit: recycled slots keep stale keys beyond the row's length
    and must never expose them.
    """
    qi = jnp.arange(s)[None, :] + start[:, None]            # (B, s)
    kj = jnp.arange(t)                                      # (t,)
    mask = (kj[None, None, :] <= qi[:, :, None]) & \
           (kj[None, None, :] < (start + s)[:, None, None])
    return mask[:, None]


def gqa_attention(
    ctx: Ctx,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[Dict[str, Any]] = None,
    causal: bool = True,
) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]]]:
    """Self-attention; with ``cache`` acts as prefill (S>1) or decode (S==1)."""
    cfg = ctx.cfg
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = dense(ctx, p["q"], x, "attn_qkv").reshape(b, s, h, hd)
    k = dense(ctx, p["k"], x, "attn_qkv").reshape(b, s, kv, hd)
    v = dense(ctx, p["v"], x, "attn_qkv").reshape(b, s, kv, hd)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # 'qseq' gives context-parallel attention when 'heads' can't take the
    # model axis (resolver priority): scores/softmax shard over query-seq.
    q = shard(q, "batch", "qseq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")

    impl = cfg.attn_impl
    if impl not in ("einsum", "kernel"):
        raise ValueError(f"attn_impl must be 'einsum' or 'kernel', "
                         f"got {impl!r}")
    if cache is None:
        out = _sdpa(q, k, v, _causal_mask(s, s) if causal else None)
        new_cache = None
    else:
        start = cache["len"]                     # (B,) per-sequence lengths
        int8_cache = "ks" in cache
        if int8_cache:
            kq, ks_ = _kv_quant(k)
            vq, vs_ = _kv_quant(v)
            rows_new = {"k": kq.reshape(b, s, kv * hd),
                        "v": vq.reshape(b, s, kv * hd), "ks": ks_, "vs": vs_}
        else:
            rows_new = {"k": k.reshape(b, s, kv * hd),
                        "v": v.reshape(b, s, kv * hd)}
        new_cache = {n: write_rows(cache, n, u, start)
                     for n, u in rows_new.items()}
        written = {**cache, **new_cache}
        new_cache["len"] = start + s
        if impl == "kernel":
            # the kernels read the written stack (or plain leaves) as is
            ck, cv = written["k"], written["v"]
            cks, cvs = written.get("ks"), written.get("vs")
        else:
            ck, cv = _view(written, "k"), _view(written, "v")
            if int8_cache:
                cks, cvs = _view(written, "ks"), _view(written, "vs")
        # the merged minor dim shards in whole KV heads, as (KV, D) did; a
        # stack's leading layer dim is not sharded
        lead = (None,) * (ck.ndim - 3)
        ck = shard(ck, *lead, "batch", "seq", ("kv_heads", hd))
        cv = shard(cv, *lead, "batch", "seq", ("kv_heads", hd))
        t = ck.shape[-2]
        if impl == "kernel" and s == 1:
            # length-aware Pallas decode: O(len[b]) KV blocks per row, int8
            # dequantised in-kernel (the cache never round-trips through a
            # full-precision HBM copy). lens counts the freshly written key.
            out = decode_attention(q[:, 0], ck, cv, start + 1, ks=cks,
                                   vs=cvs, **_at(cache))[:, None]
        elif impl == "kernel":
            # chunked/bucketed prefill via GQA-native flash (causal block
            # pruning + per-row start offsets); int8 stays int8 in HBM and
            # dequantises in-kernel, exactly as the decode kernel does.
            out = _flash_prefill(q, ck, cv, start, ks=cks, vs=cvs,
                                 **_at(cache))
        elif int8_cache:
            # einsum fallback: scales fold into logits/probs — no f32
            # dequantised copy of the whole (B, T, KV, D) cache per step
            out = _sdpa_int8(q, ck.reshape(b, t, kv, hd), cks,
                             cv.reshape(b, t, kv, hd), cvs,
                             _cached_mask(start, s, t))
        else:
            out = _sdpa(q, ck.reshape(b, t, kv, hd),
                        cv.reshape(b, t, kv, hd), _cached_mask(start, s, t))

    out = out.reshape(b, s, h * hd)
    return dense(ctx, p["o"], out, "attn_out"), new_cache


# ------------------------------------------------------------- cross-attn

def init_cross(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    pq, aq = _init_dense(ks[0], d, h * hd, ("embed", "heads"), dtype=dtype)
    pk, ak = _init_dense(ks[1], d, kv * hd, ("embed", "kv_heads"), dtype=dtype)
    pv, av = _init_dense(ks[2], d, kv * hd, ("embed", "kv_heads"), dtype=dtype)
    po, ao = _init_dense(ks[3], h * hd, d, ("heads", "embed"), dtype=dtype)
    return {"q": pq, "k": pk, "v": pv, "o": po}, {"q": aq, "k": ak, "v": av, "o": ao}


def cross_kv(ctx: Ctx, p: Params, memory: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Precompute encoder K/V once per request (whisper decode)."""
    cfg = ctx.cfg
    b, t, _ = memory.shape
    kv, hd = cfg.n_kv_heads, cfg.hd
    k = dense(ctx, p["k"], memory, "cross_qkv").reshape(b, t, kv, hd)
    v = dense(ctx, p["v"], memory, "cross_qkv").reshape(b, t, kv, hd)
    return {"k": k, "v": v}


def cross_attention(ctx: Ctx, p: Params, x: jnp.ndarray,
                    kv: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    cfg = ctx.cfg
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    q = dense(ctx, p["q"], x, "cross_qkv").reshape(b, s, h, hd)
    out = _sdpa(q, kv["k"], kv["v"], None).reshape(b, s, h * hd)
    return dense(ctx, p["o"], out, "cross_out")


# ----------------------------------------------------------------- MLA

def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    a = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qh = h * (a.nope_head_dim + a.rope_head_dim)
    ks = jax.random.split(key, 6)
    p, ax = {}, {}
    if a.q_lora is None:
        # q: d -> h*(nope+rope) in one projection
        p["q"], ax["q"] = _init_dense(ks[0], d, qh, ("embed", "heads"), dtype=dtype)
    else:
        # q: d -> q_lora (RMS-normalised) -> h*(nope+rope)
        p["dq"], ax["dq"] = _init_dense(ks[0], d, a.q_lora, ("embed", "state"), dtype=dtype)
        p["q_norm"], ax["q_norm"] = {"g": jnp.ones((a.q_lora,), dtype)}, {"g": ("state",)}
        p["uq"], ax["uq"] = _init_dense(ks[1], a.q_lora, qh, ("state", "heads"), dtype=dtype)
    # kv: d -> kv_lora (+ shared rope dims); the latent is RMS-normalised
    # before it is cached or up-projected
    p["dkv"], ax["dkv"] = _init_dense(ks[2], d, a.kv_lora + a.rope_head_dim,
                                      ("embed", "state"), dtype=dtype)
    p["kv_norm"], ax["kv_norm"] = {"g": jnp.ones((a.kv_lora,), dtype)}, {"g": ("state",)}
    # up: kv_lora -> h*(nope) for K and h*(v_head) for V
    p["uk"], ax["uk"] = _init_dense(ks[3], a.kv_lora, h * a.nope_head_dim,
                                    ("state", "heads"), dtype=dtype)
    p["uv"], ax["uv"] = _init_dense(ks[4], a.kv_lora, h * a.v_head_dim,
                                    ("state", "heads"), dtype=dtype)
    p["o"], ax["o"] = _init_dense(ks[5], h * a.v_head_dim, d, ("heads", "embed"),
                                  dtype=dtype)
    return p, ax


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict[str, Any]:
    a = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, a.kv_lora), dtype),
        # the rope keys are kept (R, T) per row, positions minor: as
        # (T, R) their 64 lanes would pad to 128
        "krope": jnp.zeros((batch, a.rope_head_dim, max_len), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-1/2``, times YaRN's ``mscale(factor,
    mscale_all_dim)^2`` under rope scaling (DeepSeek-V2's attention)."""
    a = cfg.mla
    scale = (a.nope_head_dim + a.rope_head_dim) ** -0.5
    sc = cfg.rope_scaling
    if sc is not None and sc.mscale_all_dim:
        scale *= yarn_mscale(sc.factor, sc.mscale_all_dim) ** 2
    return scale


def mla_attention(
    ctx: Ctx,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[Dict[str, Any]] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]]]:
    """Multi-head Latent Attention with compressed-KV cache (deepseek-v2).

    With ``attn_impl="kernel"`` cached attention runs *absorbed*
    (DeepSeek-V2 §2.1.2): W_uk folds into the query and W_uv into the
    output, so scores and values are taken straight from the latent cache
    by the length-aware kernels — ``mla_decode`` for one query per row,
    ``mla_prefill`` for a chunk. The einsum path up-projects the cache.
    """
    cfg = ctx.cfg
    a = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    eps, theta, rs = cfg.norm_eps, cfg.rope_theta, cfg.rope_scaling

    if a.q_lora is None:
        q = dense(ctx, p["q"], x, "attn_qkv")
    else:
        cq = rmsnorm(p["q_norm"], dense(ctx, p["dq"], x, "attn_qkv"), eps)
        q = dense(ctx, p["uq"], cq, "attn_qkv")
    q = q.reshape(b, s, h, a.nope_head_dim + a.rope_head_dim)
    q_nope, q_rope = jnp.split(q, [a.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, theta, rs)

    dkv = dense(ctx, p["dkv"], x, "attn_qkv")
    ckv, krope = jnp.split(dkv, [a.kv_lora], axis=-1)
    ckv = rmsnorm(p["kv_norm"], ckv, eps)
    krope = apply_rope(krope[:, :, None, :], positions, theta, rs)[:, :, 0, :]
    krope_t = jnp.swapaxes(krope, 1, 2)          # (B, R, S), as cached

    if cache is not None:
        start = cache["len"]                     # (B,) per-sequence lengths
        new_cache = {"ckv": write_rows(cache, "ckv", ckv, start),
                     "krope": write_rows(cache, "krope", krope_t, start,
                                     seq_axis=3)}
        written = {**cache, **new_cache}
        new_cache["len"] = start + s
        t = cache["ckv"].shape[-2]
        if cfg.attn_impl != "kernel":
            ckv_all, krope_all = (_view(written, "ckv"),
                                  _view(written, "krope"))
    else:
        start = jnp.zeros((b,), jnp.int32)
        ckv_all, krope_all, new_cache, t = ckv, krope_t, None, s

    scale = mla_softmax_scale(cfg)
    if cache is not None and (s == 1 or cfg.attn_impl == "kernel"):
        # absorbed: O(t * kv_lora) per head, never up-projecting the cache
        # (which would be ~100x more FLOPs per decode step at 32k ctx)
        wuk = p["uk"]["w"].astype(x.dtype).reshape(a.kv_lora, h, a.nope_head_dim)
        wuv = p["uv"]["w"].astype(x.dtype).reshape(a.kv_lora, h, a.v_head_dim)
        q_lat = jnp.einsum("bshd,lhd->bshl", q_nope, wuk)          # (b,s,h,lora)
        if cfg.attn_impl == "kernel" and s == 1:
            with jax.named_scope("mla.decode"):
                # 512-key blocks: a latent row is one lane-dense 1 KB
                # read, so few large blocks keep the grid short
                out_lat = mla_decode_attention(
                    q_lat[:, 0], q_rope[:, 0], written["ckv"],
                    written["krope"], start + 1, scale=scale, block_k=512,
                    **_at(cache))[:, None]                 # (b,1,h,lora)
        elif cfg.attn_impl == "kernel":
            with jax.named_scope("mla.prefill"):
                out_lat = mla_prefill_attention(
                    q_lat, q_rope, written["ckv"], written["krope"], start,
                    scale=scale, **_at(cache))
        else:
            logits = (
                jnp.einsum("bshl,btl->bhst", q_lat, ckv_all)
                + jnp.einsum("bshd,bdt->bhst", q_rope, krope_all)
            ).astype(jnp.float32) * scale
            logits = jnp.where(_cached_mask(start, s, t), logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
            out_lat = jnp.einsum("bhst,btl->bshl", probs, ckv_all)  # (b,1,h,lora)
        out = jnp.einsum("bshl,lhv->bshv", out_lat, wuv)
    else:
        # train/prefill: up-project the compressed kv once
        k_nope = dense(ctx, p["uk"], ckv_all, "attn_qkv").reshape(b, t, h, a.nope_head_dim)
        v = dense(ctx, p["uv"], ckv_all, "attn_qkv").reshape(b, t, h, a.v_head_dim)
        k_nope = shard(k_nope, "batch", "seq", "heads", "head_dim")
        v = shard(v, "batch", "seq", "heads", "head_dim")
        logits = (
            jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
            + jnp.einsum("bshd,bdt->bhst", q_rope, krope_all)
        ).astype(jnp.float32) * scale
        logits = jnp.where(_cached_mask(start, s, t), logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, v)

    out = out.reshape(b, s, h * a.v_head_dim)
    return dense(ctx, p["o"], out, "attn_out"), new_cache
