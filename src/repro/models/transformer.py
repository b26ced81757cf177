"""Decoder-LM assembly for all assigned families.

Families:
  * dense / vlm — GQA + SwiGLU pre-norm blocks (llama pattern); vlm prepends
    precomputed patch embeddings (stub vision frontend per assignment).
  * moe        — attention (GQA or MLA) + MoE FFN.
  * ssm        — Mamba2/SSD blocks (attention-free).
  * hybrid     — zamba2: scanned super-blocks of (attn_period-1) Mamba2 layers
                 + one *shared-weight* attention+MLP layer.
  * encdec     — whisper: bidirectional encoder over stub frame embeddings +
                 causal decoder with cross-attention.

All layer stacks use jax.lax.scan over stacked parameters (compile time is
O(1) in depth — essential for the 95-layer/512-chip dry-run) with optional
jax.checkpoint (remat) on the block body. Three phases everywhere:
train (no cache), prefill (cache fill), decode (1 token vs cache).

Cached GQA attention honors ``cfg.attn_impl`` (DESIGN.md §11): the default
"einsum" reference, or "kernel" — the length-aware Pallas decode kernel +
causal-pruned flash prefill, scanned per layer like any other block body
(the pallas_call lowers inside lax.scan/remat in both compiled and
interpret modes). Train-phase and cross-attention stay on einsum.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    Ctx,
    Params,
    embed,
    gelu_mlp,
    init_embedding,
    init_gelu_mlp,
    init_layernorm,
    init_rmsnorm,
    init_swiglu,
    layernorm,
    rmsnorm,
    sinusoidal_positions,
    swiglu,
    unembed,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def _stack_init(init_one, n: int, key):
    """vmap an init over n layers -> params stacked on a leading 'layers' axis."""
    keys = jax.random.split(key, n)
    stacked = jax.vmap(lambda k: init_one(k)[0])(keys)
    axes = init_one(key)[1]  # python-side structure (dead compute under trace)
    axes = jax.tree.map(lambda t: ("layers",) + tuple(t), axes, is_leaf=_is_axes_leaf)
    return stacked, axes


def scan_or_loop(cfg: ModelConfig, body, init, xs, length: int):
    """lax.scan when cfg.scan_layers (O(1) HLO in depth) else an unrolled
    python loop (used by the dry-run depth-extrapolation variants, where XLA
    cost_analysis must see every layer instance)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, init, xs)
    carry = init
    ys = []
    for i in range(length):
        xi = jax.tree.map(lambda t: t[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    return carry, jax.tree.map(lambda *ts: jnp.stack(ts), *ys)


# --------------------------------------------------------------------------
# per-family blocks
# --------------------------------------------------------------------------


def _init_dense_block(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    pa, aa = attn.init_gqa(k1, cfg, dt)
    pm, am = init_swiglu(k2, cfg.d_model, cfg.d_ff, dt)
    pn1, an1 = init_rmsnorm(cfg.d_model, dt)
    pn2, an2 = init_rmsnorm(cfg.d_model, dt)
    return ({"attn": pa, "mlp": pm, "n1": pn1, "n2": pn2},
            {"attn": aa, "mlp": am, "n1": an1, "n2": an2})


def _use_fused_layer(ctx: Ctx, x, cache) -> bool:
    """Route a decode-shaped dense block through the per-layer megakernel
    (kernels/fused_step.py, DESIGN.md §15): one Pallas program chains
    norm + QKV + rope + length-aware attention + O + SwiGLU with the
    activations VMEM-resident. Only for shapes/modes the kernel replicates
    bit-for-bit: single-token cached decode, no guard/fault instrumentation,
    ideal-digital ("off") or deployed sim matmuls (the behavioural
    ``use_kernel=False`` sim path draws ``jax.random.normal`` noise, which
    has no in-kernel equivalent — fused sim equality is against the
    ``use_kernel=True`` Threefry stream)."""
    cfg = ctx.cfg
    if not (cfg.fuse_layer and cache is not None and x.shape[1] == 1):
        return False
    if ctx.guard is not None or ctx.fault is not None or not cfg.use_rope:
        return False
    if x.dtype != jnp.float32:
        return False
    if ctx.mode == "off":
        return True
    return (ctx.mode == "sim" and ctx.deployed and ctx.key is not None
            and cfg.cim.act_clip_sigmas > 0)


def _dense_block(ctx: Ctx, p: Params, x, positions, cache):
    if _use_fused_layer(ctx, x, cache):
        from repro.kernels.fused_step import fused_dense_layer

        return fused_dense_layer(ctx, p, x, cache)
    h, new_cache = attn.gqa_attention(
        ctx, p["attn"], rmsnorm(p["n1"], x, ctx.cfg.norm_eps), positions, cache)
    x = x + h
    x = x + swiglu(ctx, p["mlp"], rmsnorm(p["n2"], x, ctx.cfg.norm_eps))
    return shard(x, "batch", "seq", "embed"), new_cache


def _init_moe_block(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    if cfg.mla is not None:
        pa, aa = attn.init_mla(k1, cfg, dt)
    else:
        pa, aa = attn.init_gqa(k1, cfg, dt)
    pm, am = moe_mod.init_moe(k2, cfg, dt)
    pn1, an1 = init_rmsnorm(cfg.d_model, dt)
    pn2, an2 = init_rmsnorm(cfg.d_model, dt)
    return ({"attn": pa, "moe": pm, "n1": pn1, "n2": pn2},
            {"attn": aa, "moe": am, "n1": an1, "n2": an2})


def _init_lead_block(key, cfg: ModelConfig):
    """A leading dense block of a ``moe`` model: its attention, and a dense
    SwiGLU of width ``dense_d_ff`` in place of the expert layer."""
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    if cfg.mla is not None:
        pa, aa = attn.init_mla(k1, cfg, dt)
    else:
        pa, aa = attn.init_gqa(k1, cfg, dt)
    pm, am = init_swiglu(k2, cfg.d_model, cfg.dense_d_ff, dt)
    pn1, an1 = init_rmsnorm(cfg.d_model, dt)
    pn2, an2 = init_rmsnorm(cfg.d_model, dt)
    return ({"attn": pa, "mlp": pm, "n1": pn1, "n2": pn2},
            {"attn": aa, "mlp": am, "n1": an1, "n2": an2})


def _moe_attention(ctx: Ctx, p: Params, x, positions, cache):
    xn = rmsnorm(p["n1"], x, ctx.cfg.norm_eps)
    if ctx.cfg.mla is not None:
        h, new_cache = attn.mla_attention(ctx, p["attn"], xn, positions, cache)
    else:
        h, new_cache = attn.gqa_attention(ctx, p["attn"], xn, positions, cache)
    return x + h, new_cache


def _lead_block(ctx: Ctx, p: Params, x, positions, cache):
    x, new_cache = _moe_attention(ctx, p, x, positions, cache)
    x = x + swiglu(ctx, p["mlp"], rmsnorm(p["n2"], x, ctx.cfg.norm_eps))
    return shard(x, "batch", "seq", "embed"), new_cache


def _moe_block(ctx: Ctx, p: Params, x, positions, cache):
    x, new_cache = _moe_attention(ctx, p, x, positions, cache)
    # serving (cached) forwards route dropless so a token's experts cannot
    # depend on how many tokens share the fixed-shape program — chunked
    # prefill stays token-for-token equal to whole-prompt prefill
    x = x + moe_mod.moe_block(ctx, p["moe"],
                              rmsnorm(p["n2"], x, ctx.cfg.norm_eps),
                              dropless=cache is not None)
    return shard(x, "batch", "seq", "embed"), new_cache


def _init_ssm_block(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    pm, am = ssm_mod.init_mamba2(key, cfg, dt)
    pn, an = init_rmsnorm(cfg.d_model, dt)
    return {"mamba": pm, "n": pn}, {"mamba": am, "n": an}


def _ssm_block(ctx: Ctx, p: Params, x, positions, cache):
    h, new_cache = ssm_mod.mamba2_block(
        ctx, p["mamba"], rmsnorm(p["n"], x, ctx.cfg.norm_eps), cache)
    x = x + h
    return shard(x, "batch", "seq", "embed"), new_cache


_BLOCKS = {
    "dense": (_init_dense_block, _dense_block),
    "vlm": (_init_dense_block, _dense_block),
    "moe": (_init_moe_block, _moe_block),
    "ssm": (_init_ssm_block, _ssm_block),
}


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    """Returns (params, logical-axes tree) for any LM family."""
    dt = _dtype(cfg)
    keys = jax.random.split(key, 4)
    p: Params = {}
    a: Params = {}
    if cfg.vocab_size:
        p["embed"], a["embed"] = init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dt)
    p["final_norm"], a["final_norm"] = init_rmsnorm(cfg.d_model, dt)

    k_head, k_lead = jax.random.split(keys[3])
    if not cfg.tie_embeddings and cfg.vocab_size:
        p["head"], a["head"] = init_embedding(k_head, cfg.vocab_size,
                                              cfg.d_model, dt)
    fam = cfg.family
    if fam in _BLOCKS:
        init_one = _BLOCKS[fam][0]
        p["blocks"], a["blocks"] = _stack_init(
            lambda k: init_one(k, cfg), cfg.n_layers - cfg.n_dense_layers,
            keys[1])
        if cfg.n_dense_layers:
            p["lead_blocks"], a["lead_blocks"] = _stack_init(
                lambda k: _init_lead_block(k, cfg), cfg.n_dense_layers, k_lead)
    elif fam == "hybrid":
        n_super = cfg.n_layers // cfg.attn_period
        n_mamba = cfg.attn_period - 1
        p["mamba_blocks"], a["mamba_blocks"] = _stack_init(
            lambda k: _stack_init(lambda kk: _init_ssm_block(kk, cfg), n_mamba, k),
            n_super, keys[1])
        p["shared_attn"], a["shared_attn"] = _init_dense_block(keys[2], cfg)
    elif fam == "encdec":
        def init_enc(k):
            k1, k2 = jax.random.split(k)
            pa, aa = attn.init_gqa(k1, cfg, dt)
            pm, am = init_gelu_mlp(k2, cfg.d_model, cfg.d_ff, dt)
            pn1, an1 = init_layernorm(cfg.d_model, dt)
            pn2, an2 = init_layernorm(cfg.d_model, dt)
            return ({"attn": pa, "mlp": pm, "n1": pn1, "n2": pn2},
                    {"attn": aa, "mlp": am, "n1": an1, "n2": an2})

        def init_dec(k):
            k1, k2, k3 = jax.random.split(k, 3)
            pa, aa = attn.init_gqa(k1, cfg, dt)
            pc, ac = attn.init_cross(k2, cfg, dt)
            pm, am = init_gelu_mlp(k3, cfg.d_model, cfg.d_ff, dt)
            pn1, an1 = init_layernorm(cfg.d_model, dt)
            pn2, an2 = init_layernorm(cfg.d_model, dt)
            pn3, an3 = init_layernorm(cfg.d_model, dt)
            return ({"attn": pa, "cross": pc, "mlp": pm,
                     "n1": pn1, "n2": pn2, "n3": pn3},
                    {"attn": aa, "cross": ac, "mlp": am,
                     "n1": an1, "n2": an2, "n3": an3})

        p["enc_blocks"], a["enc_blocks"] = _stack_init(init_enc, cfg.n_enc_layers, keys[1])
        p["dec_blocks"], a["dec_blocks"] = _stack_init(init_dec, cfg.n_layers, keys[2])
        p["enc_norm"], a["enc_norm"] = init_layernorm(cfg.d_model, dt)
    else:
        raise ValueError(f"family {fam} not handled here (vit lives in models/vit.py)")
    return p, a


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    """Stacked per-layer decoding caches (leading 'layers' axis)."""
    dt = _dtype(cfg)

    def stack(make, n):
        one = make()
        return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), one)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        return stack(lambda: attn.init_gqa_cache(cfg, batch, max_len, dt), cfg.n_layers)
    if fam == "moe":
        init = attn.init_mla_cache if cfg.mla is not None else attn.init_gqa_cache
        n_lead = cfg.n_dense_layers
        body = stack(lambda: init(cfg, batch, max_len, dt), cfg.n_layers - n_lead)
        if not n_lead:
            return body
        # one stacked cache per layer group, each scanned in place
        return {"lead": stack(lambda: init(cfg, batch, max_len, dt), n_lead),
                "blocks": body}
    if fam == "ssm":
        return stack(lambda: ssm_mod.init_ssm_cache(cfg, batch, dt), cfg.n_layers)
    if fam == "hybrid":
        n_super = cfg.n_layers // cfg.attn_period
        n_mamba = cfg.attn_period - 1
        return {
            "mamba": stack(lambda: stack(lambda: ssm_mod.init_ssm_cache(cfg, batch, dt),
                                         n_mamba), n_super),
            "attn": stack(lambda: attn.init_gqa_cache(cfg, batch, max_len, dt), n_super),
        }
    if fam == "encdec":
        return {
            "self": stack(lambda: attn.init_gqa_cache(cfg, batch, max_len, dt), cfg.n_layers),
            "cross": None,  # filled by prefill (encoder K/V per decoder layer)
        }
    raise ValueError(fam)


# --------------------------------------------------------------------------
# slot-batched cache helpers (serving engine, DESIGN.md §10)
# --------------------------------------------------------------------------
#
# Stacked caches put the batch ("slot") axis right after the layer-stack
# axes: one leading 'layers' axis everywhere except the hybrid family's
# mamba sub-tree, which stacks twice (super-block x inner layer).


def _slot_axis(path) -> int:
    if any(getattr(p, "key", None) == "mamba" for p in path):
        return 2
    return 1


def _is_len(path) -> bool:
    return bool(path) and getattr(path[-1], "key", None) == "len"


# the leaves of an attention cache group that the attention layers address
# in place by (layer, row) (models/attention.py)
_ATTN_LEAVES = frozenset(("k", "v", "ks", "vs", "ckv", "krope"))


def _attn_group(caches) -> bool:
    return (isinstance(caches, dict) and "len" in caches
            and set(caches) - {"len"} <= _ATTN_LEAVES)


def cache_in_place(caches) -> bool:
    """Whether a forward over this stacked cache addresses it in place: it
    holds attention leaves alone (the dense, vlm and moe families, a
    ``moe`` model with leading dense layers as one such group per layer
    group). Recurrent ``conv``/``state`` leaves and the hybrid and encdec
    caches are sliced per layer and per slot instead (``take_slot``/
    ``put_slot``)."""
    if isinstance(caches, dict) and set(caches) == {"lead", "blocks"}:
        return all(_attn_group(c) for c in caches.values())
    return _attn_group(caches)


def take_slot(caches, slot) -> Any:
    """Batch-1 slice of one slot row from a stacked slot-cache pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.lax.dynamic_slice_in_dim(
            leaf, slot, 1, axis=_slot_axis(path)),
        caches)


def put_slot(caches, slot_caches, slot) -> Any:
    """Write a batch-1 slot cache back into row ``slot`` of the stacked
    cache. The inverse of ``take_slot``; never re-allocates the big cache
    (a pure dynamic_update_slice per leaf, in-place under donation)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, big, one: jax.lax.dynamic_update_slice_in_dim(
            big, one.astype(big.dtype), slot, axis=_slot_axis(path)),
        caches, slot_caches)


def set_cache_lens(caches, value, rows=None) -> Any:
    """Overwrite every per-sequence 'len' leaf with ``value`` (broadcast),
    or, given ``rows`` (R,), only those slot rows of a stacked cache."""
    def put(path, leaf):
        if not _is_len(path):
            return leaf
        if rows is None:
            return jnp.broadcast_to(jnp.asarray(value, leaf.dtype),
                                    leaf.shape)
        return leaf.at[:, rows].set(jnp.asarray(value, leaf.dtype))

    return jax.tree_util.tree_map_with_path(put, caches)


def mask_cache_advance(new_caches, old_caches, active) -> Any:
    """Freeze inactive slots' cache state after a fused decode step.

    active: (B,) bool. Attention K/V leaves keep the new value — inactive
    rows' writes land in junk space (at their frozen ``len``) that the
    per-row masks never expose: the row's next chunk overwrites it, and a
    recycled slot restarts at length 0, so rows past its length are never
    read. SSM ``conv``/``state`` leaves have no such junk space (every
    decode step rolls the window and decays the state in place), so they
    are restored alongside ``len`` — otherwise a slot mid-chunked-prefill
    would have its carried state corrupted by the batch-global decode of
    the *other* slots.
    """

    def fix(path, new, old):
        if _is_len(path):
            return jnp.where(active[None, :], new, old)
        if bool(path) and getattr(path[-1], "key", None) in ("conv", "state"):
            ax = _slot_axis(path)
            shape = [1] * new.ndim
            shape[ax] = active.shape[0]
            return jnp.where(active.reshape(shape), new, old)
        return new

    return jax.tree_util.tree_map_with_path(fix, new_caches, old_caches)


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------


def _embed_input(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    dt = _dtype(cfg)
    x = embed(params["embed"], batch["tokens"], dt)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        x = jnp.concatenate([batch["patch_embeds"].astype(dt), x], axis=1)
    return shard(x, "batch", "seq", "embed")


def _scan_blocks(ctx: Ctx, blocks: Params, block_fn, x, positions, caches,
                 first: int = 0, rows=None):
    """Scan ``block_fn`` over stacked ``blocks``, the first of them layer
    ``first`` of the model (its key and its rows in the guard counts);
    ``caches`` is the group's own stack, its layer 0 that first block's.

    ``rows`` (B,) are the cache rows of ``x``'s batch rows (None: the
    cache's rows in order). An attention cache rides in the carry whole:
    each block gets the stack with its layer and rows and writes only its
    new rows, in place (``models/attention.py``). Other caches, and a
    dense block the f32 megakernel serves, get their layer's slice, which
    is written back after the block (``ctx.cache_sliced`` records it)."""
    cfg = ctx.cfg
    n = jax.tree.leaves(blocks)[0].shape[0]
    base_key = ctx.key if ctx.key is not None else jax.random.PRNGKey(0)
    guard = ctx.guard is not None
    moe_log = ctx.moe_log is not None
    b = x.shape[0]
    in_place = (_attn_group(caches) and not (
        block_fn is _dense_block and _use_fused_layer(ctx, x, caches)))
    if caches is not None and not in_place:
        ctx.cache_sliced = True

    def take(c, idx):
        c = jax.lax.dynamic_index_in_dim(c, idx, keepdims=False)
        return c if rows is None else c[rows]

    def put(c, u, idx):
        if rows is None:
            return jax.lax.dynamic_update_index_in_dim(c, u, idx, 0)
        return c.at[idx, rows].set(u)

    def body(carry, xs):
        # the stacked caches ride in the carry: as scan xs/ys they would be
        # restacked into a second whole-cache buffer, then copied into the
        # donated one
        h, caches = carry
        layer_p, idx = xs
        layer = first + idx
        if caches is None:
            layer_cache = None
        elif in_place:
            layer_cache = {**caches, "len": take(caches["len"], idx),
                           "layer": idx, "rows": rows}
        else:
            layer_cache = jax.tree.map(lambda c: take(c, idx), caches)
        lctx = dataclasses.replace(
            ctx, key=jax.random.fold_in(base_key, layer), counter=0)
        if guard:
            # fresh scratch lists per layer; guarded_dense appends (B,)
            # trip/hard counts which we drain into the scan ys -> (L, B)
            lctx.trip_log, lctx.hard_log = [], []
            if ctx.pin_layers is not None:
                lctx.pin_rows = jnp.take(ctx.pin_layers, layer, axis=1)
        if moe_log:
            lctx.moe_log = []
        h, new_cache = block_fn(lctx, layer_p, h, positions, layer_cache)
        if in_place:
            caches = {**new_cache,
                      "len": put(caches["len"], new_cache["len"], idx)}
        elif caches is not None:
            caches = jax.tree.map(lambda c, u: put(c, u, idx), caches,
                                  new_cache)
        ys = {}
        if guard:
            zero = jnp.zeros((b,), jnp.int32)
            ys["trips"] = sum(lctx.trip_log, zero) if lctx.trip_log else zero
            ys["hard"] = sum(lctx.hard_log, zero) if lctx.hard_log else zero
        if moe_log and lctx.moe_log:
            ys["moe"] = jax.tree.map(lambda *v: sum(v), *lctx.moe_log)
        return (h, caches), ys

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, new_caches), ys = scan_or_loop(cfg, body, (x, caches),
                                       (blocks, jnp.arange(n)), n)
    # side-channel outputs: read off the Ctx by the engine closures at
    # trace time (the Ctx is a fresh python object per traced call); a
    # later layer group (first > 0) adds to what the first one left
    if guard:
        if first:
            ys["trips"] = jnp.concatenate([ctx.guard_trips, ys["trips"]])
            ys["hard"] = jnp.concatenate([ctx.guard_hard, ys["hard"]])
        ctx.guard_trips, ctx.guard_hard = ys["trips"], ys["hard"]
    if not first:
        ctx.moe_rows = None
    if "moe" in ys:
        rows = jax.tree.map(lambda v: jnp.sum(v, axis=0), ys["moe"])
        if ctx.moe_rows is not None:
            rows = jax.tree.map(jnp.add, ctx.moe_rows, rows)
        ctx.moe_rows = rows
    return x, new_caches


def forward(params: Params, batch: Dict[str, Any], cfg: ModelConfig,
            ctx: Optional[Ctx] = None, caches=None,
            rows=None) -> Tuple[jnp.ndarray, Any]:
    """Forward to logits. train: caches=None; prefill/decode: caches pytree.

    ``rows`` (B,) int32: the cache rows (slots) of the batch's rows, for a
    batch narrower than the stacked cache — a prefill chunk of one slot
    runs on the whole stack with ``rows=[slot]``. None: row b is cache
    row b."""
    ctx = ctx or Ctx.make(cfg)
    if cfg.family in ("encdec", "hybrid"):
        if rows is not None:
            raise ValueError(f"family {cfg.family!r} takes its cache one "
                             f"slot at a time, not by rows")
        fwd = _encdec_forward if cfg.family == "encdec" else _hybrid_forward
        return fwd(params, batch, cfg, ctx, caches)

    x = _embed_input(cfg, params, batch)
    b, s, _ = x.shape
    if caches is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    else:
        positions = _cache_positions(cfg, caches, b, s, rows)
    block_fn = _BLOCKS[cfg.family][1]
    if cfg.n_dense_layers:
        # the leading dense blocks, then the rest, each over its own cache
        lead_c, body_c = ((None, None) if caches is None
                          else (caches["lead"], caches["blocks"]))
        x, lead = _scan_blocks(ctx, params["lead_blocks"], _lead_block, x,
                               positions, lead_c, rows=rows)
        x, body = _scan_blocks(ctx, params["blocks"], block_fn, x, positions,
                               body_c, first=cfg.n_dense_layers, rows=rows)
        new_caches = None if caches is None else {"lead": lead, "blocks": body}
    else:
        x, new_caches = _scan_blocks(ctx, params["blocks"], block_fn, x,
                                     positions, caches, rows=rows)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(ctx, _head(params), x)
    return shard(logits, "batch", "seq", "vocab"), new_caches


def _head(params: Params) -> Params:
    """The logits head: its own matrix, or the embedding where tied."""
    return params.get("head", params["embed"])


def _cache_len(cfg: ModelConfig, caches) -> jnp.ndarray:
    """Per-sequence lengths (B,) already written into the cache."""
    if cfg.family == "ssm":
        batch = jax.tree.leaves(caches)[0].shape[1]
        return jnp.zeros((batch,), jnp.int32)  # state caches carry no length
    if cfg.family == "hybrid":
        return caches["attn"]["len"][0]
    if cfg.family == "encdec":
        return caches["self"]["len"][0]
    if cfg.n_dense_layers:
        return caches["blocks"]["len"][0]
    return caches["len"][0]


def _cache_positions(cfg: ModelConfig, caches, b: int, s: int,
                     rows=None) -> jnp.ndarray:
    """(B, S) absolute positions for the next ``s`` tokens of every row
    (of cache rows ``rows``, where given)."""
    start = _cache_len(cfg, caches)
    if rows is not None:
        start = start[rows]
    return jnp.broadcast_to(jnp.arange(s)[None] + start[:, None], (b, s))


def _hybrid_forward(params, batch, cfg, ctx, caches=None):
    x = _embed_input(cfg, params, batch)
    b, s, _ = x.shape
    if caches is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    else:
        positions = _cache_positions(cfg, caches, b, s)
    n_super = cfg.n_layers // cfg.attn_period
    n_mamba = cfg.attn_period - 1
    base_key = ctx.key if ctx.key is not None else jax.random.PRNGKey(0)

    def body(h, xs):
        super_p, super_cache, idx = xs
        lctx = dataclasses.replace(ctx, key=jax.random.fold_in(base_key, idx), counter=0)
        new_mamba, new_attn = [], None
        for j in range(n_mamba):
            mp = jax.tree.map(lambda t: t[j], super_p)
            mc = None if super_cache is None else jax.tree.map(
                lambda t: t[j], super_cache["mamba"])
            h, nc = _ssm_block(lctx, mp, h, positions, mc)
            new_mamba.append(nc)
        ac = None if super_cache is None else super_cache["attn"]
        h, new_attn = _dense_block(lctx, params["shared_attn"], h, positions, ac)
        new_cache = None
        if super_cache is not None:
            new_cache = {
                "mamba": jax.tree.map(lambda *ts: jnp.stack(ts), *new_mamba),
                "attn": new_attn,
            }
        return h, new_cache

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = (params["mamba_blocks"],
          None if caches is None else caches,
          jnp.arange(n_super))
    x, new_caches = scan_or_loop(cfg, body, x, xs, n_super)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(ctx, _head(params), x)
    return shard(logits, "batch", "seq", "vocab"), new_caches


def encode(params: Params, frames: jnp.ndarray, cfg: ModelConfig, ctx: Ctx) -> jnp.ndarray:
    """Whisper encoder over stub frame embeddings -> memory (B, T, d)."""
    dt = _dtype(cfg)
    mem = frames.astype(dt)
    mem = mem + sinusoidal_positions(mem.shape[1], cfg.d_model).astype(dt)[None]
    mem = shard(mem, "batch", "frames", "embed")
    base_key = ctx.key if ctx.key is not None else jax.random.PRNGKey(0)
    enc_pos = jnp.broadcast_to(jnp.arange(mem.shape[1])[None], mem.shape[:2])

    def enc_body(h, xs):
        layer_p, idx = xs
        lctx = dataclasses.replace(ctx, key=jax.random.fold_in(base_key, idx), counter=0)
        hh, _ = attn.gqa_attention(lctx, layer_p["attn"],
                                   layernorm(layer_p["n1"], h, cfg.norm_eps),
                                   enc_pos, None, causal=False)
        h = h + hh
        h = h + gelu_mlp(lctx, layer_p["mlp"], layernorm(layer_p["n2"], h, cfg.norm_eps))
        return h, None

    if cfg.remat:
        enc_body = jax.checkpoint(enc_body)
    mem, _ = scan_or_loop(cfg, enc_body, mem,
                          (params["enc_blocks"], jnp.arange(cfg.n_enc_layers)),
                          cfg.n_enc_layers)
    return layernorm(params["enc_norm"], mem, cfg.norm_eps)


def _encdec_forward(params, batch, cfg, ctx, caches=None):
    dt = _dtype(cfg)
    if caches is not None and caches.get("cross") is not None:
        cross = caches["cross"]          # precomputed at prefill
        mem = None
    else:
        mem = encode(params, batch["frames"], cfg, ctx)
        cross = None

    x = embed(params["embed"], batch["tokens"], dt)
    b, s, _ = x.shape
    if caches is not None:
        positions = _cache_positions(cfg, caches, b, s)        # (B, S)
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = x + jax.vmap(lambda p: sinusoidal_positions(p, cfg.d_model))(
        positions).astype(dt)
    x = shard(x, "batch", "seq", "embed")
    base_key = ctx.key if ctx.key is not None else jax.random.PRNGKey(0)

    def dec_body(h, xs):
        layer_p, self_cache, cross_kv_l, idx = xs
        lctx = dataclasses.replace(ctx, key=jax.random.fold_in(base_key, 1000 + idx),
                                   counter=0)
        hh, new_self = attn.gqa_attention(
            lctx, layer_p["attn"], layernorm(layer_p["n1"], h, cfg.norm_eps),
            positions, self_cache)
        h = h + hh
        kv = cross_kv_l if cross_kv_l is not None else attn.cross_kv(
            lctx, layer_p["cross"], mem)
        h = h + attn.cross_attention(lctx, layer_p["cross"],
                                     layernorm(layer_p["n2"], h, cfg.norm_eps), kv)
        h = h + gelu_mlp(lctx, layer_p["mlp"], layernorm(layer_p["n3"], h, cfg.norm_eps))
        return h, (new_self, kv)

    if cfg.remat:
        dec_body = jax.checkpoint(dec_body)
    self_caches = None if caches is None else caches["self"]
    xs = (params["dec_blocks"], self_caches, cross, jnp.arange(cfg.n_layers))
    x, ys = scan_or_loop(cfg, dec_body, x, xs, cfg.n_layers)
    new_caches = None
    if caches is not None:
        new_self, new_cross = ys
        new_caches = {"self": new_self, "cross": new_cross}
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(ctx, _head(params), x)
    return shard(logits, "batch", "seq", "vocab"), new_caches


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------


def lm_loss(params: Params, batch: Dict[str, Any], cfg: ModelConfig,
            ctx: Optional[Ctx] = None) -> jnp.ndarray:
    """Next-token cross-entropy + z-loss. labels < 0 are masked."""
    logits, _ = forward(params, batch, cfg, ctx)
    labels = batch["labels"]
    if cfg.family == "vlm":      # image prefix carries no labels
        logits = logits[:, -labels.shape[1]:]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    zloss = 1e-4 * jnp.square(jax.nn.logsumexp(logits, axis=-1))
    return jnp.sum((nll + zloss) * valid) / jnp.maximum(jnp.sum(valid), 1)
