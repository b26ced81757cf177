"""Length-aware Pallas decode-attention kernel (DESIGN.md §11).

Kernel vs ragged oracle and vs the einsum reference path, across block
shapes and ragged ``len`` patterns — including ``len == 0`` recycled slots
(zero output by contract) and ``len == max_len`` full rows — for the f32
and int8-KV caches, plus the module-level ``attn_impl`` switch.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.decode_attention import _pick_block_k, decode_attention
from repro.kernels.ref import decode_attention_ref
from repro.models import attention as attn
from repro.models.layers import Ctx

B, H, KV, D, T = 4, 8, 2, 64, 96

LEN_PATTERNS = [
    [1, 5, 37, 96],      # ragged, incl. a fresh 1-key row and a full row
    [0, 1, 96, 50],      # len=0 recycled slot alongside a full row
    [96, 96, 96, 96],    # every row at max_len
    [3, 3, 3, 3],        # uniform tiny live context
]


def _qkv(key, int8=False):
    """q (B, H, D) and a lane-dense (B, T, KV·D) cache (int8 + (B, T, KV, 1)
    scales when ``int8``)."""
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, D))
    k = jax.random.normal(kk, (B, T, KV, D))
    v = jax.random.normal(kv, (B, T, KV, D))
    if not int8:
        return q, k.reshape(B, T, KV * D), v.reshape(B, T, KV * D), None, None
    ks = jnp.maximum(jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0, 1e-8)
    vs = jnp.maximum(jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0, 1e-8)
    kq8 = jnp.clip(jnp.round(k / ks), -127, 127).astype(jnp.int8)
    vq8 = jnp.clip(jnp.round(v / vs), -127, 127).astype(jnp.int8)
    return (q, kq8.reshape(B, T, KV * D), vq8.reshape(B, T, KV * D), ks, vs)


@pytest.mark.parametrize("lens", LEN_PATTERNS)
@pytest.mark.parametrize("block_k", [8, 32, 128])
def test_kernel_matches_oracle_f32(lens, block_k):
    q, k, v, _, _ = _qkv(jax.random.PRNGKey(sum(lens)))
    L = jnp.asarray(lens, jnp.int32)
    y = decode_attention(q, k, v, L, block_k=block_k, interpret=True)
    r = decode_attention_ref(q, k, v, L)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("lens", LEN_PATTERNS[:2])
def test_kernel_matches_oracle_int8(lens):
    q, k8, v8, ks, vs = _qkv(jax.random.PRNGKey(7), int8=True)
    L = jnp.asarray(lens, jnp.int32)
    y = decode_attention(q, k8, v8, L, ks=ks, vs=vs, interpret=True)
    r = decode_attention_ref(q, k8, v8, L, ks=ks, vs=vs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=2e-6, atol=2e-6)


def test_kernel_block_shape_invariance():
    """Re-blocking shifts only the online-softmax accumulation order —
    outputs must agree to f32 accumulation tolerance across block sizes."""
    q, k, v, _, _ = _qkv(jax.random.PRNGKey(3))
    L = jnp.asarray([1, 17, 50, 96], jnp.int32)
    outs = [np.asarray(decode_attention(q, k, v, L, block_k=bk,
                                        interpret=True))
            for bk in (8, 16, 48, 96)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-6, atol=2e-6)


def test_len_zero_rows_are_exactly_zero():
    """A never-written slot (len 0) must emit exactly 0 — not a softmax
    over masked junk — so recycled-slot garbage can never leak."""
    q, k, v, _, _ = _qkv(jax.random.PRNGKey(4))
    L = jnp.asarray([0, 0, 5, 0], jnp.int32)
    y = np.asarray(decode_attention(q, k, v, L, interpret=True))
    assert np.all(y[[0, 1, 3]] == 0.0)
    assert np.any(y[2] != 0.0)


def test_pick_block_k_never_pads():
    """block_k must divide T (padding would copy the whole cache), and it
    must be the *largest* such divisor <= block_k — a gcd-style pick would
    collapse T=258 to block 2 (129 sequential grid steps per row)."""
    for t, bk in [(96, 128), (24, 128), (512, 128), (130, 128), (1, 64)]:
        eff = _pick_block_k(t, bk)
        assert t % eff == 0 and 1 <= eff <= min(t, bk), (t, bk, eff)
        assert not any(t % c == 0 for c in range(eff + 1, min(t, bk) + 1))
    assert _pick_block_k(258, 128) == 86
    assert _pick_block_k(130, 128) == 65


# ------------------------------------------------ module-level impl switch


def _tiny_cfg(int8: bool, impl: str):
    cfg = get_config("qwen2-0.5b").reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                               vocab_size=128, n_heads=4, n_kv_heads=2,
                               head_dim=32, dtype="float32",
                               kv_cache_int8=int8, attn_impl=impl)


def _ragged_cache(cfg, lens, max_len, key):
    """Per-row einsum prefill concatenated into one ragged batched cache."""
    p, _ = attn.init_gqa(jax.random.PRNGKey(0), cfg)
    rows = []
    for i, L in enumerate(lens):
        c1 = attn.init_gqa_cache(cfg, 1, max_len, jnp.float32)
        if L:
            x = jax.random.normal(jax.random.fold_in(key, i),
                                  (1, L, cfg.d_model))
            _, c1 = attn.gqa_attention(Ctx.make(cfg), p, x,
                                       jnp.arange(L)[None], c1)
        rows.append(c1)
    return p, jax.tree.map(lambda *rs: jnp.concatenate(rs, axis=0), *rows)


@pytest.mark.parametrize("int8", [False, True])
def test_gqa_attention_kernel_equals_einsum(int8):
    """attn_impl="kernel" must match the einsum reference on ragged decode
    AND ragged prefill continuation, with identical cache updates."""
    cfg_e = _tiny_cfg(int8, "einsum")
    cfg_k = _tiny_cfg(int8, "kernel")
    lens = [5, 11, 0, 24]
    key = jax.random.PRNGKey(1)
    p, cache = _ragged_cache(cfg_e, lens, 32, key)
    tol = dict(rtol=2e-5, atol=2e-5)

    # decode: one token against the ragged cache (len=0 = recycled slot)
    x1 = jax.random.normal(jax.random.fold_in(key, 99), (len(lens), 1,
                                                         cfg_e.d_model))
    pos = jnp.asarray(lens, jnp.int32)[:, None]
    out_e, nc_e = attn.gqa_attention(Ctx.make(cfg_e), p, x1, pos, cache)
    out_k, nc_k = attn.gqa_attention(Ctx.make(cfg_k), p, x1, pos, cache)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_e), **tol)
    for le, lk in zip(jax.tree.leaves(nc_e), jax.tree.leaves(nc_k)):
        np.testing.assert_array_equal(np.asarray(le), np.asarray(lk))

    # prefill continuation: a 6-token chunk appended to every row
    x6 = jax.random.normal(jax.random.fold_in(key, 100), (len(lens), 6,
                                                          cfg_e.d_model))
    pos6 = jnp.asarray(lens, jnp.int32)[:, None] + jnp.arange(6)[None]
    oe, _ = attn.gqa_attention(Ctx.make(cfg_e), p, x6, pos6, cache)
    ok, _ = attn.gqa_attention(Ctx.make(cfg_k), p, x6, pos6, cache)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(oe), **tol)


def test_attn_impl_validated():
    cfg = _tiny_cfg(False, "typo")
    p, _ = attn.init_gqa(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="attn_impl"):
        attn.gqa_attention(Ctx.make(cfg), p, x, jnp.arange(4)[None])


def test_int8_fallback_matches_dequant_first():
    """The einsum int8 fallback folds scales into logits/probs instead of
    materialising a dequantised f32 cache copy; numerics must match the
    dequant-first construction to f32 rounding."""
    key = jax.random.PRNGKey(11)
    q, k8, v8, ks, vs = _qkv(key, int8=True)
    lens = jnp.asarray([1, 5, 37, 96], jnp.int32)
    mask = attn._cached_mask(lens - 1, 1, T)
    k8, v8 = k8.reshape(B, T, KV, D), v8.reshape(B, T, KV, D)
    out = attn._sdpa_int8(q[:, None], k8, ks, v8, vs, mask)
    kf = (k8.astype(jnp.float32) * ks)
    vf = (v8.astype(jnp.float32) * vs)
    ref = attn._sdpa(q[:, None], kf, vf, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)
