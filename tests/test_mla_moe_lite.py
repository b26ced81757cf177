"""DeepSeek-V2-Lite's mechanisms at a tiny size of the same shape.

MLA without a query low-rank projection, with its latent RMSNorm and YaRN
rope scaling; an expert layer that holds a share of the router's experts
(16 experts, 4 held, top 3 without renormalisation, 1 shared expert) and
runs them through a grouped matmul; the latent-cache prefill kernel. The
served path against the plain reference (``bench/references/mla_moe.py``)
is tested in ``bench/tests/test_bench_deepseek_v2_lite.py``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, RopeScaling
from repro.configs.registry import get_config
from repro.kernels.mla_prefill import mla_prefill_attention
from repro.models import moe as moe_mod
from repro.models.attention import mla_softmax_scale
from repro.models.layers import Ctx, apply_rope, rope_freqs, swiglu

LITE_YARN = RopeScaling(type="yarn", factor=40, original_max_position_embeddings=4096,
                        beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=0.707)


def _tiny(n_held=4, held_first=4):
    return ModelConfig(
        name="lite-tiny", family="moe", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=32, vocab_size=128, dtype="float32",
        n_dense_layers=1, dense_d_ff=96, tie_embeddings=False,
        moe=MoEConfig(n_experts=16, top_k=3, n_shared=1, n_held=n_held,
                      held_first=held_first, norm_topk=False),
        mla=MLAConfig(q_lora=None, kv_lora=32, rope_head_dim=16,
                      nope_head_dim=16, v_head_dim=16),
        rope_scaling=dataclasses.replace(LITE_YARN,
                                         original_max_position_embeddings=64))


# ------------------------------------------------------------------ YaRN

def _yarn_numpy(d=64, theta=10000.0, factor=40.0, orig=4096, fast=32.0,
                slow=1.0, mscale=0.707):
    """The published YaRN equations, transcribed in float64."""
    i = np.arange(d // 2, dtype=np.float64)
    f_extra = theta ** (-2.0 * i / d)
    f_inter = f_extra / factor
    c = lambda r: d * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))
    low, high = math.floor(c(fast)), math.ceil(c(slow))
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    inv = f_inter * (1 - m) + f_extra * m
    scale = (128 + 64) ** -0.5 * (0.1 * mscale * math.log(factor) + 1.0) ** 2
    return inv, scale, (low, high)


def test_yarn_frequencies_and_scale_match_the_published_equations():
    inv, scale, (low, high) = _yarn_numpy()
    assert (low, high) == (10, 23)
    got = np.asarray(rope_freqs(64, 10000.0, LITE_YARN), np.float64)
    # float32 evaluation of float64 equations: a few ulps
    np.testing.assert_allclose(got, inv, rtol=2e-6)
    plain = np.asarray(rope_freqs(64, 10000.0), np.float64)
    # below `low` the original frequencies, above `high` the interpolated
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1], rtol=2e-6)
    np.testing.assert_allclose(got[high:], plain[high:] / 40, rtol=2e-6)
    assert np.all(np.abs(got[low + 1:high] / plain[low + 1:high] - 1) > 1e-3)
    cfg = dataclasses.replace(
        get_config("deepseek-v2-236b"),
        mla=MLAConfig(q_lora=None, kv_lora=512, rope_head_dim=64,
                      nope_head_dim=128, v_head_dim=128),
        rope_scaling=LITE_YARN)
    assert mla_softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert mla_softmax_scale(cfg) * 192 ** 0.5 == pytest.approx(1.590, abs=1e-3)
    # mscale == mscale_all_dim: cos/sin keep their unit factor
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    ang = np.arange(5)[:, None] * inv[None]
    x1, x2 = np.split(np.asarray(x[0], np.float64), 2, -1)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_allclose(apply_rope(x, pos, 10000.0, LITE_YARN)[0],
                               want, atol=1e-5)


def _rope_freqs_before(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _apply_rope_before(x, positions, theta):
    d = x.shape[-1]
    freqs = _rope_freqs_before(d, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_no_rope_scaling_is_bit_identical(dtype):
    """``rope_scaling`` None computes exactly what the rope did before."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 3, 64)).astype(dtype)
    pos = jnp.array([[0, 1, 2, 3, 4, 5, 6], [90, 91, 92, 93, 94, 95, 96]])
    for theta in (10000.0, 1e6):
        assert np.array_equal(np.asarray(rope_freqs(64, theta)),
                              np.asarray(_rope_freqs_before(64, theta)))
        assert np.array_equal(np.asarray(apply_rope(x, pos, theta)),
                              np.asarray(_apply_rope_before(x, pos, theta)))
        assert np.array_equal(np.asarray(apply_rope(x, pos[0], theta)),
                              np.asarray(_apply_rope_before(x, pos[0], theta)))


# ------------------------------------------------------- held experts

def _moe_params(cfg, key=0):
    p, _ = moe_mod.init_moe(jax.random.PRNGKey(key), cfg, jnp.float32)
    return p


def _uncut_layer(cfg, p, x):
    """The whole layer from its equations: softmax top-k over every expert,
    no renormalisation, each expert densely weighted by its gate, plus the
    shared expert."""
    m = cfg.moe
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x @ p["router"]["w"], -1)
        gates, idx = jax.lax.top_k(probs, m.top_k)
        y = jnp.zeros_like(x)
        for e in range(m.n_experts):
            g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
            h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
            y = y + g[:, None] * (h @ p["w_down"][e])
        return y + swiglu(Ctx.make(cfg), p["shared"], x)


def test_held_shares_sum_to_the_uncut_layer():
    """Four devices holding 4 experts each give parts that add up, with
    the shared expert (which each computes alike) counted once, to the
    whole layer."""
    whole = _tiny(n_held=0, held_first=0)
    p = _moe_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64))
    want = _uncut_layer(whole, p, x[0])
    shared = swiglu(Ctx.make(whole), p["shared"], x)
    parts = []
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            cfg = _tiny(n_held=4, held_first=4 * i)
            share = dict(p, **{k: p[k][4 * i:4 * i + 4]
                               for k in ("w_gate", "w_up", "w_down")})
            parts.append(moe_mod.moe_block(Ctx.make(cfg), share, x,
                                           dropless=True))
        whole_y = moe_mod.moe_block(Ctx.make(whole), p, x, dropless=True)
    got = sum(parts) - 3 * shared
    # float32 both sides; the sums run in another order
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(whole_y[0], want, atol=1e-5, rtol=1e-5)


def test_a_held_share_refuses_the_capacity_path():
    cfg = _tiny()
    x = jnp.ones((1, 4, 64))
    with pytest.raises(ValueError, match="holding 4 of 16"):
        moe_mod.moe_block(Ctx.make(cfg), _moe_params(cfg), x)


@pytest.mark.parametrize("tokens", [8, 64, 256])
def test_grouped_matmul_rows_follow_the_routed_rows(tokens):
    """The grouped matmul computes the rows routed to the held experts and
    its tile padding (at most two tiles a held expert), not the static
    buffer of ``tokens · min(top_k, held)`` rows."""
    cfg = _tiny()
    m = cfg.moe
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64))
    ctx = Ctx.make(cfg)
    ctx.moe_log = []
    moe_mod.held_experts(ctx, p, x)
    (sizes, computed, groups), = ctx.moe_log
    with jax.default_matmul_precision("highest"):
        _, idx = jax.lax.top_k(jax.nn.softmax(x @ p["router"]["w"], -1),
                               m.top_k)
    want = [int(jnp.sum(idx == m.held_first + e)) for e in range(m.held)]
    assert np.asarray(sizes).tolist() == want
    assert int(groups) == sum(w > 0 for w in want)
    tm = moe_mod.expert_tile_rows(tokens, m)
    routed = sum(want)
    assert routed <= int(computed) <= routed + 2 * m.held * tm
    assert int(computed) % tm == 0


# ------------------------------------------------------ prefill kernel

@pytest.mark.parametrize("starts", [(0, 0), (5, 40), (61, 3)])
def test_mla_prefill_kernel_matches_dense_attention(starts):
    """Chunks at per-row offsets against a latent cache holding stale keys
    past each row's chunk: the kernel agrees with masked dense attention
    over the latent."""
    b, s, h, lat, r, t = 2, 9, 4, 32, 16, 80
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    ql = jax.random.normal(ks[0], (b, s, h, lat))
    qr = jax.random.normal(ks[1], (b, s, h, r))
    ckv = jax.random.normal(ks[2], (b, t, lat))
    kr = jax.random.normal(ks[3], (b, r, t))
    start = jnp.array(starts, jnp.int32)
    got = mla_prefill_attention(ql, qr, ckv, kr, start, scale=0.2, block_k=16)
    with jax.default_matmul_precision("highest"):
        sc = (jnp.einsum("bshl,btl->bhst", ql, ckv)
              + jnp.einsum("bshr,brt->bhst", qr, kr)) * 0.2
    qi = start[:, None] + jnp.arange(s)[None]
    kj = jnp.arange(t)
    mask = kj[None, None, :] <= qi[:, :, None]
    sc = jnp.where(mask[:, None], sc, -jnp.inf)
    want = jnp.einsum("bhst,btl->bshl", jax.nn.softmax(sc, -1), ckv)
    # float32 operands, online softmax in another order
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
