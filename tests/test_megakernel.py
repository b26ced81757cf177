"""Megakernel decode step + single-launch scheduler (DESIGN.md §15):
MLA/ssm decode kernels vs their oracles, fused-layer and fused-step
bit-stability vs the per-call paths, chunked prefill on every family."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.model import build
from repro.serving.engine import DEFAULT_CHUNK_SIZE, Engine, Request


def _tiny_dense_cfg(**over):
    cfg = get_config("qwen2-0.5b").reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                               vocab_size=128, n_heads=4, n_kv_heads=2,
                               head_dim=32, **over)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = _tiny_dense_cfg()
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, lens, seed=0, max_new=5):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, L, dtype=np.int32),
                    max_new_tokens=max_new)
            for L in lens]


# ------------------------------------------------- kernels vs their oracles


def test_mla_decode_kernel_matches_oracle():
    """Latent-cache MLA decode kernel == absorbed einsum oracle across
    ragged lengths (incl. an empty row) at a non-dividing block size."""
    from repro.kernels.mla_decode import mla_decode_attention
    from repro.kernels.ref import mla_decode_attention_ref

    b, h, lat, rope_hd, t = 3, 4, 16, 8, 24
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 4)
    q_lat = jax.random.normal(ks[0], (b, h, lat), jnp.float32)
    q_rope = jax.random.normal(ks[1], (b, h, rope_hd), jnp.float32)
    ckv = jax.random.normal(ks[2], (b, t, lat), jnp.float32)
    krope = jax.random.normal(ks[3], (b, rope_hd, t), jnp.float32)
    lens = jnp.array([24, 5, 0], jnp.int32)
    scale = 1.0 / (lat + rope_hd) ** 0.5
    got = mla_decode_attention(q_lat, q_rope, ckv, krope, lens, scale,
                               block_k=8)
    want = mla_decode_attention_ref(q_lat, q_rope, ckv, krope, lens, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ssm_decode_step_kernel_matches_oracle():
    """Single-token selective-scan kernel (conv window roll + silu + state
    recurrence + readout) == the pure-jnp oracle."""
    from repro.kernels.ref import ssm_decode_step_ref
    from repro.kernels.ssm_scan import ssm_decode_step

    b, d_inner, ngroups, d_state, nheads, win = 2, 64, 1, 16, 2, 3
    conv_dim = d_inner + 2 * ngroups * d_state
    k = jax.random.PRNGKey(1)
    ks = jax.random.split(k, 8)
    conv_cache = jax.random.normal(ks[0], (b, win, conv_dim), jnp.float32)
    xbc = jax.random.normal(ks[1], (b, 1, conv_dim), jnp.float32)
    conv_w = jax.random.normal(ks[2], (win + 1, conv_dim), jnp.float32)
    conv_b = jax.random.normal(ks[3], (conv_dim,), jnp.float32)
    dt1 = jax.nn.softplus(jax.random.normal(ks[4], (b, nheads), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[5], (nheads,), jnp.float32))
    d = jax.random.normal(ks[6], (nheads,), jnp.float32)
    state = jax.random.normal(
        ks[7], (b, nheads, d_inner // nheads, d_state), jnp.float32)
    got_y, got_conv, got_state = ssm_decode_step(
        conv_cache, xbc, conv_w, conv_b, dt1, a, d, state,
        d_inner, ngroups, d_state)
    want_y, want_conv, want_state = ssm_decode_step_ref(
        conv_cache, xbc, conv_w, conv_b, dt1, a, d, state,
        d_inner, ngroups, d_state)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_conv), np.asarray(want_conv),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------- kernel decode paths, end to end


def test_mla_engine_kernel_matches_einsum():
    """deepseek-style MLA serving: attn_impl='kernel' (latent-cache Pallas
    decode) == 'einsum', token for token, greedy."""
    cfg = get_config("deepseek-v2-236b").reduced()
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    lens = [5, 11, 3, 8]
    a = Engine(cfg, params, max_slots=2, max_len=64,
               attn_impl="kernel").generate(_requests(cfg, lens, 2))
    b = Engine(cfg, params, max_slots=2, max_len=64,
               attn_impl="einsum").generate(_requests(cfg, lens, 2))
    assert a == b, (a, b)


def test_ssm_engine_kernel_matches_einsum():
    """mamba2 serving: attn_impl='kernel' (selective-scan Pallas decode
    step) == 'einsum', token for token, greedy."""
    cfg = get_config("mamba2-130m").reduced()
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    lens = [5, 9, 3, 12]
    a = Engine(cfg, params, max_slots=2, max_len=64,
               attn_impl="kernel").generate(_requests(cfg, lens, 3))
    b = Engine(cfg, params, max_slots=2, max_len=64,
               attn_impl="einsum").generate(_requests(cfg, lens, 3))
    assert a == b, (a, b)


# ------------------------------------- chunked prefill for every family


@pytest.mark.parametrize("arch", ["mamba2-130m", "zamba2-7b", "olmoe-1b-7b"])
def test_chunked_prefill_matches_whole_prompt_all_families(arch):
    """Single-trace chunked prefill on the formerly exact-length families
    (ssm state continuation via ``ctx.prefill_valid`` dt-masking, hybrid
    super-blocks, dropless moe routing) == whole-prompt, token for token,
    with ragged + 1-token prompts and recycled slots (5 requests through
    2 slots — later occupants ride caches their predecessors dirtied)."""
    cfg = get_config(arch).reduced()
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    lens = [7, 19, 1, 12, 1]
    chunked = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=8)
    assert chunked.chunk_size == 8
    a = chunked.generate(_requests(cfg, lens, 4))
    b = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=0).generate(
        _requests(cfg, lens, 4))
    assert a == b, (arch, a, b)
    assert chunked.prefill_traces == 1


def test_chunked_prefill_default_on_ssm():
    """chunk_size=None on an ssm family now auto-chunks (no more
    whole-prompt fallback) and still matches the whole-prompt tokens."""
    cfg = get_config("mamba2-130m").reduced()
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_slots=2, max_len=96)
    assert eng.chunk_size == DEFAULT_CHUNK_SIZE
    lens = [3, 40, 33]
    a = eng.generate(_requests(cfg, lens, 5))
    b = Engine(cfg, params, max_slots=2, max_len=96, chunk_size=0).generate(
        _requests(cfg, lens, 5))
    assert a == b, (a, b)


# ------------------------------------------- single-launch scheduler step


def test_fused_step_matches_per_call_and_halves_launches(dense_setup):
    """The single-launch ``_step`` scheduler == the per-call scheduler,
    token for token, and collapses the dispatch tail: launches per
    iteration drop by >= 2x (the acceptance witness serving_bench gates)."""
    cfg, params = dense_setup
    lens = [3, 37, 6, 17, 4, 9, 33, 2]
    fused = Engine(cfg, params, max_slots=4, max_len=64, chunk_size=8)
    legacy = Engine(cfg, params, max_slots=4, max_len=64, chunk_size=8,
                    fused_step=False)
    a = fused.generate(_requests(cfg, lens, 6))
    b = legacy.generate(_requests(cfg, lens, 6))
    assert a == b, (a, b)
    assert fused._fused_ok, "fused engine silently fell back to per-call"
    fc, lc = fused.counters, legacy.counters
    assert fc.iterations == lc.iterations
    assert fc.launches == fc.iterations  # ONE launch per iteration
    assert 2 * fc.launches <= lc.launches, (fc.launches, lc.launches)


def test_fused_step_int8_and_sim(dense_setup):
    """Fused-step equality holds on the int8-KV cache layout and on the
    sim-mode deployed-plane path (same PRNG stream as per-call)."""
    cfg, params = dense_setup
    lens = [3, 11, 6, 17]
    c8 = dataclasses.replace(cfg, kv_cache_int8=True)
    a = Engine(c8, params, max_slots=2, max_len=48).generate(
        _requests(c8, lens, 7))
    b = Engine(c8, params, max_slots=2, max_len=48, fused_step=False
               ).generate(_requests(c8, lens, 7))
    assert a == b, (a, b)
    a = Engine(cfg, params, max_slots=2, max_len=48, cim_mode="sim"
               ).generate(_requests(cfg, lens, 8))
    b = Engine(cfg, params, max_slots=2, max_len=48, cim_mode="sim",
               fused_step=False).generate(_requests(cfg, lens, 8))
    assert a == b, (a, b)


def test_fused_step_failure_falls_back_to_per_call(dense_setup):
    """A raising ``_step`` must not kill the batch: the engine falls back
    to the per-call path (permanently) and still produces the per-call
    token streams."""
    cfg, params = dense_setup
    lens = [5, 9, 3]
    eng = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=8)

    def boom(*a, **kw):
        raise RuntimeError("injected step fault")

    eng._step = boom
    out = eng.generate(_requests(cfg, lens, 9))
    ref = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=8,
                 fused_step=False).generate(_requests(cfg, lens, 9))
    assert out == ref, (out, ref)
    assert not eng._fused_ok


def test_fused_step_validation(dense_setup):
    cfg, params = dense_setup
    with pytest.raises(ValueError, match="fused_step"):
        Engine(cfg, params, max_slots=1, max_len=32, chunk_size=0,
               fused_step=True)


# --------------------------------------------- per-layer decode megakernel


def test_fuse_layer_matches_unfused_off_f32(dense_setup):
    """cfg.fuse_layer routes decode-shaped dense blocks through the
    per-layer megakernel (kernels/fused_step.py): token-for-token equal to
    the unfused per-op path, greedy, ragged lengths + slot turnover."""
    cfg, params = dense_setup
    lens = [3, 11, 6, 17, 4, 9]
    a = Engine(cfg, params, max_slots=2, max_len=48, fuse_layer=True
               ).generate(_requests(cfg, lens, 10))
    b = Engine(cfg, params, max_slots=2, max_len=48).generate(
        _requests(cfg, lens, 10))
    assert a == b, (a, b)


def test_fuse_layer_matches_unfused_int8_kv(dense_setup):
    """Megakernel replicates the int8 KV quantize-write-then-read order
    (attention sees the quantize-dequantize roundtripped current token)."""
    cfg, params = dense_setup
    c8 = dataclasses.replace(cfg, kv_cache_int8=True)
    lens = [3, 11, 6, 17]
    a = Engine(c8, params, max_slots=2, max_len=48, fuse_layer=True
               ).generate(_requests(c8, lens, 11))
    b = Engine(c8, params, max_slots=2, max_len=48).generate(
        _requests(c8, lens, 11))
    assert a == b, (a, b)


def test_fuse_layer_matches_unfused_sim_deployed(dense_setup):
    """Sim-mode megakernel: the in-kernel cim_matmul_fused replica (act
    rms scale, int8 planes, per-tile Threefry readout noise on global
    (row, col) counters) == the unfused ``cim.use_kernel=True`` engine,
    token for token — same noise stream, same seeds, same draw order."""
    cfg, params = dense_setup
    cs = dataclasses.replace(
        cfg, cim=dataclasses.replace(cfg.cim, use_kernel=True))
    lens = [3, 11, 6, 17]
    a = Engine(cs, params, max_slots=2, max_len=48, cim_mode="sim",
               fuse_layer=True).generate(_requests(cs, lens, 12))
    b = Engine(cs, params, max_slots=2, max_len=48, cim_mode="sim"
               ).generate(_requests(cs, lens, 12))
    assert a == b, (a, b)


@pytest.mark.parametrize("dtype,mode", [("bfloat16", "off"),
                                        ("float32", "off"),
                                        ("float32", "sim")])
def test_fuse_layer_refused_at_published_width(dtype, mode):
    """qwen2-0.5b at its published widths does not fit the megakernel
    (whole-array projection weights in VMEM), and bf16 activations are not
    taken at all: the engine refuses fuse_layer at construction instead of
    serving silently on the per-layer path. Nothing is initialised — the
    refusal comes before the params are touched."""
    from repro.kernels.fused_step import VMEM_LIMIT_BYTES, vmem_bytes

    cfg = dataclasses.replace(get_config("qwen2-0.5b"), dtype=dtype)
    if dtype == "float32":
        assert vmem_bytes(cfg, 8, 2048, sim=mode == "sim") > VMEM_LIMIT_BYTES
    with pytest.raises(ValueError, match="fuse_layer"):
        Engine(cfg, params=None, max_slots=8, max_len=2048, cim_mode=mode,
               fuse_layer=True)


def test_fuse_layer_fits_at_test_widths(dense_setup):
    """The widths the megakernel tests serve stay inside the VMEM limit."""
    from repro.kernels.fused_step import VMEM_LIMIT_BYTES, vmem_bytes

    cfg, _ = dense_setup
    for sim in (False, True):
        assert vmem_bytes(cfg, 4, 64, sim) < VMEM_LIMIT_BYTES
