"""Pallas kernel vs pure-jnp oracles: in-kernel PRNG, fused scale, raggedness.

The kernel generates its readout noise internally (counter-based Threefry on
the global element position — see repro/core/prng.py), so the oracle match is
*value-exact up to FMA contraction*: the deterministic int accumulation is
bit-exact, and the noise term may differ by 1 ulp where XLA contracts
``acc + sigma * z`` into an FMA in one lowering but not the other. Tests use
``assert_allclose`` with ulp-scale rtol, plus strict equality on the
noiseless integer path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quant
from repro.core.cim import (
    CIMSpec,
    cim_matmul_bit_exact,
    output_noise_std_int,
    output_noise_std_int_per_tile,
)
from repro.core.prng import threefry2x32
from repro.kernels import ops, ref
from repro.kernels.cim_matmul import cim_matmul_pallas

SHAPES = [
    (8, 512, 8),          # sub-tile K
    (64, 1024, 32),       # exactly one macro tile
    (100, 2048, 130),     # ragged M/N, two tiles
    (256, 3072, 256),     # three tiles, MXU-aligned
    (1, 1024, 1),         # degenerate vector
]


def _rand_operands(m, k, n, lim=31, seed=None):
    key = jax.random.PRNGKey(seed if seed is not None else m * 7 + k + n)
    kx, kw = jax.random.split(key)
    xq = jax.random.randint(kx, (m, k), -lim, lim + 1, dtype=jnp.int32)
    wq = jax.random.randint(kw, (k, n), -lim, lim + 1, dtype=jnp.int32)
    return xq.astype(jnp.int8), wq.astype(jnp.int8)


def test_threefry_known_answer_vectors():
    """Our Threefry-2x32-20 must match the Random123 reference vectors —
    the whole oracle-exactness story rests on this primitive."""
    cases = [
        ((0, 0), (0, 0), (0x6B200159, 0x99BA4EFE)),
        ((0xFFFFFFFF, 0xFFFFFFFF), (0xFFFFFFFF, 0xFFFFFFFF),
         (0x1CB996FC, 0xBB002BE7)),
        ((0x13198A2E, 0x03707344), (0x243F6A88, 0x85A308D3),
         (0xC4923A9C, 0x483DF7A0)),
    ]
    for (k0, k1), (x0, x1), (e0, e1) in cases:
        y0, y1 = threefry2x32(k0, k1, x0, x1)
        assert (int(y0), int(y1)) == (e0, e1)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_kernel_matches_oracle(m, k, n):
    xq, wq = _rand_operands(m, k, n)
    y_k = cim_matmul_pallas(xq, wq, seed=1234, sigma=3.5, scale=0.37,
                            interpret=True)
    y_r = ref.cim_matmul_prng_ref(xq, wq, 1234, 3.5, 1024, 0.37)
    # ulp-scale slack only (FMA contraction): a 1-ulp difference at
    # intermediate accumulator magnitude (~2^11 -> 2.4e-4) can survive on a
    # near-zero output, so atol is set above that; a wrong noise stream
    # would be off by O(sigma * scale) ~ 1
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=5e-6, atol=2e-3)


@pytest.mark.parametrize("m,k,n", SHAPES[:3])
def test_kernel_noiseless_exact(m, k, n):
    """seed=None path must equal the integer matmul exactly (incl. the
    fused scale epilogue, which is a single f32 multiply)."""
    xq, wq = _rand_operands(m, k, n, lim=127, seed=k + 13)
    y = cim_matmul_pallas(xq, wq, seed=None, sigma=0.0, interpret=True)
    exact = xq.astype(jnp.int32) @ wq.astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(exact).astype(np.float32))


def test_kernel_noise_invariant_to_block_shape():
    """The noise counter is the global (row, col, tile): re-blocking the
    kernel must not change a single bit of the output."""
    xq, wq = _rand_operands(100, 2048, 130)
    a = cim_matmul_pallas(xq, wq, seed=7, sigma=2.0, bm=256, bn=256,
                          interpret=True)
    b = cim_matmul_pallas(xq, wq, seed=7, sigma=2.0, bm=128, bn=128,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_shaped_auto_tile_bit_identical():
    """Skinny decode tiles (bm=None auto-picks the next multiple of 8 for
    M <= 8 instead of a 256-row pad) must equal the bm=256 output bit for
    bit — threefry invariance extends to the serving decode shape."""
    for m in (1, 4, 8):
        xq, wq = _rand_operands(m, 2048, 96, seed=m)
        auto = cim_matmul_pallas(xq, wq, seed=11, sigma=2.0, interpret=True)
        padded = cim_matmul_pallas(xq, wq, seed=11, sigma=2.0, bm=256,
                                   bn=256, interpret=True)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(padded))


def test_modeled_decode_tile_cost_ratio():
    """The decode-shaped launch must model >= 4x fewer FLOPs + HBM bytes
    than the padded bm=256 launch (the BENCH_kernels acceptance). The auto
    tile is the 8-row block the kernel launches (it compiles for v5e,
    tests/test_tpu_compile.py)."""
    from repro.kernels.cim_matmul import modeled_cost

    pad = modeled_cost(4, 2048, 512, bm=256, bn=256)
    skinny = modeled_cost(4, 2048, 512)
    assert skinny["bm"] == 8
    ratio = (pad["flops"] + pad["hbm_bytes"]) / (
        skinny["flops"] + skinny["hbm_bytes"])
    assert ratio >= 4.0, ratio
    assert pad["flops"] / skinny["flops"] == 32.0


# ------------------------------------------------- fused activation quant


def test_fused_act_quant_kernel_matches_oracle():
    """cim_matmul_fused_pallas (in-prologue activation quantization) must
    match the quantize-then-prng jnp oracle."""
    from repro.kernels.cim_matmul import cim_matmul_fused_pallas

    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (6, 1536))
    _, wq = _rand_operands(6, 1536, 80, seed=4)
    xs = quant.abs_max_scale(x, 6)
    y_k = cim_matmul_fused_pallas(x, wq, xs, seed=21, sigma=1.5, in_bits=6,
                                  scale=0.01, interpret=True)
    y_r = ref.cim_matmul_fused_ref(x, wq, xs, 21, 1.5, 1024, 0.01, 6)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=5e-6, atol=2e-5)


def test_fused_act_quant_equals_separate_quant_pass():
    """Fusing the activation quant into the prologue must be bit-identical
    to quantizing first and running the int kernel — the fusion removes an
    HBM round-trip, never a bit."""
    from repro.kernels.cim_matmul import cim_matmul_fused_pallas

    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (4, 2048))
    _, wq = _rand_operands(4, 2048, 64, seed=6)
    xs = quant.abs_max_scale(x, 6)
    xq = quant.quantize(x, xs, 6).astype(jnp.int8)
    fused = cim_matmul_fused_pallas(x, wq, xs, seed=9, sigma=2.0, in_bits=6,
                                    scale=0.02, interpret=True)
    twopass = cim_matmul_pallas(xq, wq, seed=9, sigma=2.0, scale=0.02,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(twopass))


def test_ops_deployed_matches_ref_dispatch():
    """cim_matmul_deployed: pallas-interpret and ref dispatch agree, and the
    ref construction equals explicit quantize + cim_matmul_int."""
    spec = CIMSpec()
    key = jax.random.PRNGKey(8)
    x = jax.random.normal(key, (4, 1536))
    _, wq = _rand_operands(4, 1536, 40, seed=9)
    ws = jnp.float32(0.021)
    nk = jax.random.fold_in(key, 1)
    y_p = ops.cim_matmul_deployed(x, wq, ws, spec, nk,
                                  force="pallas_interpret")
    y_r = ops.cim_matmul_deployed(x, wq, ws, spec, nk, force="ref")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               rtol=5e-6, atol=2e-5)
    from repro.core.prng import seed_from_key
    from repro.core.cim import output_noise_std_int_per_tile

    xs = quant.abs_max_scale(x.astype(jnp.float32), spec.in_bits)
    xq = quant.quantize(x.astype(jnp.float32), xs, spec.in_bits)
    sigma = output_noise_std_int_per_tile(spec, x.shape[1])
    y_m = ops.cim_matmul_int(xq, wq, seed_from_key(nk), sigma,
                             scale=xs * ws, force="ref")
    np.testing.assert_array_equal(np.asarray(y_r), np.asarray(y_m))


def test_kernel_noise_moments():
    """In-kernel PRNG noise: per-tile std sigma, T tiles add in variance;
    zero-input matmul isolates the noise term exactly."""
    m, k, n = 256, 4096, 256  # T = 4 tiles
    xq = jnp.zeros((m, k), jnp.int8)
    wq = jnp.zeros((k, n), jnp.int8)
    y = np.asarray(cim_matmul_pallas(xq, wq, seed=42, sigma=1.0, interpret=True))
    se = 2.0 / np.sqrt(y.size)
    assert abs(y.mean()) < 4 * se, y.mean()
    assert abs(y.std() - 2.0) < 0.02, y.std()  # sqrt(T) * sigma = 2
    # different seeds decorrelate
    y2 = np.asarray(cim_matmul_pallas(xq, wq, seed=43, sigma=1.0, interpret=True))
    rho = np.corrcoef(y.ravel(), y2.ravel())[0, 1]
    assert abs(rho) < 0.02, rho


@settings(deadline=None, max_examples=10)
@given(
    m=st.integers(1, 96),
    kt=st.integers(1, 3),
    n=st.integers(1, 80),
    seed=st.integers(0, 2**31 - 1),
)
def test_kernel_property_sweep(m, kt, n, seed):
    """Property: kernel == oracle for random raggedness and tile counts."""
    k = kt * 512 + (seed % 97)
    xq, wq = _rand_operands(m, k, n, lim=15, seed=seed)
    y_k = cim_matmul_pallas(xq, wq, seed=seed, sigma=1.7, interpret=True)
    y_r = ref.cim_matmul_prng_ref(xq, wq, seed, 1.7, 1024)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=5e-6, atol=2e-3)


def test_ops_wrapper_and_ste_grad():
    spec = CIMSpec()
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (16, 1024))
    w = jax.random.normal(jax.random.fold_in(key, 1), (1024, 8))
    y = ops.cim_matmul(x, w, spec, jax.random.fold_in(key, 2))
    assert y.shape == (16, 8) and np.all(np.isfinite(np.asarray(y)))
    gx, gw = jax.grad(lambda x, w: ops.cim_matmul(x, w, spec, None).sum(),
                      argnums=(0, 1))(x, w)
    # STE backward equals the fake-quant matmul backward: g @ wq^T, xq^T @ g
    # — now reconstructed lazily from the int8 residuals (the fwd no longer
    # materialises f32 dequantized copies); values must be unchanged
    assert gx.shape == x.shape and gw.shape == w.shape
    xs = quant.abs_max_scale(x.astype(jnp.float32), spec.in_bits)
    ws = quant.abs_max_scale(w.astype(jnp.float32), spec.w_bits)
    fq_x = quant.dequantize(quant.quantize(x.astype(jnp.float32), xs,
                                           spec.in_bits), xs)
    fq_w = quant.dequantize(quant.quantize(w.astype(jnp.float32), ws,
                                           spec.w_bits), ws)
    g = jnp.ones((16, 8), jnp.float32)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(g @ fq_w.T),
                               rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(fq_x.T @ g),
                               rtol=1e-6, atol=0)


def test_ops_batched_input():
    spec = CIMSpec()
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (2, 5, 1024))
    w = jax.random.normal(jax.random.fold_in(key, 1), (1024, 12))
    y = ops.cim_matmul(x, w, spec, None)
    assert y.shape == (2, 5, 12)
    rel = (jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
    assert float(rel) < 0.1  # noiseless (key=None) -> quantization error only


def test_ops_interpret_matches_ref_dispatch():
    """force="pallas_interpret" and force="ref" run the same construction."""
    xq, wq = _rand_operands(32, 1536, 24)
    sigma, scale = 2.5, 0.01
    y_p = ops.cim_matmul_int(xq, wq, jnp.int32(99), sigma, scale=scale,
                             force="pallas_interpret")
    y_r = ops.cim_matmul_int(xq, wq, jnp.int32(99), sigma, scale=scale,
                             force="ref")
    # ulp slack as in the oracle tests above, shrunk by the 0.01 scale
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               rtol=5e-6, atol=2e-5)


# ------------------------------------------------------- ragged-K sigma bug


def test_per_tile_sigma_consistent_with_total():
    spec = CIMSpec()
    for k in (512, 640, 1024, 1536, 4096):
        t = -(-k // spec.macro_rows)
        per = output_noise_std_int_per_tile(spec, k)
        np.testing.assert_allclose(per * np.sqrt(t),
                                   output_noise_std_int(spec, k), rtol=1e-12)


def test_ragged_k_sigma_matches_bit_exact():
    """Regression (K % macro_rows != 0): the behavioral ops path must carry
    the same total noise power as the bit-exact chain, whose analog gain is
    fitted to the true K. The old per-tile sigma used gain(macro_rows),
    overstating noise by sqrt(macro_rows/K) for K < macro_rows (~27% at
    K=640)."""
    spec = CIMSpec()
    m, k, n, reps = 64, 640, 16, 8
    qx = quant.qmax(spec.in_bits)
    key = jax.random.PRNGKey(3)
    kx, kw = jax.random.split(key)
    xq = jax.random.randint(kx, (m, k), -qx, qx + 1)
    wq = jax.random.randint(kw, (k, n), -qx, qx + 1)
    exact = (xq @ wq).astype(jnp.float32)

    # behavioral path injects the *total* per-tile sigma (quant + noise +
    # static INL/DNL power as an equivalent Gaussian)
    sigma = output_noise_std_int_per_tile(spec, k)
    errs = []
    for r in range(reps):
        y = ops.cim_matmul_int(xq, wq, jnp.int32(1000 + r), sigma, force="ref")
        errs.append(np.asarray(y - exact))
    std_behav = np.concatenate(errs).std()
    pred_total = output_noise_std_int(spec, k, include_static=True)
    assert abs(std_behav / pred_total - 1.0) < 0.05, (std_behav, pred_total)

    # bit-exact repeat-to-repeat variance isolates the *random* part; its
    # gain is fitted to the true K — the quantity the old full-tile sigma
    # overstated
    ys = jnp.stack([
        cim_matmul_bit_exact(xq, wq, jax.random.fold_in(key, r), spec)
        for r in range(reps)
    ])
    std_bit = float(jnp.sqrt(jnp.mean(jnp.var(ys, axis=0)) * reps / (reps - 1)))
    pred_noise = output_noise_std_int(spec, k, include_static=False)
    assert 0.75 < std_bit / pred_noise < 1.25, (std_bit, pred_noise)

    # and the old (buggy) full-tile sigma is measurably different
    old_sigma = output_noise_std_int(spec, spec.macro_rows)
    assert old_sigma / sigma > 1.2


# ---------------------------------------------------------------- flash attn

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref

FLASH_SHAPES = [
    (4, 256, 256, 64, True),    # square causal, block-aligned
    (2, 200, 200, 64, True),    # ragged causal
    (3, 128, 384, 128, False),  # cross-attention (non-causal, t > s)
    (1, 130, 257, 64, True),    # ragged both dims
]


@pytest.mark.parametrize("bh,s,t,d,causal", FLASH_SHAPES)
def test_flash_attention_matches_oracle(bh, s, t, d, causal):
    key = jax.random.PRNGKey(s + t)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (bh, s, d))
    k = jax.random.normal(kk, (bh, t, d))
    v = jax.random.normal(kv, (bh, t, d))
    y = flash_attention(q, k, v, causal=causal, interpret=True)
    y_ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


@settings(deadline=None, max_examples=8)
@given(s=st.integers(16, 200), d=st.sampled_from([64, 128]),
       seed=st.integers(0, 2**31 - 1))
def test_flash_attention_property(s, d, seed):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, s, d))
    k = jax.random.normal(kk, (2, s, d))
    v = jax.random.normal(kv, (2, s, d))
    y = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
    y_ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_block_pruning():
    """k blocks above the causal frontier must be *skipped*, not masked:
    the per-q-block compute counts must equal ceil((qi_max+1)/block_k) —
    the ~2x the original kernel docstring left as future work — while the
    output stays bit-identical to the unpruned oracle path."""
    bq = bk = 64
    bh, s, t, d = 2, 256, 256, 64
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (bh, s, d))
    k = jax.random.normal(kk, (bh, t, d))
    v = jax.random.normal(kv, (bh, t, d))
    y, counts = flash_attention(q, k, v, causal=True, block_q=bq,
                                block_k=bk, interpret=True,
                                return_block_counts=True)
    n_q, n_k = s // bq, t // bk
    expected = np.asarray([[-(-min((i + 1) * bq, s) // bk)
                            for i in range(n_q)]] * bh)
    np.testing.assert_array_equal(np.asarray(counts), expected)
    assert counts.sum() < bh * n_q * n_k          # strictly fewer than dense
    assert int(counts.sum()) == bh * n_q * (n_q + 1) // 2  # ~half the grid
    y_ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal_not_pruned():
    """Cross-attention (non-causal) must still visit every k block."""
    bh, s, t, d = 2, 64, 192, 64
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (bh, s, d))
    k = jax.random.normal(kk, (bh, t, d))
    v = jax.random.normal(kv, (bh, t, d))
    _, counts = flash_attention(q, k, v, causal=False, block_q=64,
                                block_k=64, interpret=True,
                                return_block_counts=True)
    assert int(np.asarray(counts).sum()) == bh * 1 * (t // 64)


@pytest.mark.parametrize("starts", [[0, 7, 20], [0, 0, 0], [54, 1, 33]])
def test_flash_attention_start_offsets(starts):
    """Per-row start offsets (slot-cache prefill semantics): query i of
    row b attends keys j <= start[b]+i and j < start[b]+s, matching the
    extended oracle — including rows starting mid-cache."""
    s, t, d = 10, 64, 64
    key = jax.random.PRNGKey(sum(starts))
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (3, s, d))
    k = jax.random.normal(kk, (3, t, d))
    v = jax.random.normal(kv, (3, t, d))
    st_arr = jnp.asarray(starts, jnp.int32)
    y = flash_attention(q, k, v, causal=True, start=st_arr, block_q=8,
                        block_k=8, interpret=True)
    y_ref = flash_attention_ref(q, k, v, causal=True, start=st_arr)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_start_prunes_per_row():
    """Pruning is per-row dynamic under start offsets: a row starting at 0
    computes fewer k blocks than a row starting deep in the cache."""
    s, t, d = 8, 64, 64
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, s, d))
    k = jax.random.normal(kk, (2, t, d))
    v = jax.random.normal(kv, (2, t, d))
    _, counts = flash_attention(q, k, v, causal=True,
                                start=jnp.asarray([0, 40], jnp.int32),
                                block_q=8, block_k=8, interpret=True,
                                return_block_counts=True)
    counts = np.asarray(counts)
    assert counts[0, 0] == 1          # rows 0..7 live in block 0 only
    assert counts[1, 0] == 6          # rows 40..47 need blocks 0..5
    assert counts[1, 0] > counts[0, 0]


# ------------------------------------------------- GQA-native flash prefill

from repro.kernels.flash_attention import (flash_gqa_attention,
                                           flash_gqa_modeled_cost)
from repro.kernels.ref import flash_gqa_ref

GQA_SHAPES = [
    # (b, s, t, h, kv, d, starts)
    (2, 10, 64, 8, 2, 64, [0, 17]),     # G=4, ragged starts
    (1, 33, 96, 4, 4, 32, [60]),        # G=1 (MHA), s not block-aligned
    (3, 16, 80, 6, 3, 16, [0, 5, 64]),  # G=2, t with non-pow2 divisor
    (2, 1, 48, 8, 2, 32, [0, 40]),      # single-token chunk
]


def _gqa_operands(key, b, s, t, h, kv, d, int8=False):
    """q (b, s, h, d) and a lane-dense (b, t, kv·d) slot cache (int8 +
    (b, t, kv, 1) scales when ``int8``)."""
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, t, kv, d))
    v = jax.random.normal(kv_, (b, t, kv, d))
    if not int8:
        return q, k.reshape(b, t, kv * d), v.reshape(b, t, kv * d), None, None
    ks = jnp.maximum(jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0, 1e-8)
    vs = jnp.maximum(jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0, 1e-8)
    k8 = jnp.clip(jnp.round(k / ks), -127, 127).astype(jnp.int8)
    v8 = jnp.clip(jnp.round(v / vs), -127, 127).astype(jnp.int8)
    return q, k8.reshape(b, t, kv * d), v8.reshape(b, t, kv * d), ks, vs


@pytest.mark.parametrize("b,s,t,h,kv,d,starts", GQA_SHAPES)
def test_flash_gqa_matches_oracle(b, s, t, h, kv, d, starts):
    q, k, v, _, _ = _gqa_operands(jax.random.PRNGKey(s + t), b, s, t, h, kv, d)
    st = jnp.asarray(starts, jnp.int32)
    y = flash_gqa_attention(q, k, v, start=st, block_q=8, block_k=16,
                            interpret=True)
    y_ref = flash_gqa_ref(q, k, v, start=st)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,s,t,h,kv,d,starts", GQA_SHAPES[:2])
def test_flash_gqa_int8_matches_oracle(b, s, t, h, kv, d, starts):
    """int8 KV dequantises on the VMEM-resident block in-kernel — the
    cache never round-trips HBM at f32."""
    q, k8, v8, ks, vs = _gqa_operands(jax.random.PRNGKey(3), b, s, t, h, kv,
                                      d, int8=True)
    st = jnp.asarray(starts, jnp.int32)
    y = flash_gqa_attention(q, k8, v8, start=st, ks=ks, vs=vs, block_q=8,
                            block_k=16, interpret=True)
    y_ref = flash_gqa_ref(q, k8, v8, start=st, ks=ks, vs=vs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa_block_shape_invariance():
    """Re-blocking shifts only the online-softmax accumulation order —
    outputs must agree to f32 accumulation tolerance across block sizes."""
    b, s, t, h, kv, d = 2, 24, 96, 8, 2, 32
    q, k, v, _, _ = _gqa_operands(jax.random.PRNGKey(11), b, s, t, h, kv, d)
    st = jnp.asarray([0, 50], jnp.int32)
    outs = [np.asarray(flash_gqa_attention(q, k, v, start=st, block_q=bq,
                                           block_k=bk, interpret=True))
            for bq, bk in [(8, 8), (8, 32), (32, 16), (128, 96)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-6, atol=2e-6)


def test_flash_gqa_matches_replicated_mha_path():
    """The GQA-native kernel must reproduce the replicated-KV wrapper it
    replaced (repeat KV heads G-fold, fold (B, H) into MHA rows) — same
    block partitioning, so the online-softmax accumulation order is
    identical and agreement is bit-level."""
    b, s, t, h, kv, d = 2, 16, 64, 8, 2, 32
    g = h // kv
    q, k, v, _, _ = _gqa_operands(jax.random.PRNGKey(5), b, s, t, h, kv, d)
    st = jnp.asarray([0, 37], jnp.int32)
    bq, bk = 8, 16
    y = flash_gqa_attention(q, k, v, start=st, block_q=bq, block_k=bk,
                            interpret=True)
    # the old wrapper, verbatim: G-fold repeat + (B, H) row fold
    kx = jnp.repeat(k.reshape(b, t, kv, d), g, axis=2)
    vx = jnp.repeat(v.reshape(b, t, kv, d), g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = kx.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = vx.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    y_rep = flash_attention(qf, kf, vf, causal=True,
                            start=jnp.repeat(st, h), block_q=bq, block_k=bk,
                            interpret=True)
    y_rep = y_rep.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_rep))


def test_flash_gqa_causal_pruning_counts():
    """k blocks above the per-row causal frontier must be skipped, and the
    (B, KV, n_q) counts witness must match the closed form
    ceil((start + qi_max + 1)/block_k) — identically across KV heads."""
    b, s, t, h, kv, d = 2, 32, 64, 4, 2, 32
    q, k, v, _, _ = _gqa_operands(jax.random.PRNGKey(8), b, s, t, h, kv, d)
    starts = [0, 30]
    st = jnp.asarray(starts, jnp.int32)
    bq, bk = 8, 16
    y, counts = flash_gqa_attention(q, k, v, start=st, block_q=bq,
                                    block_k=bk, interpret=True,
                                    return_block_counts=True)
    counts = np.asarray(counts)
    n_q, n_k = s // bq, t // bk
    expected = np.asarray(
        [[[min(n_k, (stt + min((i + 1) * bq, s) - 1) // bk + 1)
           for i in range(n_q)] for _ in range(kv)] for stt in starts])
    np.testing.assert_array_equal(counts, expected)
    assert counts[1].sum() > counts[0].sum()      # deeper start, more blocks
    assert counts.sum() < b * kv * n_q * n_k      # strictly pruned
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(flash_gqa_ref(q, k, v, start=st)),
                               rtol=2e-5, atol=2e-5)


def test_flash_prefill_is_gqa_native():
    """The acceptance witness for DESIGN.md §13: the prefill wrapper must
    not head-replicate the cache (``jnp.repeat``) or dequantise it up
    front — both copies now happen (or rather, don't) in-kernel."""
    import inspect

    from repro.models.attention import _flash_prefill

    src = inspect.getsource(_flash_prefill)
    assert "repeat(" not in src, "G-fold KV replication is back"
    assert "flash_gqa_attention" in src


def test_flash_gqa_modeled_cost():
    """KV-stream model: the f32 ratio is exactly the group size G (same
    columns, H vs KV rows), int8 adds the 4x storage-width win; the
    materialise term scales with the whole cache, not the visited blocks."""
    m32 = flash_gqa_modeled_cost(b=4, s=32, t=256, h=8, kv_heads=2, d=64,
                                 start=128, kv_bytes=4)
    assert m32["kv_stream_ratio"] == pytest.approx(4.0)     # G = 4
    m8 = flash_gqa_modeled_cost(b=4, s=32, t=256, h=8, kv_heads=2, d=64,
                                start=128, kv_bytes=1)
    assert m8["kv_stream_ratio"] > 3.5 * 4                  # ~4G (+scales)
    assert m8["total_ratio"] > m8["kv_stream_ratio"]        # + materialise
    # pruning: a zero-start launch visits fewer blocks than a deep one
    shallow = flash_gqa_modeled_cost(b=1, s=32, t=256, h=8, kv_heads=2,
                                     d=64, start=0)
    deep = flash_gqa_modeled_cost(b=1, s=32, t=256, h=8, kv_heads=2, d=64,
                                  start=192)
    assert shallow["visited_blocks"] < deep["visited_blocks"]
