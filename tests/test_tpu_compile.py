"""Main-path Pallas kernels compile for a TPU v5e at qwen2-0.5b widths.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
untileable block shapes, SMEM vector loads, PRNG seeding limits, VMEM
overflow. These tests compile each kernel for a *described* v5e chip — no
TPU is attached, nothing runs — and assert the compiled program holds the
kernel's ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at import:
describing it loads the TPU compiler library, which only one process may
hold, and every test worker imports this file. All compile tests live in
this one file so they share that worker. The dense megakernel
(``fused_dense_layer``) does not fit qwen2-0.5b's widths; the engine
refuses it at construction (tests/test_megakernel.py).
"""

import os

import jax
import jax.numpy as jnp
import pytest

D, F, H, KV, HD = 896, 4864, 14, 2, 64     # qwen2-0.5b
B, T, CHUNK = 8, 2048, 64                  # decode slots, cache, prefill chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("m,k,n", [(8, D, F), (CHUNK, F, D)])
@pytest.mark.parametrize("prng", ["threefry", "hw"])
def test_cim_matmul_fused_compiles(one_chip, m, k, n, prng):
    """Decode (M=8, the 8-row int8 block) and prefill-chunk shapes, with
    the Threefry stream and the two-word-seeded hardware PRNG."""
    from repro.kernels.cim_matmul import cim_matmul_fused_pallas

    _compile(one_chip,
             lambda x, w, s, sd: cim_matmul_fused_pallas(
                 x, w, s, sd, sigma=3.0, prng_impl=prng),
             ((m, k), jnp.float32), ((k, n), jnp.int8), ((), jnp.float32),
             ((2,), jnp.int32))


def test_cim_matmul_int8_decode_block_compiles(one_chip):
    """The pre-quantized kernel takes an 8-row int8 activation block."""
    from repro.kernels.cim_matmul import cim_matmul_pallas

    _compile(one_chip,
             lambda x, w, sd: cim_matmul_pallas(x, w, sd, sigma=3.0),
             ((8, D), jnp.int8), ((D, F), jnp.int8), ((2,), jnp.int32))


@pytest.mark.parametrize("int8", [False, True])
def test_decode_attention_compiles(one_chip, int8):
    from repro.kernels.decode_attention import decode_attention

    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    shapes = [((B, H, HD), jnp.bfloat16), ((B, T, KV * HD), kv_dt),
              ((B, T, KV * HD), kv_dt), ((B,), jnp.int32)]
    if int8:
        shapes += [((B, T, KV, 1), jnp.float32)] * 2
    _compile(one_chip,
             lambda q, k, v, lens, *sc: decode_attention(
                 q, k, v, lens, *sc, interpret=False),
             *shapes)


@pytest.mark.parametrize("int8", [False, True])
def test_flash_gqa_prefill_compiles(one_chip, int8):
    """One prefill chunk per slot row against the slot cache."""
    from repro.kernels.flash_attention import flash_gqa_attention

    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    shapes = [((1, CHUNK, H, HD), jnp.bfloat16), ((1, T, KV * HD), kv_dt),
              ((1, T, KV * HD), kv_dt), ((1,), jnp.int32)]
    if int8:
        shapes += [((1, T, KV, 1), jnp.float32)] * 2
    _compile(one_chip,
             lambda q, k, v, st, *sc: flash_gqa_attention(
                 q, k, v, st, *sc, interpret=False),
             *shapes)
