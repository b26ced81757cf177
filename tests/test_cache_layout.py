"""The GQA slot cache is stored lane-dense, ``(L, B, T, KV·D)``.

A minor ``(KV, D)`` pair such as qwen2-0.5b's 2 x 64 does not fill the
TPU's (8, 128) tiles, so XLA kept the stacked cache sequence-minor and
converted the whole of it to row-major and back around every program that
handed it to a Pallas kernel. Merging the heads into one minor dim gives
the plain row-major layout the kernels read (DESIGN.md §10/§11).

On the CPU: the cache leaves' shape, both attention kernels (interpret
mode) against their ``kernels/ref.py`` oracles at cache widths KV·D of
128, 1024 and one below 128, and the fused single-launch step against the
per-call path, token for token. On a *described* v5e (no TPU attached,
nothing runs): the benchmark cell's ``step`` and ``decode`` programs hold
no copy or transpose of the whole stacked cache, and no relayout copy of
one layer's slice.
"""

import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_gqa_attention
from repro.kernels.ref import decode_attention_ref, flash_gqa_ref
from repro.models import transformer as tf
from repro.models.model import build
from repro.serving.engine import Engine, Request

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, weights  # noqa: E402

# the described-v5e fixture and program arguments of the benchmark's own
# compile test
fits = harness.load_module(ROOT / "bench" / "tests" / "test_bench_fits.py",
                           "test_bench_fits")
one_chip = fits.one_chip

# ------------------------------------------------------------ cache leaves


@pytest.mark.parametrize("name,int8,path", [
    ("qwen2-0.5b", False, ()),
    ("internlm2-1.8b", True, ()),
    ("zamba2-7b", False, ("attn",)),
])
def test_gqa_cache_leaves_are_lane_dense(name, int8, path):
    cfg = dataclasses.replace(get_config(name).reduced(), kv_cache_int8=int8)
    caches = jax.eval_shape(lambda: tf.init_caches(cfg, 3, 16))
    for key in path:
        caches = caches[key]
    lead = caches["len"].shape               # (layers, batch)
    width = cfg.n_kv_heads * cfg.hd
    for leaf in ("k", "v"):
        assert caches[leaf].shape == (*lead, 16, width), leaf
        assert caches[leaf].dtype == (jnp.int8 if int8 else
                                      jnp.dtype(cfg.dtype))
    if int8:
        for leaf in ("ks", "vs"):
            assert caches[leaf].shape == (*lead, 16, cfg.n_kv_heads, 1)


# ------------------------------------------------- kernels vs their oracles

# (H, KV, D): KV·D = 128 (qwen2-0.5b's 2 x 64), 1024 (internlm2-1.8b's
# 8 x 128) and 32, below one lane tile
WIDTHS = [(14, 2, 64), (16, 8, 128), (4, 2, 16)]
DTYPES = ["float32", "bfloat16", "int8"]
TOL = {"float32": 2e-5, "bfloat16": 2e-2, "int8": 2e-5}


def _cache(key, b, t, kv, d, dtype):
    """A lane-dense (b, t, kv·d) K and V, plus (b, t, kv, 1) scales for
    int8."""
    kk, kv_ = jax.random.split(key)
    k = jax.random.normal(kk, (b, t, kv, d))
    v = jax.random.normal(kv_, (b, t, kv, d))
    if dtype != "int8":
        return (k.reshape(b, t, kv * d).astype(dtype),
                v.reshape(b, t, kv * d).astype(dtype), None, None)
    out = []
    for x in (k, v):
        sc = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0, 1e-8)
        out.append((jnp.clip(jnp.round(x / sc), -127, 127)
                    .astype(jnp.int8).reshape(b, t, kv * d), sc))
    (k8, ks), (v8, vs) = out
    return k8, v8, ks, vs


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("h,kv,d", WIDTHS)
def test_decode_attention_lane_dense_matches_oracle(h, kv, d, dtype):
    b, t = 3, 48
    key = jax.random.PRNGKey(h * d + kv)
    qdt = "bfloat16" if dtype == "bfloat16" else "float32"
    q = jax.random.normal(key, (b, h, d)).astype(qdt)
    k, v, ks, vs = _cache(jax.random.fold_in(key, 1), b, t, kv, d, dtype)
    lens = jnp.asarray([1, 29, 48], jnp.int32)
    y = decode_attention(q, k, v, lens, ks=ks, vs=vs, block_k=16,
                         interpret=True)
    r = decode_attention_ref(q, k, v, lens, ks=ks, vs=vs)
    assert y.shape == (b, h, d)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("h,kv,d", WIDTHS)
def test_flash_gqa_lane_dense_matches_oracle(h, kv, d, dtype):
    b, s, t = 2, 12, 48
    key = jax.random.PRNGKey(h * d + kv + 7)
    qdt = "bfloat16" if dtype == "bfloat16" else "float32"
    q = jax.random.normal(key, (b, s, h, d)).astype(qdt)
    k, v, ks, vs = _cache(jax.random.fold_in(key, 1), b, t, kv, d, dtype)
    start = jnp.asarray([0, 30], jnp.int32)
    y = flash_gqa_attention(q, k, v, start=start, ks=ks, vs=vs, block_q=8,
                            block_k=16, interpret=True)
    r = flash_gqa_ref(q, k, v, start=start, ks=ks, vs=vs)
    assert y.shape == (b, s, h, d)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


# ---------------------------------------------- fused step == per-call path


@pytest.mark.parametrize("dtype,int8", [("bfloat16", False),
                                        ("float32", True)])
def test_fused_step_equals_per_call_through_kernels(dtype, int8):
    """Kernel attention (the benchmark cell's path) on the lane-dense
    cache: the single-launch ``step`` and the per-call launches give the
    same token streams, bit for bit."""
    cfg = dataclasses.replace(
        get_config("qwen2-0.5b").reduced(), n_layers=2, d_model=128,
        d_ff=256, vocab_size=128, n_heads=4, n_kv_heads=2, head_dim=32,
        dtype=dtype, kv_cache_int8=int8, attn_impl="kernel")
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (3, 19, 6, 11)]

    def run(fused):
        eng = Engine(cfg, params, max_slots=2, max_len=32, chunk_size=8,
                     fused_step=fused)
        out = eng.generate([Request(prompt=p, max_new_tokens=5)
                            for p in prompts])
        assert eng.fused_step_error is None
        return out

    assert run(True) == run(False)


# --------------------------------------- compiled for a described v5e chip


_MOVE = re.compile(r"=\s*(.*?)\s(copy|copy-start|transpose)\(")


def _moved_shapes(hlo: str):
    """Result shapes of every copy, copy-start and transpose in ``hlo``."""
    for line in hlo.splitlines():
        m = _MOVE.search(line)
        if m:
            for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
                yield tuple(int(x) for x in dims.split(","))


@pytest.mark.parametrize("program", ["step", "decode"])
def test_cell_programs_move_no_cache(one_chip, monkeypatch, program):
    """At the benchmark cell's shape, neither program copies, relayouts or
    transposes the whole stacked K/V cache, nor one layer's slice of it."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    c = harness.load_cell("qwen2-0.5b-off.conv-open", spec)
    init = tf.init_caches
    monkeypatch.setattr(tf, "init_caches", lambda *a, **k: jax.eval_shape(
        lambda: init(*a, **k)))
    eng = Engine(harness.model_config(c.config), weights.layout(c.config),
                 max_slots=c.shape["max_slots"], max_len=c.shape["max_len"],
                 cim_mode=c.config["serving"]["cim_mode"], seed=1,
                 chunk_size=c.shape["chunk_size"],
                 deploy=c.config["serving"]["deployed_planes"])
    whole = eng.caches["k"].shape            # (layers, slots, T, KV·D)
    assert whole[-1] == c.config["num_key_value_heads"] * c.config["head_dim"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = eng._programs[program].lower(
        *fits._args(eng, one_chip, program)).compile().as_text()
    moved = list(_moved_shapes(hlo))
    assert moved, "no copy at all: the HLO parse is broken"
    assert whole not in moved, (program, "copies the whole cache")
    layer = whole[1:]
    assert layer not in moved and (1, *layer) not in moved, (
        program, "relayouts a layer's slice")
