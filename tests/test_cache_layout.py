"""The GQA slot cache is stored lane-dense, ``(L, B, T, KV·D)``.

A minor ``(KV, D)`` pair such as qwen2-0.5b's 2 x 64 does not fill the
TPU's (8, 128) tiles, so XLA kept the stacked cache sequence-minor and
converted the whole of it to row-major and back around every program that
handed it to a Pallas kernel. Merging the heads into one minor dim gives
the plain row-major layout the kernels read (DESIGN.md §10/§11).

The stack is addressed in place by (layer, row): each attention kernel
reads its layer and rows through its index maps, and a launch writes only
the rows it adds (DESIGN.md §10).

On the CPU: the cache leaves' shape, both GQA attention kernels
(interpret mode) against their ``kernels/ref.py`` oracles at cache widths
KV·D of 128, 1024 and one below 128, all four attention kernels on the
stack by (layer, row) against themselves on the sliced operand and their
oracles, the positions-minor column write against a scatter, the fused
single-launch step against the per-call path token for token, a recycled
slot against a fresh engine, and the ``cache_inplace`` witness of every
launch. On a *described* v5e (no TPU attached, nothing runs): every
benchmark cell's ``step`` and ``decode`` programs hold no copy or
transpose of any whole stacked cache leaf, and no operation whose result
is one layer's slice of it or one slot's row, in any axis order.
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
from repro.kernels.cache_write import write_columns
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_gqa_attention
from repro.kernels.mla_decode import mla_decode_attention
from repro.kernels.mla_prefill import mla_prefill_attention
from repro.kernels.ref import (decode_attention_ref, flash_gqa_ref,
                               mla_decode_attention_ref,
                               mla_prefill_attention_ref)
from repro.models import transformer as tf
from repro.models.model import build
from repro.serving import engine as engine_mod
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


# ------------------------------------- kernels on the stack by (layer, row)

STACK = (3, 4)                 # layers and cache rows of the stack
ATTN_KERNELS = ([("decode_attention", dt) for dt in DTYPES]
                + [("flash_gqa_attention", dt) for dt in DTYPES]
                + [(k, dt) for k in ("mla_decode", "mla_prefill")
                   for dt in ("float32", "bfloat16")])


def _attention_case(kernel, dtype, key):
    """(run, oracle, stacked cache operands) of one kernel: ``run(ops,
    **at)`` calls it on ``ops`` with the ``layer``/``rows`` in ``at``,
    ``oracle(ops)`` is its ``kernels/ref.py`` oracle on sliced ops."""
    n_l, n_r = STACK
    b, s, t = 3, 12, 48
    lens = jnp.asarray([1, 29, 48], jnp.int32)
    start = jnp.asarray([0, 30, 7], jnp.int32)
    kq, kc = jax.random.split(key)
    qdt = "bfloat16" if dtype == "bfloat16" else "float32"
    if kernel in ("decode_attention", "flash_gqa_attention"):
        h, kv, d = 4, 2, 16
        ops = tuple(None if x is None else x.reshape(n_l, n_r, *x.shape[1:])
                    for x in _cache(kc, n_l * n_r, t, kv, d, dtype))
        if kernel == "decode_attention":
            q = jax.random.normal(kq, (b, h, d)).astype(qdt)
            return (lambda o, **at: decode_attention(
                        q, o[0], o[1], lens, ks=o[2], vs=o[3], block_k=16,
                        interpret=True, **at),
                    lambda o: decode_attention_ref(q, o[0], o[1], lens,
                                                   ks=o[2], vs=o[3]), ops)
        q = jax.random.normal(kq, (b, s, h, d)).astype(qdt)
        return (lambda o, **at: flash_gqa_attention(
                    q, o[0], o[1], start=start, ks=o[2], vs=o[3], block_q=8,
                    block_k=16, interpret=True, **at),
                lambda o: flash_gqa_ref(q, o[0], o[1], start=start, ks=o[2],
                                        vs=o[3]), ops)
    h, lat, r = 4, 32, 16
    k1, k2 = jax.random.split(kc)
    ops = (jax.random.normal(k1, (n_l, n_r, t, lat)).astype(dtype),
           jax.random.normal(k2, (n_l, n_r, r, t)).astype(dtype))
    shape = (b, h) if kernel == "mla_decode" else (b, s, h)
    ql = jax.random.normal(kq, (*shape, lat)).astype(qdt)
    qr = jax.random.normal(jax.random.fold_in(kq, 1), (*shape, r)).astype(qdt)
    if kernel == "mla_decode":
        return (lambda o, **at: mla_decode_attention(
                    ql, qr, *o, lens, scale=0.2, block_k=16, **at),
                lambda o: mla_decode_attention_ref(ql, qr, *o, lens, 0.2),
                ops)
    return (lambda o, **at: mla_prefill_attention(
                ql, qr, *o, start, scale=0.2, block_k=16, **at),
            lambda o: mla_prefill_attention_ref(ql, qr, *o, start, 0.2), ops)


@pytest.mark.parametrize("kernel,dtype", ATTN_KERNELS)
def test_kernel_reads_the_stack_by_layer_and_row(kernel, dtype):
    """Given the whole (L, R, T, W) stack, a layer and each query's cache
    row, every attention kernel gives what it gives on that layer's rows
    sliced out, bit for bit, and agrees with its oracle."""
    run, oracle, ops = _attention_case(kernel, dtype, jax.random.PRNGKey(5))
    layer, rows = 1, jnp.asarray([3, 0, 2], jnp.int32)
    sliced = tuple(None if x is None else x[layer][rows] for x in ops)
    got = np.asarray(run(ops, layer=jnp.int32(layer), rows=rows), np.float32)
    np.testing.assert_array_equal(got, np.asarray(run(sliced), np.float32))
    np.testing.assert_allclose(got, np.asarray(oracle(sliced), np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [256, 64])
def test_column_write_equals_a_scatter(dtype, t):
    """``write_columns`` sets each row's column of a positions-minor
    stack at (layer, row, position) and nothing else, as the scatter it
    replaces does, dropping a position past the end."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    stack = jax.random.normal(k1, (3, 5, 16, t)).astype(dtype)
    cols = jax.random.normal(k2, (4, 16)).astype(dtype)
    rows = jnp.asarray([4, 0, 2, 1], jnp.int32)
    pos = jnp.asarray([0, t - 1, t // 2 + 3, t], jnp.int32)
    got = write_columns(stack, cols, jnp.int32(1), rows, pos)
    want = stack.at[1, rows[:, None], :, pos[:, None]].set(
        cols[:, None], mode="drop")
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# ---------------------------------------------- fused step == per-call path

TINY_MLA = json.loads((ROOT / "bench" / "tests" / "data"
                       / "deepseek-v2-lite-tiny.json").read_text())


def _engine_model(name):
    """A tiny served model and its weights: dense GQA (bf16, or f32 with
    an int8 cache), MLA with experts and a leading dense layer at the
    widths of the DeepSeek-V2-Lite fixture, or zamba2's hybrid."""
    if name == "mla":
        cfg = harness.model_config(TINY_MLA)
    elif name == "zamba2":
        cfg = get_config("zamba2-7b").reduced()
    else:
        dtype, int8 = {"gqa-bf16": ("bfloat16", False),
                       "gqa-int8": ("float32", True)}[name]
        cfg = dataclasses.replace(
            get_config("qwen2-0.5b").reduced(), n_layers=2, d_model=128,
            d_ff=256, vocab_size=128, n_heads=4, n_kv_heads=2, head_dim=32,
            dtype=dtype, kv_cache_int8=int8)
    cfg = dataclasses.replace(cfg, attn_impl="kernel")
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize("model", [
    pytest.param("gqa-bf16", id="bfloat16-False"),
    pytest.param("gqa-int8", id="float32-True"), "mla"])
def test_fused_step_equals_per_call_through_kernels(model):
    """Kernel attention (the benchmark cells' path) on the lane-dense
    cache: the single-launch ``step`` and the per-call launches give the
    same token streams, bit for bit."""
    cfg, params = _engine_model(model)
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


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("model", ["gqa-bf16", "mla"])
def test_recycled_slot_serves_like_a_fresh_engine(model, fused):
    """A slot restarts at length 0 and keeps its previous occupant's rows
    past that length: a short prompt served after a long one in the same
    slot gives the tokens a fresh engine gives it."""
    cfg, params = _engine_model(model)
    rng = np.random.default_rng(11)
    long_p, short_p = (rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                       for n in (27, 5))

    def short(first=()):
        eng = Engine(cfg, params, max_slots=1, max_len=40, chunk_size=8,
                     fused_step=fused)
        reqs = [Request(prompt=p, max_new_tokens=n, temperature=1.0, rid=r)
                for p, n, r in first] + [
            Request(prompt=short_p, max_new_tokens=6, temperature=1.0,
                    rid="short")]
        return eng.generate(reqs)[-1]

    assert short([(long_p, 9, "long")]) == short()


@pytest.mark.parametrize("model,inplace", [("gqa-bf16", 1), ("mla", 1),
                                           ("zamba2", 0)])
def test_every_launch_reports_in_place_addressing(model, inplace,
                                                  monkeypatch):
    """``cache_inplace`` on every ``engine.launch.*`` span, and the
    counters: attention caches are addressed in place by every program
    (fused and per-call), zamba2's hybrid cache is sliced per slot."""
    cfg, params = _engine_model(model)
    stats = []

    class Span:
        def __init__(self, name, **kw):
            if name.startswith("engine.launch."):
                stats.append(kw)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(engine_mod, "TraceAnnotation", Span)
    rng = np.random.default_rng(5)
    for fused in (True, False):
        eng = Engine(cfg, params, max_slots=2, max_len=32, chunk_size=8,
                     fused_step=fused)
        eng.generate([Request(prompt=rng.integers(0, cfg.vocab_size, n,
                                                  dtype=np.int32),
                              max_new_tokens=3) for n in (4, 13, 2)])
        c = eng.counters
        assert (c.cache_inplace_launches, c.cache_slice_launches) == (
            c.launches * inplace, c.launches * (1 - inplace))
    assert stats and all(s["cache_inplace"] == inplace for s in stats)


# --------------------------------------- compiled for a described v5e chip


_OP = re.compile(r"=\s*(.*?)\s([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
# ops that move no data: a name, a free reshape, or a loop's or branch's
# result, of a buffer made elsewhere (the ops inside them are checked), or
# the end of an async copy (checked at its start)
_ALIASES = ("parameter", "bitcast", "get-tuple-element", "tuple", "while",
            "conditional", "copy-done")
# ops that write rows into their operand in place; a ``custom-call`` is a
# Pallas kernel writing into the operand it aliases
_WRITES = ("dynamic-update-slice", "scatter", "custom-call")
_LAYOUT = re.compile(r"\[[\d,]*\]\{([^}]*)\}")


def _squeeze(shape):
    return tuple(d for d in shape if d != 1)


def _staged(op: str, types: str) -> bool:
    """Whether ``op`` with result ``types`` moves one buffer between HBM
    and on-chip memory (``S(1)``) as it is laid out: memory-space
    assignment staging a buffer that fits on chip, not a relayout."""
    if op != "copy-start":
        return False
    dst, src = _LAYOUT.findall(types)[:2]
    return ("S(1)" in dst + src
            and dst.replace("S(1)", "") == src.replace("S(1)", ""))


def _results(hlo: str):
    """(op, result shape, staged) of every instruction in ``hlo``, a
    fusion named by its fused computation's root op; a tuple result gives
    one per element."""
    roots, comp, lines = {}, None, hlo.splitlines()
    for line in lines:
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
        elif comp and line.lstrip().startswith("ROOT"):
            roots[comp] = _OP.search(line).group(2)
    for line in lines:
        m = _OP.search(line)
        if not m:
            continue
        op = m.group(2)
        if op == "fusion":
            op = roots.get(_CALLS.search(line).group(1), op)
        staged = _staged(op, m.group(1))
        for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
            yield op, tuple(int(x) for x in dims.split(",")), staged


@pytest.mark.parametrize("program", ["step", "decode"])
@pytest.mark.parametrize("cell", [w["name"] for w in fits.SPEC["workloads"]])
def test_cell_programs_move_no_cache(one_chip, monkeypatch, cell, program):
    """At each benchmark cell's shape, no operation of either program
    makes a whole stacked cache leaf, one layer's slice of it
    ``(slots, T, W)`` (or ``(1, slots, T, W)``) or one slot's row
    ``(layers, 1, T, W)`` / ``(1, 1, T, W)``, in any axis order — no copy,
    relayout, transpose or slice: the stack is addressed in place by
    (layer, row). Only ops that move no data and writes into a whole leaf
    in place may have such a result, and the one-layer stack of a leading
    dense layer, which is also its own layer slice, may be staged in
    on-chip memory as it is laid out."""
    c = harness.load_cell(cell, fits.SPEC)
    init = tf.init_caches
    monkeypatch.setattr(tf, "init_caches", lambda *a, **k: jax.eval_shape(
        lambda: init(*a, **k)))
    eng = Engine(harness.model_config(c.config), weights.layout(c.config),
                 max_slots=c.shape["max_slots"], max_len=c.shape["max_len"],
                 cim_mode=c.config["serving"]["cim_mode"], seed=1,
                 chunk_size=c.shape["chunk_size"],
                 deploy=c.config["serving"]["deployed_planes"])
    leaves = [(jax.tree_util.keystr(p), x.shape) for p, x
              in jax.tree_util.tree_leaves_with_path(eng.caches)
              if x.ndim >= 4]
    assert {n.split("'")[-2] for n, _ in leaves} & {"k", "ckv"}, leaves
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = eng._programs[program].lower(
        *fits._args(eng, one_chip, program)).compile().as_text()
    results = list(_results(hlo))
    wholes = {_squeeze(whole) for _, whole in leaves}
    for name, whole in leaves:
        assert any(shape == whole for _, shape, _ in results), (
            name, "the HLO parse does not see the stack")
        # a row is (T, W), or the rope keys' (R, T)
        n_layers, slots, *row = whole
        parts = {tuple(sorted(x)) for x in (
            whole,                                          # the stack
            (slots, *row),                                  # a layer
            (1, slots, *row),                               # ... kept 4-D
            (n_layers, 1, *row), (1, 1, *row))}             # a slot's row
        for op, shape, staged in results:
            if op in _ALIASES or (_squeeze(shape) in wholes
                                  and (op in _WRITES or staged)):
                continue
            # in any axis order: a relayout is as much a copy as a slice
            assert tuple(sorted(shape)) not in parts, (
                program, name, op, shape)
