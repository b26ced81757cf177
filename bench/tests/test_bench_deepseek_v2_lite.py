"""The DeepSeek-V2-Lite configuration: its file states the published model
and the cut, the served path agrees with the plain reference
(``bench/references/mla_moe.py``) at a tiny size on the CPU, the new
kernels keep the names their metrics read, and the new readers compute
their shares from a reading made by hand.

The tiny fixture (``bench/tests/data/deepseek-v2-lite-tiny.json``) has the
configuration's shape: MLA without a query low-rank projection, the
latent RMSNorm, YaRN, one leading dense layer and two expert layers of 16
routed experts of which the device holds 4, top 3 without
renormalisation, one shared expert.
"""

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, harness, spans, trace, weights  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "deepseek-v2-lite-ep8-off"
CELL = f"{NAME}.conv-open"
CONFIG = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
TINY = json.loads((BENCH / "tests" / "data" / "deepseek-v2-lite-tiny.json")
                  .read_text())
REF = harness.load_module(BENCH / "references" / "mla_moe.py", "mla_moe")

# huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json, the
# keys that give the language model's shape
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}


# ------------------------------------------------------------- the file

@pytest.mark.parametrize("config", [CONFIG, TINY], ids=("lite", "tiny"))
def test_rope_scaling_fields_equal_the_published_dict(config):
    """``family.py`` compares flat keys only: the program's YaRN fields are
    pinned here to the file's published ``rope_scaling``."""
    mcfg = harness.model_config(config)
    pub = config["rope_scaling"]
    assert set(config["program"]["rope_scaling"]) == set(pub)
    for k, v in pub.items():
        assert getattr(mcfg.rope_scaling, k) == v, k
    assert sorted(config["unpublished"]) == sorted(
        f"rope_scaling.{k}" for k in pub)


def test_the_file_states_the_published_model_and_the_cut():
    for k, v in PUBLISHED.items():
        if k != "n_routed_experts":
            assert CONFIG[k] == v, k
    assert CONFIG["reduced"] == ["n_routed_experts"]
    assert CONFIG["n_routed_experts"] == 8
    assert CONFIG["n_routed_experts_published"] == 64
    assert CONFIG["held_first_expert"] == 0
    assert "8 chips" in CONFIG["assumed"]["deployment"]
    pub = CONFIG["published"]
    assert pub["moe.n_experts"] == "n_routed_experts_published"
    assert pub["moe.n_held"] == "n_routed_experts"
    mcfg = harness.model_config(CONFIG)
    assert (mcfg.moe.n_experts, mcfg.moe.top_k, mcfg.moe.held) == (64, 6, 8)
    assert mcfg.mla.q_lora is None and not mcfg.moe.norm_topk
    entry = next(c for c in SPEC["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "conv-open", 1)


# ------------------------------------------------ served vs reference

def _gaps(lg, toks):
    return lg.max(-1) - lg[np.arange(len(toks)), toks]


def test_served_path_matches_the_reference():
    """Chunked prefill then cached decode through ``Engine`` (the Pallas
    kernels in interpret mode) serve what the reference's full forward
    puts first, and the same cached path's logits equal the reference's."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tf
    from repro.models.layers import Ctx
    from repro.serving.engine import Engine, Request

    mcfg = harness.model_config(TINY)
    w = weights.make(TINY, 11)
    eng = Engine(mcfg, w, max_slots=3, max_len=64, chunk_size=8, seed=5)
    rng = np.random.default_rng(4)
    reqs = [Request(prompt=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=k) for n, k in ((5, 6), (19, 4), (12, 7))]
    outs = eng.generate(reqs)
    assert eng.fused_step_error is None
    assert sum(eng.counters.expert_rows) > 0
    for r, toks in zip(reqs, outs):
        seq = np.array(r.prompt + toks[:-1], np.int32)
        lg = REF.logits(w, TINY, seq, len(r.prompt) - 1)
        # float32 on both sides: served tokens are the reference's argmax
        # up to summation-order rounding
        assert _gaps(lg, np.array(toks)).max() < 1e-4
    # logits of the cached path: two chunks of 8, then three decode steps
    prompt = np.array(reqs[1].prompt[:16], np.int32)
    toks = [int(t) for t in rng.integers(0, 256, 3)]
    caches = tf.init_caches(mcfg, 1, 64)
    got = []
    for piece in (prompt[:8], prompt[8:], *[[t] for t in toks]):
        lg, caches = tf.forward(w, {"tokens": jnp.asarray([piece])}, mcfg,
                                Ctx.make(mcfg), caches)
        got.append(np.asarray(lg[0]))
    got = np.concatenate(got)
    want = REF.logits(w, TINY, np.concatenate([prompt, toks]), 0)
    # float32; the kernels' absorbed attention sums in another order
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert jax.tree.leaves(caches)  # the cache held both layer groups
    assert set(caches) == {"lead", "blocks"}


def test_float8_control_and_altered_tokens_are_told_apart():
    """The reference at float8 and every token altered by one both read
    gaps far above the served path's."""
    w = weights.make(TINY, 12)
    seq = np.random.default_rng(6).integers(0, 256, 40).astype(np.int32)
    lg = REF.logits(w, TINY, seq, 0)
    lo = REF.logits(w, TINY, seq, 0, low=True)
    assert _gaps(lg, lo.argmax(-1)).max() > 1e-3
    assert _gaps(lg, (lg.argmax(-1) + 1) % 256).max() > 1e-3


# ----------------------------------------------------------- kernels

fits = harness.load_module(Path(__file__).with_name("test_bench_fits.py"),
                           "test_bench_fits")
one_chip = fits.one_chip
OPS = {n: harness.load_module(BENCH / "metrics" / f"{n}.py", n).OP
       for n in ("mla_attn_roofline.chat", "mla_prefill_roofline.chat",
                 "expert_roofline.chat")}


def test_new_kernels_keep_their_names_in_the_compiled_step(one_chip,
                                                           monkeypatch):
    """Each roofline reader finds its kernel in the cell's fused step,
    compiled for a described v5e at the cell's shape."""
    import jax

    import repro.models.transformer as tf
    from repro.serving.engine import Engine

    c = harness.load_cell(CELL, SPEC)
    init = tf.init_caches
    monkeypatch.setattr(tf, "init_caches", lambda *a, **k: jax.eval_shape(
        lambda: init(*a, **k)))
    eng = Engine(harness.model_config(c.config), weights.layout(c.config),
                 max_slots=c.shape["max_slots"], max_len=c.shape["max_len"],
                 cim_mode="off", seed=1, chunk_size=c.shape["chunk_size"],
                 deploy=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = eng._programs["step"].lower(
        *fits._args(eng, one_chip, "step")).compile().as_text()
    ops = [trace._op(line.strip(), 0, 0) for line in text.splitlines()
           if line.lstrip().startswith("%")]
    names = {o.name for o in ops}
    for metric, op in OPS.items():
        assert any(re.search(op, n) for n in names), (metric, op)
    # the staged expert weights that expert_roofline.chat times with gmm
    assert {"bf16[8,2048,1408]", "bf16[8,1408,2048]"} <= {o.result for o in ops}


# ----------------------------------------------------------- readers

def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py", name)


def _hand_reading():
    dev = "/device:TPU:0"
    ops = [trace.Op("mla_decode", 0, 2_000_000, ""),      # 2 ms
           trace.Op("mla_prefill", 0, 5_000_000, ""),     # 5 ms
           trace.Op("gmm", 0, 4_000_000, ""),             # 4 ms
           # the expert weights staged for gmm: 6 ms
           trace.Op("dynamic-slice_bitcast_fusion", 0, 4_000_000,
                    "bf16[8,2048,1408]"),
           trace.Op("dynamic-slice_bitcast_fusion", 0, 2_000_000,
                    "bf16[8,1408,2048]"),
           # a latent cache slice of the same fusion kind is not theirs
           trace.Op("dynamic-slice_bitcast_fusion", 0, 9_000_000,
                    "bf16[32,4096,512]")]
    summary = trace.Summary(window=(0, 100_000_000),
                            busy={dev: [(0, 50_000_000)]},  # 50 ms
                            ops={dev: ops}, modules={dev: 3}, spans={})
    r = harness.Reading(
        summary=summary,
        ticks=[harness.Tick(decode_lens=[100, 200], chunks=[]),
               harness.Tick(decode_lens=[], chunks=[(512, 100, True)])],
        recs=[], dims=None, config=CONFIG, shape={},
        peaks=peaks_for("TPU v5 lite"), flops=flops)
    r.spans = spans.Spans((0, 100_000_000), [
        spans.Span("engine.drain.experts", 1, 2,
                   {"routed_rows": 300, "expert_pad_rows": 100,
                    "expert_groups": 200, "expert_rows": "", "launches": 2},
                   None),
        spans.Span("engine.drain.experts", 3, 4,
                   {"routed_rows": 100, "expert_pad_rows": 100,
                    "expert_groups": 8, "expert_rows": "", "launches": 1},
                   None)])
    return r


BF16, HBM = 197e12, 819e9


def test_readers_on_a_hand_built_reading():
    r = _hand_reading()
    # decode: 300 live keys, 16 heads of absorbed work 2(512+64) + 2·512,
    # latent rows of 576 bf16 channels plus 2 rows of q and out; 27 layers
    flop = 16 * 2176 * 300
    byt = 300 * 576 * 2 + 2 * 16 * (2 * 512 + 64) * 2
    want = 27 * max(flop / BF16, byt / HBM) / 2e-3 * 100
    assert _reader("mla_attn_roofline.chat").read(r) == pytest.approx(want)
    # prefill: 100 queries at 512: 100·512 + 100·101/2 pairs; the absorbed
    # form is the fewer operations for so few queries (the up-projected
    # one costs 640 a pair-head, plus 612 keys at 2·512·16·256)
    pairs = 100 * 512 + 100 * 101 / 2
    absorbed = 16 * 2176 * pairs
    assert absorbed < 16 * 640 * pairs + 612 * 2 * 512 * 16 * 256
    byt = 612 * 576 * 2 + 100 * 16 * (2 * 512 + 64) * 2
    want = 27 * max(absorbed / BF16, byt / HBM) / 5e-3 * 100
    assert _reader("mla_prefill_roofline.chat").read(r) == pytest.approx(want)
    # experts: 400 routed rows through 3 matrices of 2048 x 1408; 208
    # (layer, expert) weight sets read once, each row in and out once; the
    # time is gmm's 4 ms and its weights' staging 6 ms
    flop = 400 * 6 * 2048 * 1408
    byt = 208 * 3 * 2048 * 1408 * 2 + 400 * 2 * 2048 * 2
    want = max(flop / BF16, byt / HBM) / 10e-3 * 100
    assert _reader("expert_roofline.chat").read(r) == pytest.approx(want)
    assert _reader("expert_pad_share.chat").read(r) == pytest.approx(
        100 * 200 / 600)
    # step: per token, per layer, the linears q 2048·3072, kv
    # 2048·576 + 512·16·256 and o 16·128·2048; attention 640 a key-head;
    # layer 0's SwiGLU of 10944, 26 layers' shared SwiGLU of 2·1408 and
    # router of 64; the head of 102400 for decoded tokens and the prompt's
    # last; the routed rows
    lin = 2 * (2048 * 3072 + 2048 * 576 + 512 * 16 * 256 + 2048 * 2048)
    ffn = 6 * 2048 * 10944 + 26 * (6 * 2048 * 2816 + 2 * 2048 * 64)
    head = 2 * 2048 * 102400
    need = sum(27 * (lin + 16 * 640 * c) + ffn + head for c in (100, 200))
    need += 27 * (lin * 100 + 16 * 640 * pairs) + ffn * 100 + head
    need += 400 * 6 * 2048 * 1408
    want = need / BF16 / 50e-3 * 100
    assert _reader("mla_moe_step_mfu.chat").read(r) == pytest.approx(want)


def test_readers_read_nothing_without_the_programs_expert_spans():
    """A program without expert spans (a parent that lacks them) leaves
    the span-read metrics out rather than raising."""
    r = _hand_reading()
    r.spans = spans.Spans((0, 1), [])
    for name in ("expert_roofline.chat", "expert_pad_share.chat",
                 "mla_moe_step_mfu.chat"):
        assert _reader(name).read(r) is None

