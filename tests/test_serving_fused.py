"""Fused slot-batched engine (DESIGN.md §10): loop-engine equality, prefill
bucketing, ragged batched decode across cache families, request validation."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, MoEConfig
from repro.configs.registry import get_config
from repro.models import attention as attn
from repro.models import transformer as tf
from repro.models.layers import Ctx
from repro.models.model import build
from repro.serving.engine import (Engine, LoopEngine, Request, RequestError,
                                  _pow2_bucket)


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


def _ragged_requests(cfg, lens, rng):
    return [Request(prompt=rng.integers(0, cfg.vocab_size, L, dtype=np.int32),
                    max_new_tokens=3 + (i % 4))
            for i, L in enumerate(lens)]


# ------------------------------------------------------------ loop equality


def test_fused_matches_loop_greedy_ragged(dense_setup):
    """Greedy (temp=0, cim=off) fused output == frozen LoopEngine output,
    token for token, on ragged prompt lengths with slot turnover."""
    cfg, params = dense_setup
    lens = [3, 11, 6, 17, 4, 9]
    fused = Engine(cfg, params, max_slots=4, max_len=64, drain_every=5)
    loop = LoopEngine(cfg, params, max_slots=4, max_len=64)
    a = fused.generate(_ragged_requests(cfg, lens, np.random.default_rng(0)))
    b = loop.generate(_ragged_requests(cfg, lens, np.random.default_rng(0)))
    assert a == b, (a, b)


def test_fused_matches_loop_greedy_ssm():
    """Same equality for the recurrent-state (exact-length prefill) path.

    The trailing length-1 prompts recycle slots whose previous occupants
    left nonzero conv/state behind — a 1-token prefill takes the SSM decode
    branch and reads them, so prefill must zero-reset the whole slot row."""
    cfg = get_config("mamba2-130m").reduced()
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    lens = [5, 9, 3, 12, 1, 1]
    a = Engine(cfg, params, max_slots=2, max_len=48).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(1)))
    b = LoopEngine(cfg, params, max_slots=2, max_len=48).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(1)))
    assert a == b, (a, b)


def test_fused_kernel_impl_matches_einsum_greedy(dense_setup):
    """attn_impl="kernel" (length-aware Pallas decode + flash bucketed
    prefill, DESIGN.md §11) must reproduce the einsum path token for token
    on ragged prompts with slot turnover — greedy, f32 GQA."""
    cfg, params = dense_setup
    lens = [3, 11, 6, 17, 4, 9]
    a = Engine(cfg, params, max_slots=4, max_len=64,
               attn_impl="kernel").generate(
        _ragged_requests(cfg, lens, np.random.default_rng(0)))
    b = Engine(cfg, params, max_slots=4, max_len=64,
               attn_impl="einsum").generate(
        _ragged_requests(cfg, lens, np.random.default_rng(0)))
    assert a == b, (a, b)


def test_fused_kernel_impl_matches_einsum_int8():
    """Same token-for-token equality for the int8-KV cache: the kernel
    dequantises blocks in-kernel, the einsum path folds scales into
    logits/probs — greedy argmax must agree within dequant tolerance."""
    cfg = _tiny_dense_cfg(kv_cache_int8=True, dtype="float32")
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    lens = [3, 9, 5, 12]
    a = Engine(cfg, params, max_slots=2, max_len=48,
               attn_impl="kernel").generate(
        _ragged_requests(cfg, lens, np.random.default_rng(2)))
    b = Engine(cfg, params, max_slots=2, max_len=48,
               attn_impl="einsum").generate(
        _ragged_requests(cfg, lens, np.random.default_rng(2)))
    assert a == b, (a, b)


def test_single_token_budget_honored(dense_setup):
    """max_new_tokens=1 emits exactly 1 token (the frozen LoopEngine
    over-emits a 2nd at this boundary — documented seed quirk)."""
    cfg, params = dense_setup
    eng = Engine(cfg, params, max_slots=2, max_len=32)
    outs = eng.generate([Request(prompt=np.arange(1, 5 + i, dtype=np.int32),
                                 max_new_tokens=1) for i in range(3)])
    assert [len(o) for o in outs] == [1, 1, 1]


# ------------------------------------------------ chunked prefill (§13)


@pytest.mark.parametrize("int8,impl", [(False, "einsum"), (True, "einsum"),
                                       (False, "kernel"), (True, "kernel")])
def test_chunked_matches_whole_prompt_greedy(int8, impl):
    """Chunked prefill (one fixed-shape trace, decode-interleaved) must be
    token-for-token equal to the whole-prompt bucketed path on ragged
    prompts with slot turnover — f32 and int8 KV, einsum and kernel
    attention. Lengths cover < chunk, == chunk boundary, > 2 chunks, and
    a 1-token prompt into a recycled slot."""
    cfg = _tiny_dense_cfg(kv_cache_int8=int8, dtype="float32")
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    lens = [3, 18, 33, 16, 9, 1]
    a = Engine(cfg, params, max_slots=2, max_len=48, chunk_size=16,
               attn_impl=impl).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(2)))
    b = Engine(cfg, params, max_slots=2, max_len=48, chunk_size=0,
               attn_impl=impl).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(2)))
    assert a == b, (a, b)


def test_chunked_prefill_single_trace(dense_setup):
    """Every prompt length must stream through ONE compiled chunk program
    (the whole point vs O(log2 max_len) bucket traces)."""
    cfg, params = dense_setup
    eng = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=16)
    lens = [3, 4, 5, 9, 13, 17, 23, 33, 50]
    reqs = [Request(prompt=np.random.default_rng(i).integers(
                        0, cfg.vocab_size, L, dtype=np.int32),
                    max_new_tokens=2) for i, L in enumerate(lens)]
    eng.generate(reqs)
    assert eng.prefill_traces == 1


def test_chunked_default_and_fallbacks(dense_setup):
    """chunk_size=None now auto-chunks EVERY family (DESIGN.md §15): the
    old exact-length carve-outs (ssm/hybrid state, moe capacity routing)
    are covered by state-carrying chunk continuation and dropless serving
    routing. Negative sizes stay a loud error."""
    from repro.serving.engine import DEFAULT_CHUNK_SIZE

    cfg, params = dense_setup
    assert Engine(cfg, params, max_slots=1,
                  max_len=32).chunk_size == DEFAULT_CHUNK_SIZE
    for arch in ("mamba2-130m", "zamba2-7b", "olmoe-1b-7b"):
        fam_cfg = get_config(arch).reduced()
        eng = Engine(fam_cfg, params=None, max_slots=1, max_len=16)
        assert eng.chunk_size == DEFAULT_CHUNK_SIZE, arch
        assert Engine(fam_cfg, params=None, max_slots=1, max_len=16,
                      chunk_size=8).chunk_size == 8, arch
    with pytest.raises(ValueError, match="chunk_size"):
        Engine(cfg, params, max_slots=1, max_len=32, chunk_size=-2)


def test_chunked_near_max_len_boundary(dense_setup):
    """A prompt whose final padded chunk extends past max_len must not
    clamp its cache write back onto live keys: the cache over-allocates to
    the next chunk multiple."""
    cfg, params = dense_setup
    lens = [13, 14]
    a = Engine(cfg, params, max_slots=2, max_len=18, chunk_size=8).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(3)))
    b = Engine(cfg, params, max_slots=2, max_len=18, chunk_size=0).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(3)))
    assert a == b, (a, b)


def test_record_ttft(dense_setup):
    """record_ttft must stamp a first-token latency for every request."""
    cfg, params = dense_setup
    eng = Engine(cfg, params, max_slots=2, max_len=32, record_ttft=True)
    reqs = [Request(prompt=np.arange(1, 4 + i, dtype=np.int32),
                    max_new_tokens=2) for i in range(3)]
    eng.generate(reqs)
    assert len(eng.ttft_s) == 3
    assert all(t is not None and t > 0 for t in eng.ttft_s)


# --------------------------------------------------------- prefill buckets


def test_prefill_bucket_trace_count(dense_setup):
    """The legacy whole-prompt path (chunk_size=0, and the exact-length
    families' fallback) must compile at most log2(max_len) prefill
    programs (power-of-two buckets), not one per distinct length."""
    cfg, params = dense_setup
    max_len = 64
    eng = Engine(cfg, params, max_slots=2, max_len=max_len, chunk_size=0)
    lens = [3, 4, 5, 6, 7, 9, 11, 13, 17, 19, 23]
    reqs = [Request(prompt=np.random.default_rng(i).integers(
                        0, cfg.vocab_size, L, dtype=np.int32),
                    max_new_tokens=2) for i, L in enumerate(lens)]
    eng.generate(reqs)
    n_buckets = len({_pow2_bucket(L) for L in lens})
    assert eng.prefill_traces == n_buckets
    assert eng.prefill_traces <= int(math.log2(max_len))
    assert eng.prefill_traces < len(set(lens))


def test_sampling_temperature_path(dense_setup):
    """Temperature > 0 samples on device and stays in-vocab."""
    cfg, params = dense_setup
    eng = Engine(cfg, params, max_slots=2, max_len=32)
    outs = eng.generate([Request(prompt=np.arange(1, 6, dtype=np.int32),
                                 max_new_tokens=8, temperature=1.3)
                         for _ in range(3)])
    assert all(len(o) == 8 for o in outs)
    assert all(0 <= t < cfg.vocab_size for o in outs for t in o)


# ------------------------------------- ragged batched decode, per family


@pytest.mark.parametrize("kind", ["gqa", "gqa_int8", "mla"])
def test_ragged_batched_decode_equals_per_sequence(kind):
    """One batched decode step against ragged per-sequence lengths must
    bit-match decoding each sequence alone — for gqa, int8-quantized gqa,
    and MLA compressed-KV caches."""
    if kind == "mla":
        cfg = get_config("deepseek-v2-236b").reduced()
        cfg = dataclasses.replace(cfg, n_layers=1)
        init_fn, attn_fn = attn.init_mla, attn.mla_attention
        cache_init = lambda b: attn.init_mla_cache(cfg, b, 24, jnp.float32)
    else:
        cfg = _tiny_dense_cfg(kv_cache_int8=(kind == "gqa_int8"),
                              dtype="float32")
        init_fn, attn_fn = attn.init_gqa, attn.gqa_attention
        cache_init = lambda b: attn.init_gqa_cache(cfg, b, 24, jnp.float32)

    ctx = Ctx.make(cfg)
    p, _ = init_fn(jax.random.PRNGKey(0), cfg)
    lens = [5, 11, 2]
    key = jax.random.PRNGKey(1)
    xs = [jax.random.normal(jax.random.fold_in(key, i), (1, L, cfg.d_model))
          for i, L in enumerate(lens)]
    x_new = jax.random.normal(jax.random.fold_in(key, 99),
                              (len(lens), 1, cfg.d_model))

    def prefill_one(i):
        pos = jnp.arange(lens[i])[None]
        _, c = attn_fn(ctx, p, xs[i], pos, cache_init(1))
        return c

    rows = [prefill_one(i) for i in range(len(lens))]
    batched = jax.tree.map(lambda *rs: jnp.concatenate(rs, axis=0), *rows)
    assert batched["len"].tolist() == lens

    pos_b = jnp.asarray(lens, jnp.int32)[:, None]
    out_b, new_b = attn_fn(ctx, p, x_new, pos_b, batched)
    assert new_b["len"].tolist() == [L + 1 for L in lens]

    # gqa decode is bit-exact across batch shapes; MLA's absorbed-decode
    # einsums get batched differently by XLA -> f32-epsilon differences
    tol = 1e-5 if kind == "mla" else 0.0
    for i, L in enumerate(lens):
        out_1, _ = attn_fn(ctx, p, x_new[i:i + 1],
                           jnp.asarray([[L]], jnp.int32), rows[i])
        d = np.max(np.abs(np.asarray(out_b[i]) - np.asarray(out_1[0])))
        scale = np.max(np.abs(np.asarray(out_1[0]))) or 1.0
        assert d <= tol * max(scale, 1.0), (kind, i, d)


def test_slot_take_put_roundtrip_hybrid():
    """take_slot/put_slot honor the hybrid family's double-stacked mamba
    sub-tree (batch axis 2) alongside its attn caches (batch axis 1)."""
    cfg = get_config("zamba2-7b").reduced()
    caches = tf.init_caches(cfg, 3, 16)
    marked = jax.tree.map(lambda t: jnp.ones_like(t), caches)
    row = tf.take_slot(marked, 1)
    assert jax.tree.leaves(row)[0].shape != jax.tree.leaves(marked)[0].shape
    out = tf.put_slot(caches, row, 1)
    for leaf, ref in zip(jax.tree.leaves(out), jax.tree.leaves(caches)):
        assert leaf.shape == ref.shape
    # exactly the slot-1 rows became ones
    for path, leaf in jax.tree_util.tree_flatten_with_path(out)[0]:
        ax = 2 if any(getattr(p, "key", None) == "mamba" for p in path) else 1
        arr = np.asarray(leaf)
        assert np.all(np.take(arr, 1, axis=ax) == 1)
        assert np.all(np.take(arr, 0, axis=ax) == 0)


# ------------------------------------------------------------- validation


def test_request_validation_errors(dense_setup):
    cfg, params = dense_setup
    eng = Engine(cfg, params, max_slots=2, max_len=16)
    with pytest.raises(ValueError, match="overflows the engine's max_len"):
        eng.generate([Request(prompt=np.arange(14, dtype=np.int32),
                              max_new_tokens=8)])
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng.generate([Request(prompt=np.zeros(0, np.int32))])
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        eng.generate([Request(prompt=np.arange(4, dtype=np.int32),
                              max_new_tokens=0)])


def test_encdec_rejected():
    cfg = get_config("whisper-medium").reduced()
    with pytest.raises(ValueError, match="encdec"):
        Engine(cfg, params=None, max_slots=1, max_len=8)


def test_kernel_attn_impl_accepted_everywhere_bogus_rejected():
    """attn_impl='kernel' is now a real path for every decode family —
    ssm routes through kernels/ssm_scan.py and MLA through
    kernels/mla_decode.py (DESIGN.md §15) — so engine construction accepts
    it (the old loud rejection guarded a silent einsum fallback that no
    longer exists). Unknown strings still fail at construction."""
    ssm_eng = Engine(get_config("mamba2-130m").reduced(), params=None,
                     max_slots=1, max_len=8, attn_impl="kernel")
    assert ssm_eng.cfg.attn_impl == "kernel"
    mla_eng = Engine(get_config("deepseek-v2-236b").reduced(), params=None,
                     max_slots=1, max_len=8, attn_impl="kernel")
    assert mla_eng.cfg.attn_impl == "kernel"
    with pytest.raises(ValueError, match="attn_impl"):
        Engine(get_config("qwen2-0.5b").reduced(), params=None,
               max_slots=1, max_len=8, attn_impl="flash")
    with pytest.raises(ValueError, match="attn_impl"):
        LoopEngine(get_config("qwen2-0.5b").reduced(), params=None,
                   max_slots=1, max_len=8, attn_impl="flash")


# ------------------------------------------- build errors are not requests


def _trace_failure(*args):
    raise TypeError("refused while tracing")


@pytest.mark.parametrize("program,kw", [
    ("step", {}),
    ("decode", {"fused_step": False}),
    ("prefill_chunk", {"fused_step": False}),
])
def test_build_error_propagates(dense_setup, program, kw):
    """A program that fails to trace or compile raises out of generate():
    it never becomes a per-request RequestError and never flips the fused
    engine onto the per-call path."""
    cfg, params = dense_setup
    eng = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=8, **kw)
    broken = jax.jit(_trace_failure)
    eng._programs[program] = broken
    setattr(eng, "_" + program, broken)
    with pytest.raises(TypeError, match="refused while tracing"):
        eng.generate(_ragged_requests(cfg, [5, 9], np.random.default_rng(0)))
    assert eng._fused_ok
    assert all(e is None for e in eng.request_errors)


def test_sampled_streams_repeat_across_sessions(dense_setup):
    """Sampled (temperature > 0) streams depend only on (seed, rid): the
    same engine replays them across sessions, and the per-call path agrees
    with the fused step. Guards the host-state snapshot: a request key
    mutated in place after dispatch (slot recycling) must not reach a step
    that was already issued."""
    cfg, params = dense_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 24, dtype=np.int32)
               for _ in range(2)]

    def reqs():
        return [Request(prompt=p, max_new_tokens=6, temperature=t,
                        rid=f"r{i}")
                for i, (p, t) in enumerate(zip(prompts, (0.0, 0.7)))]

    fused = Engine(cfg, params, max_slots=2, max_len=48, chunk_size=4)
    percall = Engine(cfg, params, max_slots=2, max_len=48, chunk_size=4,
                     fused_step=False)
    runs = [fused.generate(reqs()) for _ in range(3)]
    runs += [percall.generate(reqs()) for _ in range(3)]
    assert all(r == runs[0] for r in runs), runs


# ----------------------------------------- per-request failure isolation


def test_prefill_exception_fails_one_request_not_batch(dense_setup):
    """A per-slot prefill exception (whole-prompt path) yields the None
    sentinel for that request only; the freed slot's next occupant and all
    other requests match a fresh engine token for token (DESIGN.md §14
    failure contract)."""
    cfg, params = dense_setup
    lens = [6, 9, 5, 7]
    eng = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=0)
    real = eng._prefill
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:  # second admit = request 1 into slot 1
            raise RuntimeError("injected prefill fault")
        return real(*a, **kw)

    eng._prefill = flaky
    out = eng.generate(_ragged_requests(cfg, lens, np.random.default_rng(4)))
    ref = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=0).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(4)))
    assert isinstance(out[1], RequestError)
    assert "injected prefill fault" in eng.request_errors[1].reason
    assert eng.request_errors[1].phase == "prefill"
    assert eng.request_errors[1].slot == 1
    for i in (0, 2, 3):
        assert out[i] == ref[i], i
        assert eng.request_errors[i] is None


def test_midprompt_chunk_abort_recycles_slot_cleanly(dense_setup):
    """Abort a chunked prefill *mid-prompt* (after its first chunk already
    wrote cache state): the request fails with the sentinel and the next
    occupant of the recycled slot — whose admit must fully re-initialise the
    dirty slot — generates token-for-token what a fresh engine produces."""
    cfg, params = dense_setup
    lens = [7, 12, 5]
    # fused_step=False: the single-launch step has no per-slot failure
    # isolation (it falls back to this per-call path when it raises)
    eng = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=4,
                 fused_step=False)
    real = eng._prefill_chunk
    calls = {"n": 0}

    # slot-ordered chunk schedule: call 1 = req0 c0, 2 = req1 c0,
    # 3 = req0 c1 (final), 4 = req1 c1 <- abort here, mid-prompt
    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("injected chunk fault")
        return real(*a, **kw)

    eng._prefill_chunk = flaky
    out = eng.generate(_ragged_requests(cfg, lens, np.random.default_rng(5)))
    ref = Engine(cfg, params, max_slots=2, max_len=64, chunk_size=4,
                 fused_step=False).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(5)))
    assert isinstance(out[1], RequestError)
    assert "injected chunk fault" in eng.request_errors[1].reason
    assert eng.request_errors[1].phase == "prefill"
    assert out[0] == ref[0]
    assert out[2] == ref[2]  # rode the recycled (dirty) slot 1


def test_decode_exception_isolated_to_victim_slot(dense_setup):
    """A persistent per-slot decode exception kills only the victim: the
    batch decode raises, the engine re-probes each active slot solo against
    the same compiled program with the same step key, the faulty slot
    becomes a retryable RequestError(phase='decode'), and every survivor's
    token stream matches a fresh engine bit for bit."""
    cfg, params = dense_setup
    lens = [6, 9, 5]
    eng = Engine(cfg, params, max_slots=3, max_len=64, chunk_size=0,
                 fused_step=False)
    real = eng._decode

    def flaky(params_, caches, last_tok, active, temps, key, rkeys,
              tok_idx, lvls, pin=None, frow=None):
        # persistent per-slot fault: raises whenever slot 1 is live, so
        # the solo isolation probe reproduces it (a transient fault that
        # passes its probe is *supposed* to survive)
        if bool(np.asarray(active)[1]):
            raise RuntimeError("injected decode fault")
        return real(params_, caches, last_tok, active, temps, key, rkeys,
                    tok_idx, lvls, pin=pin, frow=frow)

    eng._decode = flaky
    out = eng.generate(_ragged_requests(cfg, lens, np.random.default_rng(6)))
    ref = Engine(cfg, params, max_slots=3, max_len=64, chunk_size=0,
                 fused_step=False).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(6)))
    err = out[1]
    assert isinstance(err, RequestError)
    assert err.phase == "decode"
    assert err.retryable is True
    assert err.slot == 1
    assert "injected decode fault" in err.reason
    assert out[0] == ref[0]
    assert out[2] == ref[2]


# --------------------------------- incremental session API + cancellation


def test_incremental_session_matches_generate(dense_setup):
    """begin/submit/step/drain must be bit-identical to generate(): both
    consume the same PRNG streams and the same scheduler order."""
    cfg, params = dense_setup
    lens = [3, 11, 6, 9]
    eng = Engine(cfg, params, max_slots=2, max_len=64)
    ref = eng.generate(_ragged_requests(cfg, lens, np.random.default_rng(7)))
    reqs = _ragged_requests(cfg, lens, np.random.default_rng(7))
    eng.begin()
    for r in reqs:
        eng.submit(r)
    while eng.has_work():
        eng.step()
    eng.drain_pending()
    assert [r.out_tokens for r in reqs] == ref
    assert all(eng.status_of(r) == "completed" for r in reqs)


def test_cancel_mid_chunked_prefill_token_clean_recycle(dense_setup):
    """Cancel a request while its chunked prefill is mid-prompt (cache
    already dirtied by earlier chunks): the slot's next occupant must
    generate token-for-token what a fresh engine produces — the PR 6
    admission reset does the cleanup, cancellation itself is free."""
    cfg, params = dense_setup
    lens = [14, 13, 6]
    mk = lambda: Engine(cfg, params, max_slots=2, max_len=64, chunk_size=4,
                        fused_step=False)
    ref_eng = mk()
    ref = ref_eng.generate(_ragged_requests(cfg, [14, 6], np.random.default_rng(8)))

    eng = mk()
    rng = np.random.default_rng(8)
    r0 = Request(prompt=rng.integers(0, cfg.vocab_size, 14, dtype=np.int32),
                 max_new_tokens=3)
    victim = Request(prompt=np.arange(13, dtype=np.int32) % cfg.vocab_size,
                     max_new_tokens=3)
    r2 = Request(prompt=rng.integers(0, cfg.vocab_size, 6, dtype=np.int32),
                 max_new_tokens=3 + (1 % 4))
    eng.begin()
    for r in (r0, victim, r2):
        eng.submit(r)
    eng.step()  # both slots admitted, one 4-token chunk written each
    s = next(i for i, o in enumerate(eng._slots) if o is victim)
    assert eng._offsets[s] > 0 and not eng._decoding[s], \
        "victim must be mid-prompt for the test to bite"
    assert eng.cancel(victim)
    assert eng.status_of(victim) == "cancelled"
    while eng.has_work():
        eng.step()
    eng.drain_pending()
    assert victim.out_tokens == []          # never reached decode
    assert r0.out_tokens == ref[0]
    assert r2.out_tokens == ref[1]          # rode the recycled dirty slot
    assert eng.cancel(victim) is False      # terminal: cancel is idempotent


def test_cancel_mid_decode_keeps_partial_stream(dense_setup):
    """Cancel a decoding request between steps: tokens already emitted
    stay (a prefix of the uncancelled stream), the recycled slot's next
    occupant is token-clean, and the outcome vocabulary distinguishes
    client cancellation from deadline expiry."""
    cfg, params = dense_setup
    lens = [6, 9, 5]
    full = Engine(cfg, params, max_slots=2, max_len=64).generate(
        _ragged_requests(cfg, lens, np.random.default_rng(9)))

    eng = Engine(cfg, params, max_slots=2, max_len=64)
    reqs = _ragged_requests(cfg, lens, np.random.default_rng(9))
    eng.begin()
    for r in reqs:
        eng.submit(r)
    victim = reqs[1]
    while True:
        eng.step()
        eng.drain_pending()
        if eng.status_of(victim) != "running":
            pytest.fail("victim finished before emitting a partial stream")
        if len(victim.out_tokens) >= 2:
            break
    assert eng.cancel(victim, outcome="deadline_expired")
    assert eng.status_of(victim) == "deadline_expired"
    while eng.has_work():
        eng.step()
    eng.drain_pending()
    got = victim.out_tokens
    assert 2 <= len(got) < len(full[1])
    assert got == full[1][:len(got)]        # partial stream is a prefix
    assert reqs[0].out_tokens == full[0]
    assert reqs[2].out_tokens == full[2]    # recycled slot token-clean


def test_engine_deadline_expiry_queued_and_running(dense_setup):
    """step(now) expires deadlines on the caller's clock: a queued request
    dies without ever touching a slot; a running one dies mid-decode with
    its partial tokens intact; unexpired requests are untouched."""
    cfg, params = dense_setup
    eng = Engine(cfg, params, max_slots=1, max_len=64)
    rng = np.random.default_rng(10)
    a = Request(prompt=rng.integers(0, cfg.vocab_size, 5, dtype=np.int32),
                max_new_tokens=8, deadline=50.0)
    b = Request(prompt=rng.integers(0, cfg.vocab_size, 5, dtype=np.int32),
                max_new_tokens=8, deadline=2.0)   # expires while queued
    eng.begin()
    eng.submit(a)
    eng.submit(b)
    eng.step(now=1.0)                       # a admitted (1 slot), b queued
    assert eng.status_of(b) == "queued"
    eng.step(now=3.0)                       # b's deadline passed
    assert eng.status_of(b) == "deadline_expired"
    assert b.out_tokens == []
    eng.step(now=60.0)                      # now a expires mid-decode
    assert eng.status_of(a) == "deadline_expired"
    assert not eng.has_work()
