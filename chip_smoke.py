#!/usr/bin/env python3
"""On-chip smoke of the serving path at qwen2-0.5b's published widths.

Runs on one TPU (the default) or, with ``--chips 4``, on a four-chip host:

  python chip_smoke.py             # kernel, serve-off, serve-sim, fuse_layer
  python chip_smoke.py --chips 4   # four one-chip replicas behind the router

One chip, in one process:

  * kernel: each main-path Pallas kernel at qwen2-0.5b widths against its
    oracle in ``kernels/ref.py`` (the fused CIM matmul at a decode and a
    prefill-chunk M, with the Threefry and the hardware PRNG; decode
    attention on a bf16 and an int8 cache; the GQA flash prefill), each
    compiled program checked for its ``tpu_custom_call``.
  * serve-off: ``Engine`` with kernel attention and chunked prefill serves
    the requests; the model's prefill and first-decode logits on the
    kernel path agree with the einsum path within a bf16 bound.
  * serve-sim: deployed int8 planes through the Pallas CIM kernel
    (``cim.use_kernel=True``) serve the same requests; logits are finite.
  * fuse_layer: the engine either builds the dense megakernel or refuses
    it at construction with its reason.

``--chips 4`` runs only the replica phase: four ``Engine`` replicas, each
committed to its own chip, behind ``ReplicaRouter``, against one
single-chip engine on the same requests in ``off`` mode; streams must be
bit-identical, also after one replica is killed mid-stream.

Parameters come from ``init(PRNGKey(--seed))`` and prompts from the same
seed; nothing is downloaded. Times printed on the way are one unrepeated
smoke reading, not a benchmark figure. Any failed check exits non-zero.
The last line of a passing run is the JSON device record.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N_REQUESTS, PROMPT_LEN, NEW_TOKENS, SLOTS, CHUNK = 8, 128, 32, 4, 64
MAX_LEN = PROMPT_LEN + NEW_TOKENS


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(*parts) -> None:
    print(*parts, flush=True)


def compile_and_count(fn, *args) -> tuple:
    """Compile ``fn`` for ``args``; returns (compiled, tpu_custom_call count,
    compile seconds)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return (compiled, compiled.as_text().count("tpu_custom_call"),
            time.perf_counter() - t0)


# --------------------------------------------------------------- kernels


def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.core.cim import output_noise_std_int_per_tile
    from repro.core.sac import get_policy
    from repro.kernels import ref
    from repro.kernels.cim_matmul import MACRO_ROWS, cim_matmul_fused_pallas
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_gqa_attention

    cfg = get_config("qwen2-0.5b")
    d, f = cfg.d_model, cfg.d_ff
    key = jax.random.PRNGKey(seed)
    spec = get_policy(cfg.cim.policy).spec_for_role("mlp_in")
    qmax = 2 ** (spec.in_bits - 1) - 1
    # x = integer codes times a power-of-two scale: the in-kernel round/clip
    # reproduces the codes exactly, so the integer dot has one right answer
    xs = 2.0 ** -4
    for m, k, n in ((8, d, f), (CHUNK, f, d)):
        k1, k2, key = jax.random.split(key, 3)
        codes = jax.random.randint(k1, (m, k), -qmax, qmax + 1)
        x = codes.astype(jnp.float32) * xs
        wq = jax.random.randint(k2, (k, n), -127, 128).astype(jnp.int8)
        n_k = -(-k // MACRO_ROWS)
        sigma = output_noise_std_int_per_tile(spec, k)
        seed2 = jnp.array([seed, 7], jnp.int32)

        def fused(x, wq, seed2, sigma=sigma, prng="threefry"):
            return cim_matmul_fused_pallas(x, wq, xs, seed2, sigma=sigma,
                                           in_bits=spec.in_bits, scale=1.0,
                                           prng_impl=prng)

        # exact integer dot: noise off, scale 1 -> integers in f32
        comp, ncc, secs = compile_and_count(
            lambda x, w, s: fused(x, w, s, sigma=0.0), x, wq, seed2)
        got = comp(x, wq, seed2)
        want = ref.cim_matmul_fused_ref(x, wq, xs, None, 0.0, MACRO_ROWS, 1.0,
                                        spec.in_bits)
        diff = float(jnp.max(jnp.abs(got - want)))
        log(f"[kernel] cim_matmul_fused_pallas M={m} K={k} N={n} exact dot: "
            f"max_abs_diff={diff} bound=0 tpu_custom_call={ncc} "
            f"compile_s={secs:.2f}")
        check(ncc > 0 and diff == 0.0, f"fused CIM matmul M={m} exact dot")
        exact = got

        # Threefry noise: the oracle's stream, equal up to the TPU's
        # transcendentals in Box-Muller (bound in integer product units)
        comp, ncc, secs = compile_and_count(fused, x, wq, seed2)
        got = comp(x, wq, seed2)
        want = ref.cim_matmul_fused_ref(x, wq, xs, seed2, sigma, MACRO_ROWS,
                                        1.0, spec.in_bits)
        diff = float(jnp.max(jnp.abs(got - want)))
        bound = 1e-3 * n_k * max(sigma, 1.0)
        log(f"[kernel] cim_matmul_fused_pallas M={m} threefry sigma="
            f"{sigma:.3f}: max_abs_diff={diff:.3e} bound={bound:.3e} "
            f"tpu_custom_call={ncc} compile_s={secs:.2f}")
        check(ncc > 0 and diff <= bound, f"fused CIM matmul M={m} threefry")

        # hardware PRNG: its own stream, so check the noise's moments —
        # (y - exact dot) / sigma sums n_k unit normals per element
        comp, ncc, secs = compile_and_count(
            lambda x, w, s: fused(x, w, s, prng="hw"), x, wq, seed2)
        z = np.asarray((comp(x, wq, seed2) - exact) / (sigma * n_k ** 0.5))
        mean, std = float(z.mean()), float(z.std())
        mean_bound = 5.0 / z.size ** 0.5
        log(f"[kernel] cim_matmul_fused_pallas M={m} hw prng: noise mean="
            f"{mean:.4f} (bound {mean_bound:.4f}) std={std:.4f} (bound "
            f"[0.95, 1.05]) n={z.size} tpu_custom_call={ncc} "
            f"compile_s={secs:.2f}")
        check(ncc > 0 and abs(mean) <= mean_bound and 0.95 <= std <= 1.05,
              f"fused CIM matmul M={m} hw prng moments")

    b, t, h, kv, hd = 8, 2048, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.bfloat16)
    # the slot cache's lane-dense (B, T, KV·D) layout
    kc = jax.random.normal(ks[1], (b, t, kv * hd), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (b, t, kv * hd), jnp.bfloat16)
    lens = jnp.array([1, 17, 128, 129, 700, 1024, 2047, 2048], jnp.int32)
    # bf16 output of a softmax-weighted mean of unit-scale values: a few
    # bf16 ulps of |o| <= 4 (the kernel rounds p to bf16 for the PV dot)
    bound = 3e-2
    comp, ncc, secs = compile_and_count(decode_attention, q, kc, vc, lens)
    diff = float(jnp.max(jnp.abs(
        comp(q, kc, vc, lens).astype(jnp.float32)
        - ref.decode_attention_ref(q, kc, vc, lens).astype(jnp.float32))))
    log(f"[kernel] decode_attention bf16 B={b} T={t} H={h} KV={kv} D={hd}: "
        f"max_abs_diff={diff:.3e} bound={bound} tpu_custom_call={ncc} "
        f"compile_s={secs:.2f}")
    check(ncc > 0 and diff <= bound, "decode_attention bf16")

    kq = jax.random.randint(ks[3], (b, t, kv * hd), -127, 128).astype(jnp.int8)
    vq = jax.random.randint(ks[4], (b, t, kv * hd), -127, 128).astype(jnp.int8)
    sc = jnp.full((b, t, kv, 1), 1.0 / 127.0, jnp.float32)
    comp, ncc, secs = compile_and_count(
        lambda q, k, v, l, s1, s2: decode_attention(q, k, v, l, ks=s1, vs=s2),
        q, kq, vq, lens, sc, sc)
    diff = float(jnp.max(jnp.abs(
        comp(q, kq, vq, lens, sc, sc).astype(jnp.float32)
        - ref.decode_attention_ref(q, kq, vq, lens, sc, sc)
        .astype(jnp.float32))))
    log(f"[kernel] decode_attention int8 KV: max_abs_diff={diff:.3e} "
        f"bound={bound} tpu_custom_call={ncc} compile_s={secs:.2f}")
    check(ncc > 0 and diff <= bound, "decode_attention int8")

    # one prefill chunk of a slot that already holds one chunk
    qc = jax.random.normal(ks[5], (1, CHUNK, h, hd), jnp.bfloat16)
    start = jnp.array([CHUNK], jnp.int32)
    comp, ncc, secs = compile_and_count(
        lambda q, k, v, s: flash_gqa_attention(q, k, v, start=s),
        qc, kc[:1], vc[:1], start)
    diff = float(jnp.max(jnp.abs(
        comp(qc, kc[:1], vc[:1], start).astype(jnp.float32)
        - ref.flash_gqa_ref(qc, kc[:1], vc[:1], start)
        .astype(jnp.float32))))
    log(f"[kernel] flash_gqa_attention bf16 S={CHUNK} T={t} start={CHUNK}: "
        f"max_abs_diff={diff:.3e} bound={bound} tpu_custom_call={ncc} "
        f"compile_s={secs:.2f}")
    check(ncc > 0 and diff <= bound, "flash_gqa_attention")


# --------------------------------------------------------------- serving


def make_requests(cfg, seed: int, n: int = N_REQUESTS, temps=(0.0,)):
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN, dtype=np.int32)
               for _ in range(n)]
    return lambda: [Request(prompt=p, max_new_tokens=NEW_TOKENS,
                            temperature=temps[i % len(temps)],
                            rid=f"smoke-{i}")
                    for i, p in enumerate(prompts)]


def serve(engine, requests, label: str):
    """Serve the requests twice (cold, then warm); every request must
    complete with NEW_TOKENS in-vocabulary tokens on the fused step."""
    from repro.serving.engine import RequestError

    outs = None
    for run in ("cold", "warm"):
        reqs = requests()
        t0 = time.perf_counter()
        outs = engine.generate(reqs)
        dt = time.perf_counter() - t0
        errs = [o for o in outs if isinstance(o, RequestError)]
        check(not errs, f"{label}: request errors {errs[:2]}")
        check(engine._fused_ok, f"{label}: fused step fell back to per-call")
        n_tok = sum(len(o) for o in outs)
        check(all(len(o) == NEW_TOKENS for o in outs)
              and all(0 <= t < engine.cfg.vocab_size for o in outs for t in o),
              f"{label}: incomplete or out-of-vocabulary streams")
        log(f"[{label}] {run}: {len(outs)} requests, {n_tok} tokens in "
            f"{dt:.2f}s ({n_tok / dt:.1f} tok/s; smoke reading, not "
            f"measured as a benchmark)")
    return outs


def forward_logits(cfg, params, prompts, mode: str, deployed: bool, seed: int):
    """The model's cached forward as the engine drives it: two prefill
    chunks into a fresh slot cache, then one decode step. The decoded token
    is the prompt's first token, not a sampled one, so two attention paths
    decode the same input. Returns (logits of chunk 1, chunk 2, decode) and
    the tpu_custom_call count."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tf
    from repro.models.layers import Ctx

    def run(params, toks, key):
        ctx = Ctx.make(cfg, key, mode=mode, deployed=deployed)
        caches = tf.init_caches(cfg, toks.shape[0], MAX_LEN)
        l1, caches = tf.forward(params, {"tokens": toks[:, :CHUNK]}, cfg,
                                ctx, caches)
        l2, caches = tf.forward(params, {"tokens": toks[:, CHUNK:]}, cfg,
                                ctx, caches)
        l3, _ = tf.forward(params, {"tokens": toks[:, :1]}, cfg, ctx, caches)
        return l1[:, -1], l2[:, -1], l3[:, -1]

    toks = jnp.asarray(prompts)
    key = jax.random.PRNGKey(seed)
    comp, ncc, secs = compile_and_count(run, params, toks, key)
    outs = [o.astype(jnp.float32) for o in comp(params, toks, key)]
    return outs, ncc, secs


def serve_phase(seed: int, params, cfg) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import Engine

    requests = make_requests(cfg, seed)
    prompts = np.stack([r.prompt for r in requests()[:2]])

    # off: kernel attention vs the einsum reference
    logits = {}
    for impl in ("kernel", "einsum"):
        c = dataclasses.replace(cfg, attn_impl=impl)
        logits[impl], ncc, secs = forward_logits(c, params, prompts, "off",
                                                 False, seed)
        log(f"[serve-off] forward attn_impl={impl}: tpu_custom_call={ncc} "
            f"compile_s={secs:.1f}")
        check((ncc > 0) == (impl == "kernel"),
              f"attn_impl={impl} custom calls {ncc}")
    for name, a, b in zip(("chunk1", "chunk2", "decode"), logits["kernel"],
                          logits["einsum"]):
        scale = float(jnp.max(jnp.abs(b)))
        diff = float(jnp.max(jnp.abs(a - b)))
        # bf16 activations through 24 layers: the two attention paths round
        # differently (f32 online softmax vs bf16 einsum), a few percent of
        # the logit range at most
        bound = 0.05 * scale
        agree = float(jnp.mean(jnp.argmax(a, -1) == jnp.argmax(b, -1)))
        log(f"[serve-off] {name} logits kernel vs einsum: max_abs_diff="
            f"{diff:.4f} bound={bound:.4f} (5% of max |logit| {scale:.3f}) "
            f"argmax agreement={agree:.2f}")
        check(bool(jnp.all(jnp.isfinite(a))) and diff <= bound,
              f"off logits {name} kernel vs einsum")

    t0 = time.perf_counter()
    eng = Engine(cfg, params, max_slots=SLOTS, max_len=MAX_LEN, seed=seed,
                 cim_mode="off", attn_impl="kernel", chunk_size=CHUNK)
    log(f"[serve-off] engine built in {time.perf_counter() - t0:.1f}s")
    got = serve(eng, requests, "serve-off kernel")
    del eng
    ref_eng = Engine(cfg, params, max_slots=SLOTS, max_len=MAX_LEN,
                     seed=seed, cim_mode="off", attn_impl="einsum",
                     chunk_size=CHUNK)
    want = serve(ref_eng, requests, "serve-off einsum")
    del ref_eng
    same = sum(a == b for a, b in zip(got, want))
    first = sum(a[0] == b[0] for a, b in zip(got, want))
    log(f"[serve-off] greedy streams kernel vs einsum: {same}/{len(got)} "
        f"identical, first token equal in {first}/{len(got)} (bf16: streams "
        f"may part where two logits tie within the bound above)")

    # sim: deployed planes through the Pallas CIM kernel
    sim_cfg = dataclasses.replace(
        cfg, attn_impl="kernel",
        cim=dataclasses.replace(cfg.cim, mode="sim", use_kernel=True))
    t0 = time.perf_counter()
    eng = Engine(sim_cfg, params, max_slots=SLOTS, max_len=MAX_LEN,
                 seed=seed, cim_mode="sim", chunk_size=CHUNK)
    check(eng.deployed, "sim engine did not deploy its planes")
    log(f"[serve-sim] engine built (planes deployed) in "
        f"{time.perf_counter() - t0:.1f}s")
    serve(eng, requests, "serve-sim use_kernel")
    outs, ncc, secs = forward_logits(sim_cfg, eng.params, prompts, "sim",
                                     True, seed)
    finite = all(bool(jnp.all(jnp.isfinite(o))) for o in outs)
    log(f"[serve-sim] forward logits finite={finite} tpu_custom_call={ncc} "
        f"compile_s={secs:.1f}")
    check(finite and ncc > 0, "sim logits finite through the CIM kernel")
    del eng

    try:
        Engine(dataclasses.replace(cfg, fuse_layer=True), params,
               max_slots=SLOTS, max_len=MAX_LEN, cim_mode="off",
               attn_impl="kernel", chunk_size=CHUNK)
    except ValueError as e:
        log(f"[fuse_layer] refused at construction: {e}")
    else:
        raise SmokeFailure("fuse_layer engine built at qwen2-0.5b widths; "
                           "the smoke has no check for it")


# -------------------------------------------------------------- replicas


def replica_phase(seed: int, params, cfg, devices) -> None:
    from repro.core.faults import ReplicaFaultSpec
    from repro.serving.engine import Engine
    from repro.serving.router import ReplicaRouter, build_pool

    requests = make_requests(cfg, seed, temps=(0.0, 0.8))
    kw = dict(max_slots=SLOTS, max_len=MAX_LEN, cim_mode="off",
              attn_impl="kernel", chunk_size=CHUNK)
    ref = Engine(cfg, params, seed=seed, device=devices[0], **kw)
    want = serve(ref, requests, "replicas single-engine")
    del ref

    pool = build_pool(cfg, params, len(devices), devices=devices, seed=seed,
                      **kw)
    for i, (e, dev) in enumerate(zip(pool, devices)):
        where = {d for leaf in _leaves(e.caches) for d in leaf.devices()}
        check(where == {dev}, f"replica r{i} cache on {where}, not {dev}")
    log(f"[replicas] {len(pool)} replicas, cache of r_i on device i: "
        f"{[str(d) for d in devices]}")
    # the kill lands mid-decode (after the prompt chunks, half the new
    # tokens in); that run comes last, as it leaves r1 dead
    kill_at = PROMPT_LEN // CHUNK + NEW_TOKENS // 2
    for fault in (None, ReplicaFaultSpec(mode="kill", at_step=kill_at,
                                         victim=1)):
        router = ReplicaRouter(pool, replica_fault=fault)
        reqs = requests()
        t0 = time.perf_counter()
        got = router.generate(reqs)
        dt = time.perf_counter() - t0
        label = "no fault" if fault is None else f"kill r1 at step {kill_at}"
        migrated = sum(router.migrations_of(r) > 0 for r in reqs)
        same = sum(a == b for a, b in zip(got, want))
        log(f"[replicas] {label}: {same}/{len(want)} streams bit-identical "
            f"to the single engine, {migrated} migrated, in {dt:.2f}s "
            f"(smoke reading)")
        check(got == want, f"replica streams ({label}) differ from the "
                           f"single engine")
        if fault is not None:
            check(migrated > 0, "the kill migrated no in-flight request")
        for i, (e, dev) in enumerate(zip(pool, devices)):
            if e.dead is None:
                where = {d for leaf in _leaves(e.caches)
                         for d in leaf.devices()}
                check(where == {dev}, f"replica r{i} cache moved to {where}")


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package at {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import build

    cache_dir = enable_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache: {cache_dir}")
    cfg = get_config("qwen2-0.5b")
    t0 = time.perf_counter()
    params = jax.jit(lambda k: build(cfg).init(k)[0])(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"qwen2-0.5b at published widths: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}, {n_params} "
        f"params initialised in {time.perf_counter() - t0:.1f}s")

    if args.chips == 4:
        phases = [("replicas",
                   lambda: replica_phase(args.seed, params, cfg, devices))]
    else:
        phases = [("kernel", lambda: kernel_phase(args.seed)),
                  ("serve", lambda: serve_phase(args.seed, params, cfg))]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:      # noqa: BLE001 — reported, exit non-zero
            failed.append(name)
            log(f"[{name}] FAILED: {type(e).__name__}: {e}")
            import traceback
            traceback.print_exc()
        log(f"[{name}] phase took {time.perf_counter() - t0:.1f}s "
            f"(compile included; smoke reading)")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
