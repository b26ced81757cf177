"""Deploy pass (DESIGN.md §12): pre-quantized weight planes must reproduce
the on-the-fly quantization bit for bit, across SAC roles, families, modes
and ragged K; the fused serving engine's greedy tokens must be unchanged.

Whole-forward bitwise equality is asserted on the *unrolled* program
(scan_layers=False): with lax.scan the deployed and on-the-fly programs have
different HLO (the weight-quant ops are gone), so XLA may re-vectorize
downstream f32 reductions (rmsnorm/softmax) and shift logits by float
epsilon even though every dense output is bit-identical — the scan-mode
check is therefore epsilon-tolerant plus exact greedy-token equality at the
engine level (the user-visible invariant).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import quant
from repro.core.cim import CIMSpec, cim_dense
from repro.core.deploy import deploy, plane_summary, quantize_plane
from repro.core.sac import get_policy
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf
from repro.models.layers import Ctx, dense
from repro.models.model import build
from repro.serving.engine import Engine, LoopEngine, Request


def _tiny_dense_cfg(**over):
    cfg = get_config("qwen2-0.5b").reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                               vocab_size=128, n_heads=4, n_kv_heads=2,
                               head_dim=32, **over)


# ------------------------------------------------------------- plane quant


def test_quantize_plane_matches_per_slice_on_the_fly():
    """Batched plane quantization == abs_max_scale/quantize per layer slice
    (ragged K: 640 is neither a tile multiple nor a power of two)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 640, 48))
    for bits in (4, 6, 8):
        wq, ws = quantize_plane(w, bits, reduce_axes=2)
        assert wq.dtype == jnp.int8
        for layer in range(w.shape[0]):
            ws_ref = quant.abs_max_scale(w[layer], bits)
            wq_ref = quant.quantize(w[layer], ws_ref, bits)
            np.testing.assert_array_equal(np.asarray(ws[layer]),
                                          np.asarray(ws_ref))
            np.testing.assert_array_equal(
                np.asarray(wq[layer].astype(jnp.int32)), np.asarray(wq_ref))


def test_quantize_operands_helper_matches_legacy_chain():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (5, 96))
    w = jax.random.normal(jax.random.fold_in(key, 1), (96, 32))
    xq, xs, wq, ws = quant.quantize_operands(x, w, 6, 6)
    np.testing.assert_array_equal(
        np.asarray(xq), np.asarray(quant.quantize(x, quant.abs_max_scale(x, 6), 6)))
    np.testing.assert_array_equal(
        np.asarray(wq), np.asarray(quant.quantize(w, quant.abs_max_scale(w, 6), 6)))
    # pre-quantized plane short-circuits the weight side verbatim
    xq2, _, wq2, ws2 = quant.quantize_operands(
        x, None, 6, 6, w_scale=ws, wq=wq.astype(jnp.int8))
    np.testing.assert_array_equal(np.asarray(wq2), np.asarray(wq))
    assert ws2 is ws
    with pytest.raises(ValueError, match="w_scale"):
        quant.quantize_operands(x, None, 6, 6, wq=wq.astype(jnp.int8))


def test_cim_dense_prequant_bit_identical():
    """cim_dense on a deployed plane == cim_dense quantizing per call, bit
    for bit, for both SAC operating points and ragged K."""
    key = jax.random.PRNGKey(2)
    for spec in (CIMSpec(in_bits=4, w_bits=4, cb=False), CIMSpec()):
        for k_dim in (640, 1024):
            x = jax.random.normal(jax.random.fold_in(key, k_dim), (4, k_dim))
            w = jax.random.normal(jax.random.fold_in(key, k_dim + 1),
                                  (k_dim, 24))
            wq, ws = quantize_plane(w, spec.w_bits, reduce_axes=2)
            nk = jax.random.fold_in(key, 9)
            y_fly = cim_dense(x, w, spec, nk, mode="sim")
            y_dep = cim_dense(x, None, spec, nk, mode="sim",
                              w_scale=ws, wq=wq)
            np.testing.assert_array_equal(np.asarray(y_fly), np.asarray(y_dep))


# ------------------------------------------------ tree walk / role mapping


def test_deploy_covers_routed_roles_and_skips_digital():
    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    dep = deploy(cfg, params)
    blocks = dep["blocks"]
    pol = get_policy(cfg.cim.policy)
    # the plane key fingerprints the deployed bit-width per SAC class:
    # attention at 4b, MLP at 6b under paper_sac
    for name in ("q", "k", "v", "o"):
        sub = blocks["attn"][name]
        key = f"wq{pol.attn.w_bits}"
        assert key in sub and sub[key].dtype == jnp.int8
        assert int(np.max(np.abs(np.asarray(sub[key])))) <= \
            quant.qmax(pol.attn.w_bits)
    for name in ("gate", "up", "down"):
        assert f"wq{pol.mlp.w_bits}" in blocks["mlp"][name]
    # digital leaves untouched: embeddings carry no planes
    assert not any(k.startswith("wq") for k in dep["embed"])
    summary = plane_summary(dep)
    assert summary["planes"] == 7  # 4 attn + 3 mlp (stacked over layers)
    assert summary["f32_bytes"] == 4 * summary["int8_bytes"]


def test_deploy_moe_expert_banks():
    cfg = get_config("olmoe-1b-7b").reduced()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    dep = deploy(cfg, params)
    moe = dep["blocks"]["moe"]
    spec = get_policy(cfg.cim.policy).spec_for_role("moe_expert")
    for bank in ("w_gate", "w_up", "w_down"):
        qk, sk = f"{bank}_q{spec.w_bits}", f"{bank}_s{spec.w_bits}"
        assert qk in moe and moe[qk].dtype == jnp.int8
        # per-layer per-tensor scale, exactly _expert_dense's chain
        ws_ref = quant.abs_max_scale(moe[bank][0].astype(jnp.float32),
                                     spec.w_bits)
        np.testing.assert_array_equal(np.asarray(moe[sk][0]),
                                      np.asarray(ws_ref))
    assert not any(k.startswith("wq") for k in moe["router"])  # digital


# --------------------------------------------------- forward bit-identity


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-130m"])
def test_unrolled_forward_bit_identical(arch):
    """Deployed == on-the-fly forward, bit for bit, on the unrolled program
    (dense incl. qkv_bias, and ssm in/out projections)."""
    cfg = get_config(arch).reduced()
    if arch == "qwen2-0.5b":
        cfg = _tiny_dense_cfg()
    cfg = dataclasses.replace(cfg, scan_layers=False)
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    dep = deploy(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                              cfg.vocab_size)
    key = jax.random.PRNGKey(5)
    for mode in ("sim", "off"):
        l_fly, _ = tf.forward(params, {"tokens": toks}, cfg,
                              Ctx.make(cfg, key, mode=mode))
        l_dep, _ = tf.forward(dep, {"tokens": toks}, cfg,
                              Ctx.make(cfg, key, mode=mode,
                                       deployed=(mode == "sim")))
        np.testing.assert_array_equal(np.asarray(l_fly), np.asarray(l_dep))


def test_scanned_forward_matches_within_float_epsilon():
    """Under lax.scan the two programs have different HLO, so downstream f32
    reductions may re-vectorize — logits agree to float epsilon (each dense
    output itself is bit-identical; see module docstring)."""
    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    dep = deploy(cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                              cfg.vocab_size)
    key = jax.random.PRNGKey(5)
    l_fly, _ = tf.forward(params, {"tokens": toks}, cfg,
                          Ctx.make(cfg, key, mode="sim"))
    l_dep, _ = tf.forward(dep, {"tokens": toks}, cfg,
                          Ctx.make(cfg, key, mode="sim", deployed=True))
    np.testing.assert_allclose(np.asarray(l_fly), np.asarray(l_dep),
                               rtol=1e-5, atol=1e-5)


def test_deployed_ctx_requires_planes():
    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    ctx = Ctx.make(cfg, jax.random.PRNGKey(0), mode="sim", deployed=True)
    p = jax.tree.map(lambda t: t[0], params["blocks"]["attn"]["q"])
    x = jnp.ones((1, 2, cfg.d_model))
    with pytest.raises(ValueError, match="pre-quantized weight plane"):
        dense(ctx, p, x, "attn_qkv")


def test_policy_mismatch_planes_never_consumed():
    """Planes deployed under one policy must not be consumed when serving
    resolves a different bit-width: the bits-suffixed key misses, falling
    back to (correct) on-the-fly quantization — or raising when the ctx
    asserts deployment."""
    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    dep = deploy(cfg, params, policy=get_policy("paper_sac"))  # attn at 4b
    p = jax.tree.map(lambda t: t[0], dep["blocks"]["attn"]["q"])
    assert "wq4" in p and "wq6" not in p
    cfg6 = dataclasses.replace(
        cfg, cim=dataclasses.replace(cfg.cim, policy="uniform_6b"))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 2, cfg.d_model))
    key = jax.random.PRNGKey(4)
    # serving at 6b ignores the stale 4b plane: identical to raw params
    y_dep = dense(Ctx.make(cfg6, key, mode="sim"), p, x, "attn_qkv")
    p_raw = jax.tree.map(lambda t: t[0], params["blocks"]["attn"]["q"])
    y_raw = dense(Ctx.make(cfg6, key, mode="sim"), p_raw, x, "attn_qkv")
    np.testing.assert_array_equal(np.asarray(y_dep), np.asarray(y_raw))
    # and an asserting ctx refuses to run on the mismatched tree
    with pytest.raises(ValueError, match="w_bits=6"):
        dense(Ctx.make(cfg6, key, mode="sim", deployed=True), p, x,
              "attn_qkv")


# ----------------------------------------------------------- engine level


def test_fused_engine_greedy_unchanged_by_deploy():
    """The acceptance invariant: deploy() must not change a single greedy
    token of the fused sim-mode engine (ragged prompts, slot turnover)."""
    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    lens = [3, 11, 6, 17, 4, 9]

    def reqs():
        rng = np.random.default_rng(0)
        return [Request(prompt=rng.integers(0, cfg.vocab_size, L,
                                            dtype=np.int32),
                        max_new_tokens=3 + (i % 4))
                for i, L in enumerate(lens)]

    dep = Engine(cfg, params, max_slots=4, max_len=64, cim_mode="sim")
    raw = Engine(cfg, params, max_slots=4, max_len=64, cim_mode="sim",
                 deploy=False)
    assert dep.deployed and not raw.deployed
    a = dep.generate(reqs())
    b = raw.generate(reqs())
    assert a == b, (a, b)


def test_loop_engine_deploys_and_matches_raw():
    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    reqs = lambda: [Request(prompt=np.arange(1, 6, dtype=np.int32),
                            max_new_tokens=4) for _ in range(2)]
    dep = LoopEngine(cfg, params, max_slots=2, max_len=32, cim_mode="sim")
    raw = LoopEngine(cfg, params, max_slots=2, max_len=32, cim_mode="sim",
                     deploy=False)
    assert dep.deployed
    assert dep.generate(reqs()) == raw.generate(reqs())


def test_engine_deploy_requires_sim_mode():
    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="deploy=True"):
        Engine(cfg, params, max_slots=1, max_len=16, cim_mode="off",
               deploy=True)
    # off-mode default never deploys
    eng = Engine(cfg, params, max_slots=1, max_len=16)
    assert not eng.deployed


# --------------------------------------------- sharded deploy (PR 10, §18)


def test_sharded_deploy_bit_identical_single_device():
    """deploy(rules=) on a live 1x1 mesh: every plane carries a
    NamedSharding and every plane VALUE is bit-identical to the unsharded
    deploy — sharding is pure placement, applied after quantization,
    checksum and fault injection."""
    import jax.sharding as jsh
    from repro.distributed.sharding import default_rules

    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    plain = deploy(cfg, params, guard=True)
    sharded = deploy(cfg, params, guard=True, rules=default_rules(mesh))

    n_planes = [0]

    def walk(a, b):
        assert set(a) == set(b)
        for k in a:
            if isinstance(a[k], dict):
                walk(a[k], b[k])
            elif k.startswith(("wq", "ws", "wc")) or k.endswith(("_q", "_s")):
                n_planes[0] += 1
                assert isinstance(b[k].sharding, jsh.NamedSharding), k
                assert b[k].sharding.mesh.shape == dict(mesh.shape)
                np.testing.assert_array_equal(np.asarray(a[k]),
                                              np.asarray(b[k]))

    walk(plain, sharded)
    assert n_planes[0] > 0


def test_plan_deploy_sharding_big_configs_dryrun():
    """Shape-only TP plan on the production-sized virtual mesh: both
    scale-out target configs shard every weight plane without
    materializing a single parameter (the dryrun contract)."""
    from repro.core.deploy import plan_deploy_sharding
    from repro.distributed.sharding import (VirtualMesh, default_rules,
                                            dp_axes, tp_axis)

    vm = VirtualMesh.make(data=16, model=16)
    assert dp_axes(vm) == ("data",) and tp_axis(vm) == "model"
    for name in ("deepseek-v2-236b", "zamba2-7b"):
        cfg = get_config(name)
        plan = plan_deploy_sharding(cfg, default_rules(vm))
        assert plan["ok"], plan
        assert plan["weight_planes"] > 0
        assert plan["tp_sharded_planes"] > 0
        # sharding is real: the per-device footprint sits between perfect
        # 256-way division and a 10x reduction (replicated planes allowed)
        assert plan["int8_bytes_per_device"] >= plan["int8_bytes_total"] / 256
        assert plan["int8_bytes_per_device"] <= plan["int8_bytes_total"] / 10
        # every recorded plane resolved its logical axes
        assert all(e["logical_axes"] is not None for e in plan["entries"])


def test_plan_matches_live_rules_resolution():
    """VirtualMesh planning parity: the PartitionSpec the plan records for
    a plane equals what a live mesh of the same shape resolves — the
    virtual mesh is shape-faithful, so dryrun plans transfer."""
    from repro.core.deploy import plan_deploy_sharding
    from repro.distributed.sharding import VirtualMesh, default_rules

    cfg = _tiny_dense_cfg()
    vm_plan = plan_deploy_sharding(cfg, default_rules(VirtualMesh.make(
        data=1, model=1)))
    mesh = make_mesh((1, 1), ("data", "model"))
    live_plan = plan_deploy_sharding(cfg, default_rules(mesh))
    assert vm_plan["ok"] and live_plan["ok"]
    a = {e["path"] + "/" + e["plane"]: e["spec"] for e in vm_plan["entries"]}
    b = {e["path"] + "/" + e["plane"]: e["spec"]
         for e in live_plan["entries"]}
    assert a == b


def test_deploy_sharded_guard_segments_compose():
    """rules= and guard=GuardSpec(segments=G) compose: the segmented wc
    plane places with a trailing replicated axis and keeps its values."""
    from repro.core.guard import GuardSpec
    from repro.distributed.sharding import default_rules

    cfg = _tiny_dense_cfg()
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    plain = deploy(cfg, params, guard=GuardSpec(segments=4))
    shard = deploy(cfg, params, guard=GuardSpec(segments=4),
                   rules=default_rules(mesh))

    def walk(a, b):
        for k in a:
            if isinstance(a[k], dict):
                walk(a[k], b[k])
            elif k.startswith("wc"):
                assert a[k].ndim >= 2          # (..., K, G)
                np.testing.assert_array_equal(np.asarray(a[k]),
                                              np.asarray(b[k]))

    walk(plain, shard)
