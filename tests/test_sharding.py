"""Sharding rules + multi-device lowering (subprocess with 8 fake devices)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.sharding import default_rules


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_rule_resolution_divisibility():
    import jax
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    from repro.distributed.sharding import ShardingRules
    rules = ShardingRules(
        mesh=mesh,
        activation={"batch": ("pod", "data"), "heads": "model", "seq": None},
        param={"embed": ("pod", "data"), "heads": "model"},
    )
    # divisible -> sharded
    spec = rules.activation_spec(("batch", "seq", "heads"), (64, 128, 32))
    assert spec[0] == ("pod", "data") and spec[1] is None and spec[2] == "model"
    # non-divisible (14 heads on 16-way) -> replicated
    spec = rules.activation_spec(("batch", "seq", "heads"), (64, 128, 14))
    assert spec[2] is None
    # batch=1 (long_500k) -> replicated
    spec = rules.activation_spec(("batch",), (1,))
    assert spec[0] is None


def test_duplicate_axis_suppressed():
    from repro.distributed.sharding import ShardingRules
    mesh = FakeMesh({"data": 4, "model": 2})
    rules = ShardingRules(mesh=mesh,
                          activation={"batch": "data", "seq": "data"},
                          param={})
    spec = rules.activation_spec(("batch", "seq"), (8, 8))
    assert spec[0] == "data" and spec[1] is None  # axis used once only


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_config
    from repro.distributed.sharding import default_rules, use_rules
    from repro.models.model import build, param_specs
    import dataclasses

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced()
    api = build(cfg)
    rules = default_rules(mesh)
    pspecs, paxes = param_specs(cfg)

    def psh(spec, names):
        if isinstance(spec, dict):
            return {k: psh(spec[k], names[k]) for k in spec}
        return NamedSharding(mesh, rules.param_spec(names, spec.shape))

    pshard = psh(pspecs, paxes)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    bshard = {"tokens": NamedSharding(mesh, P("data", None)),
              "labels": NamedSharding(mesh, P("data", None))}
    with use_rules(rules):
        fn = jax.jit(lambda p, b: api.loss(p, b),
                     in_shardings=(pshard, bshard))
        lowered = fn.lower(pspecs, batch)
        compiled = lowered.compile()
    txt = compiled.as_text()
    has_coll = any(op in txt for op in
                   ("all-reduce", "all-gather", "reduce-scatter"))
    # run it for real on the fake mesh
    params, _ = api.init(jax.random.PRNGKey(0))
    params = jax.device_put(params, pshard)
    b = {"tokens": jnp.ones((8, 32), jnp.int32),
         "labels": jnp.ones((8, 32), jnp.int32)}
    b = jax.device_put(b, bshard)
    loss = float(fn(params, b))
    print(json.dumps({"collectives": has_coll, "loss": loss}))
""")


def test_multidevice_lowering_and_execution():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["collectives"] is True        # TP/DP really communicates
    assert res["loss"] > 0 and res["loss"] < 20


COMPRESSION_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.compression import compressed_dp_grads

    mesh = make_mesh((8,), ("data",))
    params = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}
    batch = {"x": jnp.arange(32.0).reshape(8, 4) / 32.0}

    def grad_fn(p, b):
        return jax.grad(lambda p: jnp.sum((b["x"] @ p["w"][:4, :]) ** 2))(p)

    g_comp = compressed_dp_grads(grad_fn, params, batch, mesh, "data",
                                 jax.random.PRNGKey(0))
    # reference: mean of per-shard grads
    gs = [grad_fn(params, {"x": batch["x"][i:i+1]}) for i in range(8)]
    g_ref = jax.tree.map(lambda *t: sum(t) / 8.0, *gs)
    rel = float(jnp.linalg.norm(g_comp["w"] - g_ref["w"]) /
                (jnp.linalg.norm(g_ref["w"]) + 1e-9))
    print(json.dumps({"rel": rel}))
""")


def test_compressed_allreduce_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", COMPRESSION_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["rel"] < 0.02, res  # int8 + stochastic rounding ~ sub-1% error


ELASTIC_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.training.checkpoint import CheckpointManager

    # save from a 4-way DP layout, restore onto 8-way (elastic rescale)
    mesh4 = make_mesh((4,), ("data",))
    state = {"w": jnp.arange(64.0).reshape(8, 8)}
    sharded4 = jax.device_put(state, jax.tree.map(
        lambda _: NamedSharding(mesh4, P("data")), state))
    ckpt = CheckpointManager("/tmp/elastic_ckpt_test", keep=1)
    ckpt.save(1, sharded4)

    mesh8 = make_mesh((8,), ("data",))
    restored, meta = ckpt.restore(1, state, shardings=jax.tree.map(
        lambda _: NamedSharding(mesh8, P("data")), state))
    ok = bool(jnp.all(restored["w"] == state["w"]))
    n_shards = len(restored["w"].sharding.device_set)
    print(json.dumps({"ok": ok, "shards": n_shards}))
""")


def test_elastic_rescale_restore():
    """Checkpoint from a 4-way mesh restores sharded onto an 8-way mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", ELASTIC_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["shards"] == 8


# ------------------------------------------- canonical axis naming (PR 10)


def test_axis_helpers_and_virtual_mesh():
    from repro.distributed.sharding import (MESH_AXES, VirtualMesh, dp_axes,
                                            mesh_axis_sizes, pp_axis, tp_axis)

    vm = VirtualMesh.make(pod=2, data=16, model=16)
    assert MESH_AXES == ("pod", "data", "model")
    assert mesh_axis_sizes(vm) == {"pod": 2, "data": 16, "model": 16}
    assert dp_axes(vm) == ("pod", "data")
    assert tp_axis(vm) == "model"
    assert pp_axis(vm) == "pod"
    assert vm.devices.size == 512

    dp_only = VirtualMesh.make(data=8)
    assert dp_axes(dp_only) == ("data",)
    assert tp_axis(dp_only) is None and pp_axis(dp_only) is None

    with pytest.raises(ValueError):
        VirtualMesh.make(rows=4)          # not a canonical axis name

    # FakeMesh/real-mesh shape ducks work through the same helpers
    assert dp_axes(FakeMesh({"data": 4, "model": 2})) == ("data",)
    assert tp_axis(FakeMesh({"data": 4})) is None


SHARDED_DEPLOY_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import dataclasses
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.configs.registry import get_config
    from repro.core.deploy import deploy
    from repro.distributed.sharding import default_rules
    from repro.models.model import build

    cfg = get_config("qwen2-0.5b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                              vocab_size=128, n_heads=4, n_kv_heads=2,
                              head_dim=32)
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    mesh = make_mesh((1, 2), ("data", "model"))
    plain = deploy(cfg, params, guard=True)
    shard = deploy(cfg, params, guard=True, rules=default_rules(mesh))

    stats = {"planes": 0, "tp_multi_device": 0, "mismatch": 0}

    def walk(a, b):
        for k in a:
            if isinstance(a[k], dict):
                walk(a[k], b[k])
            elif k.startswith(("wq", "ws", "wc")) or k.endswith(("_q", "_s")):
                stats["planes"] += 1
                assert isinstance(b[k].sharding, NamedSharding), k
                if len(b[k].sharding.device_set) > 1:
                    stats["tp_multi_device"] += 1
                if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                    stats["mismatch"] += 1

    walk(plain, shard)

    # the sharded plane is executable: dequantized matmul on the 2-device
    # mesh against the single-device reference
    p = jax.tree.map(lambda t: t[0], shard["blocks"]["attn"]["q"])
    pr = jax.tree.map(lambda t: t[0], plain["blocks"]["attn"]["q"])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, cfg.d_model))
    f = jax.jit(lambda w, s, v: (v @ w.astype(jnp.float32)) * s)
    bits = [k[2:] for k in p if k.startswith("wq")][0]
    y = f(p["wq" + bits], p["ws" + bits], x)
    y_ref = f(pr["wq" + bits], pr["ws" + bits], x)
    stats["exec_max_err"] = float(jnp.max(jnp.abs(y - y_ref)))
    print(json.dumps(stats))
""")


def test_sharded_deploy_two_device_bit_identical():
    """deploy(rules=) on a forced 2-device TP mesh: plane values stay
    bit-identical to the single-device deploy (sharding is placement only)
    and the sharded planes actually span both devices and execute."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SHARDED_DEPLOY_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["planes"] > 0
    assert res["mismatch"] == 0
    assert res["tp_multi_device"] > 0
    assert res["exec_max_err"] == 0.0
