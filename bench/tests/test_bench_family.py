"""A configuration of any family the program builds enters the benchmark
through its own file, and the dense files build what they built before.

Frozen here are the dense mapping from a file's published keys to the
program's ``ModelConfig`` and the hand-written dense weight tree with its
draw, as the harness had them before the tree became the program's own:
today's files must build the same ``ModelConfig`` and draw bit-identical
weights at every seed. A ``moe`` file with MLA attention
(``bench/tests/data/moe-mla-tiny.json``) is served through the harness's
``Driver`` on the CPU.
"""

import dataclasses
import functools
import json
import os
import re
import shutil
import subprocess
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, loadgen, weights  # noqa: E402

FIXTURE = BENCH / "tests" / "data" / "moe-mla-tiny.json"


def _frozen_model_config(config):
    from repro.configs.base import CIMModelConfig, ModelConfig

    s = config["serving"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        qkv_bias=config["attention_bias"],
        rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"],
        attn_impl=s["attn_impl"],
        kv_cache_int8=s["kv_cache_dtype"] == "int8",
        cim=CIMModelConfig(mode=s["cim_mode"], policy=s["cim_policy"],
                           act_clip_sigmas=s["act_clip_sigmas"],
                           use_kernel=s["cim_use_kernel"]))


def _frozen_layout(config):
    import jax
    import jax.numpy as jnp

    n, d = config["num_hidden_layers"], config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    f = config["intermediate_size"]
    dt = jnp.dtype(config["torch_dtype"])

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    def lin(k, m, bias=False):
        p = {"w": leaf(n, k, m)}
        if bias:
            p["b"] = leaf(n, m)
        return p

    bias = config["attention_bias"]
    return {
        "blocks": {
            "attn": {"q": lin(d, q, bias), "k": lin(d, kv, bias),
                     "v": lin(d, kv, bias), "o": lin(q, d)},
            "mlp": {"gate": lin(d, f), "up": lin(d, f), "down": lin(f, d)},
            "n1": {"g": leaf(n, d)}, "n2": {"g": leaf(n, d)},
        },
        "embed": {"e": leaf(config["vocab_size"], d)},
        "final_norm": {"g": leaf(d)},
    }


def _frozen_draw(key, path, shape, dtype):
    import jax
    import jax.numpy as jnp

    leaf = path.rsplit("[", 1)[-1].strip("]'\"")
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf == "w":
        v = z / jnp.sqrt(jnp.float32(shape[-2]))
    elif leaf == "b":
        v = 0.1 * z
    elif leaf == "g":
        v = 1.0 + 0.1 * z
    elif leaf == "e":
        v = 0.02 * z
    else:
        raise ValueError(f"no rule to draw weight {path}")
    return v.astype(dtype)


def _frozen_make(config, seed32):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _frozen_layout(config))
    specs = [(jax.tree_util.keystr(p), s.shape, s.dtype) for p, s in leaves]

    def init(key):
        return [_frozen_draw(
            jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
            path, shape, dtype) for path, shape, dtype in specs]

    vals = jax.jit(init)(jax.random.PRNGKey(seed32))
    return jax.tree_util.tree_unflatten(treedef, vals)


# the benchmark's configurations that carry no ``program`` block
DENSE_FILES = [p for p in sorted((BENCH / "configs").glob("*.json"))
               if "program" not in json.loads(p.read_text())]


@pytest.mark.parametrize("path", DENSE_FILES, ids=lambda p: p.stem)
def test_dense_file_builds_the_frozen_model_config(path):
    config = json.loads(path.read_text())
    assert harness.model_config(config) == _frozen_model_config(config)


@pytest.mark.parametrize("seed", [1, 2**33 + 3])
def test_dense_weights_are_bit_identical_to_the_frozen_draw(seed):
    import jax

    config = json.loads((BENCH / "configs/qwen2-0.5b-off.json").read_text())
    config.update(num_hidden_layers=2, hidden_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                  intermediate_size=256, vocab_size=512)
    s32 = harness.seed32(seed)
    got, want = weights.make(config, s32), _frozen_make(config, s32)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                         jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, p
        assert np.array_equal(np.asarray(a), np.asarray(b)), p


def test_program_block_builds_the_presets_shape():
    """Nested ``moe`` and ``mla`` objects become their dataclasses: the
    fixture's block is the program's reduced deepseek-v2 preset."""
    from repro.configs.registry import get_config

    config = json.loads(FIXTURE.read_text())
    want = dataclasses.replace(get_config("deepseek-v2-236b").reduced(),
                               name=config["name"])
    assert harness.model_config(config) == want


def test_unknown_program_key_is_refused():
    config = json.loads(FIXTURE.read_text())
    config["program"]["mla"]["q_rank"] = 64
    config["unpublished"].append("mla.q_rank")
    with pytest.raises(TypeError):
        harness.model_config(config)


@pytest.mark.parametrize("field,value", [
    ("d_model", 128), ("d_ff", 256), ("moe.n_experts", 4), ("moe.top_k", 1),
    ("mla.kv_lora", 32), ("mla.v_head_dim", 16), ("dtype", "bfloat16")])
def test_program_departing_from_its_published_key_is_refused(field, value):
    """The block runs the widths and dtype the file publishes."""
    config = json.loads(FIXTURE.read_text())
    *outer, last = field.split(".")
    functools.reduce(dict.__getitem__, outer, config["program"])[last] = value
    with pytest.raises(ValueError, match=f"program {field} = "):
        harness.model_config(config)


@pytest.mark.parametrize("field", ["mla.kv_lora", "moe.n_shared", "d_ff"])
def test_program_field_without_a_published_key_is_refused(field):
    config = json.loads(FIXTURE.read_text())
    del config["published"][field]
    with pytest.raises(ValueError, match="no published key"):
        harness.model_config(config)


ROUTER = "['blocks']['moe']['router']['w']"


def test_leaf_dtypes_come_from_the_file():
    """The program's bf16 ``moe`` init keeps its router in float32: the
    tree is refused until the file states that leaf's dtype, and then
    drawn in it."""
    import jax

    config = json.loads(FIXTURE.read_text())
    config["torch_dtype"] = config["program"]["dtype"] = "bfloat16"
    with pytest.raises(ValueError, match=re.escape(f"{ROUTER} float32")):
        weights.layout(config)
    config["leaf_dtypes"] = {ROUTER: "float32"}
    drawn = jax.eval_shape(lambda: weights.make(config, 1))
    dtypes = {jax.tree_util.keystr(p): s.dtype
              for p, s in jax.tree_util.tree_flatten_with_path(drawn)[0]}
    assert dtypes.pop(ROUTER) == np.float32
    assert set(dtypes.values()) == {np.dtype(jax.numpy.bfloat16)}


def test_a_program_file_beside_the_dense_configs_passes_the_bench_tests(
        tmp_path):
    """A new family enters as new files only: in a copy of the benchmark,
    the fixture placed in ``bench/configs`` with a reference of its own
    leaves every test of the configuration files passing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    config = json.loads(FIXTURE.read_text())
    config["reference"] = "moe_mla"
    (tmp_path / "bench/configs" / FIXTURE.name).write_text(json.dumps(config))
    (tmp_path / "bench/references/moe_mla.py").write_text(
        "def logits(params, config, seq, start):\n"
        "    raise NotImplementedError\n")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    me = "bench/tests/test_bench_family.py::" + sys._getframe().f_code.co_name
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-p", "no:randomly", "bench/tests/test_bench_files.py",
         "bench/tests/test_bench_family.py", "--deselect", me],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    passed = r.stdout.split("short test summary info")[-1]
    for test in ("test_every_config_builds", "test_weight_layout_is_the_programs"):
        assert passed.count(f"PASSED bench/tests/test_bench_files.py::{test}"
                            f"[moe-mla-tiny") == 2, test
    assert "test_dense_file_builds_the_frozen_model_config[moe-mla" not in passed


def test_moe_mla_file_serves_through_the_driver():
    """Engine, weights and load from the file alone, over ``conv-open``
    at short lengths: at least 8 requests complete and none fails."""
    from repro.serving.engine import Engine
    from repro.serving.frontend import Frontend

    config = json.loads(FIXTURE.read_text())
    traffic = json.loads((BENCH / "traffic/conv-open.json").read_text())
    traffic.update(
        prompt={"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 4,
                "max": 40},
        output={"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 3,
                "max": 16},
        pool=64)
    seed = 2**31 + 29
    s32 = harness.seed32(seed)
    eng = Engine(harness.model_config(config), weights.make(config, s32),
                 max_slots=4, max_len=64, cim_mode="off", seed=s32,
                 chunk_size=16, deploy=False)
    pool = loadgen.make_pool(traffic, seed, config["vocab_size"], 64,
                             rate=200.0)
    fe = Frontend(eng, queue_limit=len(pool.items) + 1, max_retries=0,
                  clock=harness.clock)
    drv = harness.Driver(fe, pool, traffic, 0)
    drv.begin()
    drv.run_until(8, limit_s=300)
    ended = [r for r in drv.recs if r.outcome is not None]
    assert sum(r.outcome == "completed" for r in ended) >= 8
    assert all(r.outcome == "completed" for r in ended)
    for r in ended:
        toks = np.asarray(r.ticket.tokens)
        assert len(toks) == r.item.max_new
        assert toks.min() >= 0 and toks.max() < config["vocab_size"]
