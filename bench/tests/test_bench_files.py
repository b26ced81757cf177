"""Every configuration, traffic mix, cell, reference and per-layer metric
loads by name, and ``BENCHMARK.json`` keeps to its format."""

import json
import os
import re
import shutil
import subprocess
import sys
from functools import reduce
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import family, harness, loadgen  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_names_units_and_whys():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(cell):
    c = harness.load_cell(cell)
    assert c.entry["chips"] == 1
    assert {"max_slots", "max_len", "chunk_size", "limits"} <= set(c.shape)
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names, (cell, m["name"])
    pool = loadgen.make_pool(c.traffic, 1, c.config["vocab_size"], 64,
                             rate=float(c.shape.get("rate_rps") or 0.0))
    assert max(i.prompt_len + i.max_new for i in pool.items) <= \
        c.shape["max_len"]


# the benchmark's configurations, and test fixtures of other families
CONFIG_FILES = sorted((BENCH / "configs").glob("*.json")) + sorted(
    (BENCH / "tests" / "data").glob("*.json"))


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_every_config_builds(path):
    config = json.loads(path.read_text())
    assert config["name"] == path.stem
    mcfg = harness.model_config(config)
    for field, key in family.published(config).items():
        assert reduce(getattr, field.split("."), mcfg) == config[key], field
    if path.parent == BENCH / "configs":
        assert (BENCH / "references" / f"{config['reference']}.py").is_file()
    for entry in SPEC["configs"]:
        if entry["name"] == path.stem:
            assert entry["file"] == f"bench/configs/{path.name}"
            assert sorted(entry["reduced"]) == sorted(config["reduced"])


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_weight_layout_is_the_programs(path):
    """The tree ``bench/weights.py`` draws has the paths, shapes and dtypes
    of the program's own ``init`` for the configuration: every leaf has a
    rule to draw it."""
    import jax

    from bench import weights
    from repro.models.model import build

    config = json.loads(path.read_text())
    drawn = jax.eval_shape(lambda: weights.make(config, 1))
    prog = jax.eval_shape(
        lambda k: build(harness.model_config(config)).init(k)[0],
        jax.random.PRNGKey(0))
    flat = lambda t: {jax.tree_util.keystr(p): (s.shape, s.dtype)
                      for p, s in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert flat(drawn) == flat(prog)


def test_a_leaf_with_no_draw_rule_raises():
    """A tree with a leaf ``bench/weights.py`` has no rule for (here an
    SSM's ``A_log``) is refused, not drawn by a guess."""
    from bench import weights

    config = json.loads((BENCH / "tests/data/moe-mla-tiny.json").read_text())
    config["program"] = {"family": "ssm", "n_layers": 2, "d_model": 64,
                         "n_heads": 2, "n_kv_heads": 2, "d_ff": 128,
                         "vocab_size": 64, "dtype": "float32",
                         "ssm": {"d_state": 16, "headdim": 32, "chunk": 32}}
    config["published"] = {"dtype": "torch_dtype"}
    config["unpublished"] = ["n_layers", "d_model", "n_heads", "n_kv_heads",
                             "d_ff", "vocab_size", "ssm.d_state",
                             "ssm.headdim", "ssm.chunk"]
    with pytest.raises(ValueError, match="no rule to draw weight .*A_log"):
        weights.make(config, 1)


@pytest.mark.parametrize("path", sorted((BENCH / "metrics").glob("*.py")),
                         ids=lambda p: p.stem)
def test_every_metric_reader_loads_and_reads_nothing_from_nothing(path):
    mod = harness.load_module(path, path.stem)
    empty = harness.Reading(
        summary=_EmptySummary(), ticks=[], recs=[],
        dims={"n_layers": 1, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
              "head_dim": 4, "d_ff": 16, "vocab_size": 32},
        config={}, shape={"max_slots": 2, "chunk_size": 4}, peaks={}, flops=None)
    assert mod.read(empty) is None


def test_every_named_file_exists():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("path", sorted((BENCH / "references").glob("*.py")),
                         ids=lambda p: p.stem)
def test_every_reference_loads(path):
    assert callable(harness.load_module(path, path.stem).logits)


class _EmptySummary:
    busy = {}
    modules = {}
    busy_s = 0.0
    window_s = 0.0

    def op_time_s(self, pattern):
        return 0.0

    def span_count(self, name):
        return 0

    def idle_in(self, name):
        return 0.0, 0


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_refuses_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode == 3 and r.stdout == ""
    assert "needs a TPU, JAX found cpu" in r.stderr


def test_refuses_in_a_directory_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout == ""
