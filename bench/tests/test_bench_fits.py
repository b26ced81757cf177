"""Every cell's engine programs compile for a TPU v5e at the cell's shape.

A serving shape whose fused iteration program does not fit the chip's
memory makes the engine refuse to serve (it raises the compiler's
RESOURCE_EXHAUSTED at its first mixed iteration), so the cell's every run
would fail. These tests build each cell's ``Engine`` from shapes alone
(no weights or cache are allocated) and compile its programs for a
*described* v5e: nothing runs, and no TPU is attached.

The topology is described inside a module-scoped fixture, never at
import: describing it loads the TPU compiler library, which only one
process may hold.
"""

import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, weights  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PROGRAMS = ("step", "decode", "prefill_chunk")


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _args(eng, one_chip, name):
    """The program's arguments as shapes on the described chip."""
    import jax
    import jax.numpy as jnp

    def on(t):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), t)

    def arr(dt, *shape):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    s, c = eng.max_slots, eng.chunk_size
    i32, u32 = jnp.int32, jnp.uint32
    head = (on(eng.params), on(eng.caches), arr(i32, s))
    if name == "step":
        return head + (arr(i32, s, 1, c), arr(i32, s, 7),
                       arr(jnp.float32, s), arr(u32, s + 1, 2),
                       arr(u32, s, 2), None)
    if name == "decode":
        return head + (arr(jnp.bool_, s), arr(jnp.float32, s),
                       arr(u32, 2), arr(u32, s, 2), arr(i32, s),
                       arr(i32, s), None)
    return head + (arr(i32, 1, c), arr(jnp.bool_), arr(i32),
                   arr(jnp.bool_), arr(i32), arr(jnp.float32),
                   arr(u32, 2), arr(u32, 2), arr(i32), None)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_programs_fit_a_v5e(one_chip, monkeypatch, cell, program):
    import jax

    import repro.models.transformer as tf
    from repro.serving.engine import Engine

    c = harness.load_cell(cell, SPEC)
    init = tf.init_caches
    monkeypatch.setattr(tf, "init_caches", lambda *a, **k: jax.eval_shape(
        lambda: init(*a, **k)))
    eng = Engine(harness.model_config(c.config), weights.layout(c.config),
                 max_slots=c.shape["max_slots"], max_len=c.shape["max_len"],
                 cim_mode=c.config["serving"]["cim_mode"], seed=1,
                 chunk_size=c.shape["chunk_size"],
                 deploy=c.config["serving"]["deployed_planes"])
    # kernels lower through Mosaic, not interpret mode
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = _args(eng, one_chip, program)
    eng._programs[program].lower(*args).compile()
