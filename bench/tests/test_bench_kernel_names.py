"""Every kernel a per-layer metric times is in the compiled programs under
the name the metric matches.

A roofline metric finds its kernel's device time by the HLO instruction
name the trace prints (``KERNEL`` in ``bench/metrics/<metric>.py``); a
kernel renamed in the program would make the metric read nothing. These
tests compile the benchmark cell's engine programs (``step``, ``decode``,
``prefill_chunk``) for a described v5e, as ``test_bench_fits.py`` does,
and look for an instruction whose name, as ``bench/trace.py`` reduces it,
matches each ``KERNEL``. The ``cim_*`` metrics read the macro's kernel,
which runs only in ``sim``: their programs are compiled from
``bench/configs/qwen2-0.5b-sim.json`` at the same cell's shape, ``decode``
alone (each program runs every linear through the kernel).
"""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace, weights  # noqa: E402

fits = harness.load_module(Path(__file__).with_name("test_bench_fits.py"),
                           "test_bench_fits")
one_chip = fits.one_chip

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = SPEC["workloads"][0]["name"]
SIM = json.loads((ROOT / "bench" / "configs" / "qwen2-0.5b-sim.json")
                 .read_text())
KERNELS = {p.stem: harness.load_module(p, p.stem).KERNEL
           for p in sorted((ROOT / "bench" / "metrics").glob("*.py"))
           if "KERNEL = " in p.read_text()}


def _names(config: dict, programs, one_chip) -> set:
    import jax

    import repro.models.transformer as tf
    import repro.serving.engine as engine

    c = harness.load_cell(CELL, SPEC)
    init, deploy = tf.init_caches, engine._maybe_deploy
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tf, "init_caches", lambda *a, **k: jax.eval_shape(
            lambda: init(*a, **k)))
        # deployed planes from shapes alone
        mp.setattr(engine, "_maybe_deploy", lambda cfg, params, *a, **k:
                   jax.eval_shape(lambda p: deploy(cfg, p, *a, **k), params))
        eng = engine.Engine(
            harness.model_config(config), weights.layout(config),
            max_slots=c.shape["max_slots"], max_len=c.shape["max_len"],
            cim_mode=config["serving"]["cim_mode"], seed=1,
            chunk_size=c.shape["chunk_size"],
            deploy=config["serving"]["deployed_planes"])
        mp.setattr(jax, "default_backend", lambda: "tpu")
        names = set()
        for p in programs:
            text = eng._programs[p].lower(
                *fits._args(eng, one_chip, p)).compile().as_text()
            names |= {trace._op(line.strip(), 0, 0).name
                      for line in text.splitlines()
                      if line.lstrip().startswith("%")}
    return names


@pytest.fixture(scope="module")
def compiled(one_chip):
    got = {}

    def names(sim: bool) -> set:
        if sim not in got:
            got[sim] = (_names(SIM, ("decode",), one_chip) if sim else
                        _names(harness.load_cell(CELL, SPEC).config,
                               fits.PROGRAMS, one_chip))
        return got[sim]

    return names


def test_every_roofline_metric_names_a_kernel():
    assert set(KERNELS) >= {"attn_roofline.chat", "prefill_attn_roofline.chat",
                            "cim_roofline.chat"}


@pytest.mark.parametrize("metric", sorted(KERNELS))
def test_kernel_is_in_the_compiled_programs(compiled, metric):
    rx = re.compile(KERNELS[metric])
    names = compiled(metric.startswith("cim_"))
    assert any(rx.search(n) for n in names), (
        metric, KERNELS[metric], sorted(n for n in names if "_" in n)[:40])
