#!/usr/bin/env python3
"""Record the small trace of the serving program's spans that the spans
test reads (``bench/tests/test_bench_spans.py``).

  python3 bench/data/record_spans.py <out_dir>

A tiny ``Engine`` (2 layers, d_model 128, 2 slots, chunks of 8) behind a
``Frontend`` serves four scripted requests (prompts of 5, 19, 12 and 9
tokens, 4, 6, 3 and 5 new tokens), once to compile and once inside the
harness's window annotation, each ``Frontend.tick`` in a ``tick`` span.
It copies the ``.xplane.pb`` to ``<out_dir>/spans_trace.xplane.pb``. On a
TPU the trace holds the device's planes too.
"""

import dataclasses
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import trace  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.models.model import build  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.frontend import Frontend  # noqa: E402

LENS = (5, 19, 12, 9)
MAX_NEW = (4, 6, 3, 5)


def serve(eng, vocab: int) -> None:
    fe = Frontend(eng, queue_limit=8, max_retries=0)
    rng = np.random.default_rng(7)
    for i, (n, m) in enumerate(zip(LENS, MAX_NEW)):
        fe.submit(rng.integers(0, vocab, n).tolist(), max_new=m, rid=f"r{i}")
    while fe.pending():
        with jax.profiler.TraceAnnotation("tick"):
            fe.tick()


def main(out: str) -> int:
    cfg = dataclasses.replace(
        get_config("qwen2-0.5b").reduced(), n_layers=2, d_model=128,
        d_ff=256, vocab_size=128, n_heads=4, n_kv_heads=2, head_dim=32)
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_slots=2, max_len=48, seed=3, chunk_size=8)
    serve(eng, cfg.vocab_size)
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        serve(eng, cfg.vocab_size)
    jax.profiler.stop_trace()
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(trace.find_xplane(tmp), Path(out) / "spans_trace.xplane.pb")
    shutil.rmtree(tmp)
    print(f"record_spans: {jax.devices()[0].device_kind}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
