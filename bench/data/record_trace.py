#!/usr/bin/env python3
"""Record the small trace that the reduction's test reads.

  python3 bench/data/record_trace.py <out_dir>

Run on a TPU. Inside the harness's window annotation it makes three
"ticks" (a jitted step holding a Pallas kernel and a matmul), each after a
short "loadgen" pause in which the device idles, and copies the
``.xplane.pb`` to ``<out_dir>/small_trace.xplane.pb``.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from bench import trace  # noqa: E402


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def step(x):
    y = pl.pallas_call(_double, out_shape=jax.ShapeDtypeStruct(x.shape,
                                                               x.dtype))(x)
    return jnp.tanh(y @ y)


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    x = jnp.ones((512, 512), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("loadgen"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("tick"):
                x = step(x)
                x.block_until_ready()
    jax.profiler.stop_trace()
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(trace.find_xplane(tmp), Path(out) / "small_trace.xplane.pb")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
