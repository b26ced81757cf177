"""CIM dense fast-path microbenchmark (DESIGN.md §12).

Two before/after comparisons on the paper's serving hot path, recorded to
BENCH_kernels.json:

* **pre-quantized weight planes** — wall-clock of a decode-shaped
  ``cim_dense`` call (M = 4 serving slots) quantizing the weight per call
  (PR 3 path) vs executing on a deployed ``(wq int8, ws)`` plane
  (``core.deploy``). Same jnp behavioural construction both sides, so the
  ratio isolates exactly the per-call weight abs-max/round/clip the deploy
  pass removes; outputs are bit-identical (tested in tests/test_deploy.py).

* **decode-shaped tiles** — modeled FLOPs + HBM bytes of the Pallas kernel
  launch at M <= 8 with the auto-picked skinny tile (compiled-TPU floor:
  32 sublanes, Mosaic's native int8 tile; interpret mode can run 8) vs the
  training-shaped bm = 256 pad, via ``cim_matmul.modeled_cost``
  (block-DMA traffic model; interpret-mode wall clock is emulation, the
  model is the perf witness — same convention as attention_bench).
  Acceptance: combined (FLOPs + bytes) ratio >= 4x. The modeled weight
  stream of the fused deployed path (int8 plane in, xq never written) vs
  the old two-pass pipeline (f32 weight read + quantize + int8 re-read) is
  recorded as ``prequant_weight_hbm_ratio``.

  PYTHONPATH=src python -m benchmarks.cim_dense_bench
"""

from __future__ import annotations

import os

import jax

from benchmarks.common import time_call
from repro.core.cim import CIMSpec, cim_dense
from repro.core.deploy import quantize_plane
from repro.kernels.cim_matmul import modeled_cost

_BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")

# decode shape: M = active serving slots, (K, N) a serving-scale linear
M, K, N = 4, 2048, 512


def bench_prequant_wall() -> dict:
    spec = CIMSpec()           # 6b/6b w/CB (the MLP-class operating point)
    key = jax.random.PRNGKey(0)
    kx, kw, kn = jax.random.split(key, 3)
    x = jax.random.normal(kx, (M, K))
    w = jax.random.normal(kw, (K, N))
    wq, ws = quantize_plane(w, spec.w_bits, reduce_axes=2)

    f_fly = jax.jit(lambda x, w: cim_dense(x, w, spec, kn, mode="sim"))
    f_dep = jax.jit(lambda x, wq, ws: cim_dense(
        x, None, spec, kn, mode="sim", w_scale=ws, wq=wq))
    us_fly = time_call(f_fly, x, w)
    us_dep = time_call(f_dep, x, wq, ws)
    return {
        "decode_shape": f"{M}x{K}x{N}",
        "cim_dense_onthefly_us": us_fly,
        "cim_dense_deployed_us": us_dep,
        "cim_dense_deploy_speedup_x": us_fly / us_dep,
    }


def bench_decode_tiles() -> dict:
    # padded-grid cost of the Pallas launch: training-shaped bm=256 pad vs
    # the auto skinny tile (bit-identical under threefry; the model carries
    # the compiled-TPU 32-sublane floor so the ratio is a real launch)
    pad = modeled_cost(M, K, N, bm=256, bn=256)
    skinny = modeled_cost(M, K, N)           # auto: bm = next multiple of 8
    combined_pad = pad["flops"] + pad["hbm_bytes"]
    combined_skinny = skinny["flops"] + skinny["hbm_bytes"]

    # weight-side HBM per call: the old pipeline reads the f32 weight,
    # writes the int8 wq, then the matmul re-reads it; the deployed fused
    # path streams the resident int8 plane once
    w_bytes_old = K * N * (4 + 1 + 1)
    w_bytes_dep = K * N * 1
    return {
        "decode_bm_auto": skinny["bm"],
        "decode_flops_ratio": pad["flops"] / skinny["flops"],
        "decode_hbm_ratio": pad["hbm_bytes"] / skinny["hbm_bytes"],
        "decode_cost_ratio": combined_pad / combined_skinny,
        "prequant_weight_hbm_ratio": w_bytes_old / w_bytes_dep,
    }


def run() -> dict:
    out = bench_prequant_wall()
    out.update(bench_decode_tiles())
    from benchmarks.common import append_run
    append_run(_BENCH_JSON, out)
    return out


if __name__ == "__main__":
    for k, v in run().items():
        print(f"{k}: {v}")
