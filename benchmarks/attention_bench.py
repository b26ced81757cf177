"""Decode-attention benchmark: length-aware Pallas kernel vs dense einsum.

The fused serving engine's decode step historically ran ``_sdpa`` over the
entire ``(B, max_len)`` slot cache and masked the dead tail — O(max_len)
FLOPs and HBM bytes per token. The ``kernels.decode_attention`` kernel
visits only ``ceil(len[b]/block_k)`` KV blocks per row (scalar-prefetched
lengths, ``pl.when`` early-out, clamped index maps), so its cost scales
with the *live* context. This bench quantifies that at the four
(max_len, live-len) cells {512, 2048} x {32, 256}.

On this CPU-only container the Pallas kernel executes in interpret mode
(a sequential lax-level emulation of the grid), so kernel wall-clock is
not the TPU number. Every interpret-mode wall-clock column is named
``*_interpret_us`` and is TREND-ONLY: it tracks emulation-overhead drift
across PRs and must never be compared against the compiled ``*_einsum_us``
columns or gated in CI (the JSON carries the same warning in
``interpret_note``). The acceptance metric is the analytic per-step
FLOP/HBM-byte ratio — the quantity the TPU kernel actually removes —
cross-checked against XLA's ``cost_analysis`` of the jitted einsum step.
The kernel model counts the blocks the grid actually computes (verified by
the block-count witness in tests/test_kernels.py for flash and the parity
suite for decode).

Results append to BENCH_attention.json at the repo root (PR-over-PR):

  PYTHONPATH=src python -m benchmarks.attention_bench
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_run, time_call
from repro.kernels.decode_attention import _pick_block_k, decode_attention
from repro.models.attention import _cached_mask, _sdpa

_BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_attention.json")

B, H, KV, D = 8, 8, 2, 64
BLOCK_K = 128
CELLS = [(512, 32), (512, 256), (2048, 32), (2048, 256)]

# acceptance (ISSUE 3): >= 3x at max_len=2048 / live-len=32
ACCEPT_CELL, ACCEPT_X = (2048, 32), 3.0


def _operands(max_len: int, live: int):
    key = jax.random.PRNGKey(max_len + live)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, max_len, KV * D), jnp.float32)
    v = jax.random.normal(kv, (B, max_len, KV * D), jnp.float32)
    lens = jnp.full((B,), live, jnp.int32)
    return q, k, v, lens


def _einsum_step(q, k, v, lens):
    """The engine's einsum decode-attention step (post cache write):
    dense scores over the whole (B, T, KV·D) cache, masked to the live
    prefix."""
    t = k.shape[1]
    return _sdpa(q[:, None], k.reshape(B, t, KV, D), v.reshape(B, t, KV, D),
                 _cached_mask(lens - 1, 1, t))[:, 0]


def _xla_cost(fn, *args) -> dict:
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):     # jax 0.4.x returns a per-device list
        ca = ca[0]
    return ca or {}


def _model(max_len: int, live: int) -> dict:
    """Analytic per-step cost: FLOPs = 4*H*D per visited KV column (q@k^T
    + p@v), HBM bytes = the k+v columns actually streamed (f32)."""
    bk = _pick_block_k(max_len, BLOCK_K)
    cols_kernel = -(-live // bk) * bk          # visited blocks, padded
    cols_einsum = max_len
    io = 2 * B * H * D * 4                     # q in + o out, both paths

    def cost(cols):
        flops = 4.0 * B * cols * H * D
        bytes_ = 2.0 * B * cols * KV * D * 4 + io
        return flops, bytes_

    fe, be = cost(cols_einsum)
    fk, bk_bytes = cost(cols_kernel)
    return {
        "kernel_block_k": bk,
        "kernel_cols": cols_kernel,
        "flops_einsum": fe,
        "flops_kernel": fk,
        "hbm_mib_einsum": be / 2**20,
        "hbm_mib_kernel": bk_bytes / 2**20,
        "speedup_flops_x": fe / fk,
        "speedup_bytes_x": be / bk_bytes,
    }


def run() -> dict:
    out = {"shape": f"B{B}_H{H}_KV{KV}_D{D}",
           "interpret_note": ("*_interpret_us columns are interpret-mode "
                              "(CPU-emulated) wall clock: trend-only, not "
                              "comparable to *_einsum_us, never gated")}
    for max_len, live in CELLS:
        q, k, v, lens = _operands(max_len, live)
        tag = f"L{max_len}_live{live}"

        einsum_us = time_call(jax.jit(_einsum_step), q, k, v, lens)
        kernel_us = time_call(
            lambda q, k, v, lens: decode_attention(q, k, v, lens,
                                                   block_k=BLOCK_K,
                                                   interpret=True),
            q, k, v, lens, iters=3)
        # parity guard: the numbers being compared must be the same numbers
        err = float(jnp.max(jnp.abs(
            _einsum_step(q, k, v, lens)
            - decode_attention(q, k, v, lens, block_k=BLOCK_K,
                               interpret=True))))
        assert err < 2e-5, (tag, err)

        m = _model(max_len, live)
        xla = _xla_cost(_einsum_step, q, k, v, lens)
        out[f"{tag}_einsum_us"] = einsum_us
        out[f"{tag}_kernel_interpret_us"] = kernel_us
        out[f"{tag}_einsum_xla_gflops"] = float(xla.get("flops", 0.0)) / 1e9
        out[f"{tag}_einsum_model_gflops"] = m["flops_einsum"] / 1e9
        out[f"{tag}_kernel_model_gflops"] = m["flops_kernel"] / 1e9
        out[f"{tag}_einsum_hbm_mib"] = m["hbm_mib_einsum"]
        out[f"{tag}_kernel_hbm_mib"] = m["hbm_mib_kernel"]
        out[f"{tag}_speedup_flops_x"] = m["speedup_flops_x"]
        out[f"{tag}_speedup_bytes_x"] = m["speedup_bytes_x"]

    a_tag = f"L{ACCEPT_CELL[0]}_live{ACCEPT_CELL[1]}"
    accept = min(out[f"{a_tag}_speedup_flops_x"],
                 out[f"{a_tag}_speedup_bytes_x"])
    out["accept_cell"] = a_tag
    out["accept_speedup_x"] = accept
    out["accept_pass"] = bool(accept >= ACCEPT_X)
    append_run(_BENCH_JSON, out)
    return out


if __name__ == "__main__":
    for key, val in run().items():
        print(f"{key}: {val}")
