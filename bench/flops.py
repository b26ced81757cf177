"""Operations and bytes of one kernel call and of one model token.

Counted from shapes, with no tile padding: the least work the algorithm
needs, so that a share of a peak computed from these numbers cannot pass
100% unless the device time leaves out part of the work. Integer MACs of
the emulated macro count as int8 operations; attention, the logits head
and linears that bypass the macro as bf16 floating point operations. A multiply-add counts as two.

The arithmetic follows the kernels' own cost models (the CIM matmul's
``modeled_cost`` and the GQA flash prefill's ``flash_gqa_modeled_cost``)
with the padding to tile multiples taken out.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable


@dataclasses.dataclass(frozen=True)
class Cost:
    int8_ops: float = 0.0
    bf16_flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.int8_ops + other.int8_ops,
                    self.bf16_flops + other.bf16_flops,
                    self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Cost":
        return Cost(self.int8_ops * k, self.bf16_flops * k, self.bytes * k)

    def compute_s(self, peaks: dict) -> float:
        """Least compute time at the peaks: int8 and bf16 work in series."""
        return (self.int8_ops / peaks["int8_ops_s"]
                + self.bf16_flops / peaks["bf16_flops_s"])

    def least_s(self, peaks: dict) -> float:
        """Roofline bound: the larger of compute time and HBM time."""
        return max(self.compute_s(peaks), self.bytes / peaks["hbm_bytes_s"])


def cim_call(m: int, k: int, n: int, x_bytes: int = 4,
             w_bytes: int = 1, out_bytes: int = 4) -> Cost:
    """One fused CIM matmul: (m, k) float activation, (k, n) int8 plane,
    (m, n) float32 output. Each operand crosses HBM once."""
    return Cost(int8_ops=2.0 * m * k * n,
                bytes=float(m * k * x_bytes + k * n * w_bytes
                            + m * n * out_bytes))


def decode_attn_call(lens: Iterable[int], heads: int, kv_heads: int,
                     head_dim: int, kv_bytes: int = 2,
                     q_bytes: int = 2) -> Cost:
    """One length-aware decode attention call over a batch of rows whose
    caches hold ``lens`` keys: scores and the weighted sum of values for
    every query head; each live key and value is read once per KV head."""
    lens = list(lens)
    live = float(sum(lens))
    flops = 4.0 * heads * head_dim * live
    kv = 2.0 * live * kv_heads * head_dim * kv_bytes
    qo = 2.0 * len(lens) * heads * head_dim * q_bytes
    return Cost(bf16_flops=flops, bytes=kv + qo)


def flash_prefill_call(s: int, start: int, heads: int, kv_heads: int,
                       head_dim: int, kv_bytes: int = 2,
                       q_bytes: int = 2) -> Cost:
    """One causal GQA prefill call: ``s`` queries at positions
    ``start .. start+s-1`` against the keys before and among them."""
    pairs = s * start + s * (s + 1) / 2.0
    flops = 4.0 * heads * head_dim * pairs
    kv = 2.0 * (start + s) * kv_heads * head_dim * kv_bytes
    qo = 2.0 * s * heads * head_dim * q_bytes
    return Cost(bf16_flops=flops, bytes=kv + qo)


def linear_shapes(cfg: dict) -> list:
    """(K, N) of every CIM-routed linear in one dense GQA block, in the
    order the block calls them (q, k, v, o, gate, up, down)."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hd = cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def cim_forward_least_s(cfg: dict, m: int, peaks: dict) -> float:
    """Roofline time of every CIM call of one forward over ``m`` rows, all
    layers: each call bounded on its own, then summed."""
    one = sum(cim_call(m, k, n).least_s(peaks) for k, n in linear_shapes(cfg))
    return one * cfg["n_layers"]


def _linears(cfg: dict, tokens: float) -> Cost:
    """The block linears of ``tokens`` tokens, all layers: int8 MACs where
    they run through the macro (``cfg["linear"]`` is ``int8``, the
    default), bf16 flops where they run digital."""
    ops = 2.0 * sum(k * n for k, n in linear_shapes(cfg)) * tokens \
        * cfg["n_layers"]
    if cfg.get("linear", "int8") == "int8":
        return Cost(int8_ops=ops)
    return Cost(bf16_flops=ops)


def chunk_cost(cfg: dict, start: int, valid: int, final: bool) -> Cost:
    """Work the ``valid`` real tokens of one prefill chunk need, at
    positions ``start .. start+valid-1``; only the prompt's last token
    needs the logits head."""
    pairs = valid * start + valid * (valid + 1) / 2.0
    attn = 4.0 * cfg["n_heads"] * cfg["head_dim"] * pairs
    head = 2.0 * cfg["d_model"] * cfg["vocab_size"] if final else 0.0
    return _linears(cfg, valid) + Cost(
        bf16_flops=attn * cfg["n_layers"] + head)


def token_cost(cfg: dict, context: int, logits: bool) -> Cost:
    """Work one token needs: every block linear, attention over
    ``context`` keys (itself included), and the logits head when the
    token's logits are used. Bytes are not counted here: this is the
    compute side of a step's share of peak."""
    hd = cfg["head_dim"]
    attn = 4.0 * cfg["n_heads"] * hd * context
    head = 2.0 * cfg["d_model"] * cfg["vocab_size"] if logits else 0.0
    return _linears(cfg, 1) + Cost(bf16_flops=attn * cfg["n_layers"] + head)
