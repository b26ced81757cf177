"""Operation and byte counts against hand-counted shapes, and the peaks."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops, peaks  # noqa: E402

QWEN2 = {"n_layers": 24, "d_model": 896, "n_heads": 14, "n_kv_heads": 2,
         "head_dim": 64, "d_ff": 4864, "vocab_size": 151936}
V5E = peaks.PEAKS["TPU v5 lite"]


def test_cim_call_counts():
    c = flops.cim_call(2, 3, 4)
    assert c.int8_ops == 2 * 2 * 3 * 4
    # f32 activation 2x3, int8 plane 3x4, f32 output 2x4
    assert c.bytes == 2 * 3 * 4 + 3 * 4 + 2 * 4 * 4
    assert c.bf16_flops == 0


def test_decode_attention_counts_live_keys_only():
    c = flops.decode_attn_call([1, 3], heads=4, kv_heads=2, head_dim=8)
    # 4 live keys: scores and weighted sum over 4 query heads of width 8
    assert c.bf16_flops == 4 * 4 * 8 * 4
    # keys and values (2 KV heads, bf16) + queries and outputs of 2 rows
    assert c.bytes == 2 * 4 * 2 * 8 * 2 + 2 * 2 * 4 * 8 * 2


def test_flash_prefill_counts_causal_pairs():
    c = flops.flash_prefill_call(2, 3, heads=2, kv_heads=1, head_dim=4)
    # queries at positions 3 and 4 see 4 and 5 keys: 9 pairs
    assert c.bf16_flops == 4 * 2 * 4 * 9
    assert c.bytes == 2 * 5 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2


def test_qwen2_plane_bytes_per_forward():
    per_layer = sum(k * n for k, n in flops.linear_shapes(QWEN2))
    assert per_layer == 896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864
    # one decode row reads every plane once: about 358 MB of int8
    assert per_layer * 24 == 357_826_560


def test_roofline_bound_is_the_larger_side():
    c = flops.Cost(int8_ops=393e12, bytes=819e9 * 3)
    assert c.compute_s(V5E) == pytest.approx(1.0)
    assert c.least_s(V5E) == pytest.approx(3.0)
    c = flops.Cost(int8_ops=393e12, bf16_flops=197e12 * 2, bytes=1.0)
    assert c.least_s(V5E) == pytest.approx(3.0)


def test_forward_least_time_sums_per_call_bounds():
    m = 128
    want = 24 * sum(flops.cim_call(m, k, n).least_s(V5E)
                    for k, n in flops.linear_shapes(QWEN2))
    assert flops.cim_forward_least_s(QWEN2, m, V5E) == pytest.approx(want)


def test_token_and_chunk_costs_agree():
    one = flops.chunk_cost(QWEN2, start=0, valid=1, final=True)
    tok = flops.token_cost(QWEN2, context=1, logits=True)
    assert one == tok
    many = flops.chunk_cost(QWEN2, start=10, valid=3, final=False)
    parts = [flops.token_cost(QWEN2, 11 + i, False) for i in range(3)]
    assert many.int8_ops == sum(p.int8_ops for p in parts)
    assert many.bf16_flops == pytest.approx(sum(p.bf16_flops for p in parts))


def test_unknown_device_raises():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops_s"] == 393e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
