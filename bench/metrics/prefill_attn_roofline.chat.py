"""Share of its roofline that the GQA flash prefill kernel reaches in a
mix of prefill and decode.

Kernel time: the device operations of ``flash_gqa_attention`` in the
traced window. Least time: one call a layer per prefill chunk, over the
causal key pairs of the chunk's valid queries at its offset (bf16), or
the keys and values up to its end read once (HBM); the chunk's padding
rows are not work the prompt needs.
"""

KERNEL = r"^flash_gqa_attention$"


def read(r):
    t = r.summary.op_time_s(KERNEL)
    if t <= 0:
        return None
    f, d = r.flops, r.dims
    least = 0.0
    for tk in r.ticks:
        for start, valid, _final in tk.chunks:
            least += d["n_layers"] * f.flash_prefill_call(
                valid, start, d["n_heads"], d["n_kv_heads"],
                d["head_dim"]).least_s(r.peaks)
    return 100.0 * least / t
