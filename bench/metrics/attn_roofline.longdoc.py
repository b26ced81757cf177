"""Share of its roofline that the GQA flash prefill kernel reaches.

Kernel time: the device operations of ``flash_gqa_attention`` in the
traced window. Least time: one call a layer per prefill chunk of
``chunk_size`` queries at the chunk's offset, over its causal key pairs
(bf16) or the keys and values up to its end read once (HBM).
"""

KERNEL = r"^flash_gqa_attention$"


def read(r):
    t = r.summary.op_time_s(KERNEL)
    if t <= 0:
        return None
    f, d, s = r.flops, r.dims, r.shape
    least = 0.0
    for tk in r.ticks:
        for start, _valid, _final in tk.chunks:
            least += d["n_layers"] * f.flash_prefill_call(
                s["chunk_size"], start, d["n_heads"], d["n_kv_heads"],
                d["head_dim"]).least_s(r.peaks)
    return 100.0 * least / t
