"""Share of its roofline that the decode attention kernel reaches.

Kernel time: the device operations of ``decode_attention`` in the traced
window. Least time: one call a layer per decode step, over the live keys
of each decoded row (bf16 scores and weighted sum at the bf16 peak, or
the keys and values read once at the HBM peak).
"""

KERNEL = r"^decode_attention$"


def read(r):
    t = r.summary.op_time_s(KERNEL)
    if t <= 0:
        return None
    f, d = r.flops, r.dims
    least = 0.0
    for tk in r.ticks:
        if tk.decode_lens:
            least += d["n_layers"] * f.decode_attn_call(
                tk.decode_lens, d["n_heads"], d["n_kv_heads"],
                d["head_dim"]).least_s(r.peaks)
    return 100.0 * least / t
