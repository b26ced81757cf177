"""Share of its roofline that the latent-cache MLA decode kernel reaches.

Kernel time: the device operations of ``mla_decode`` in the traced
window. Least time: one call a layer per decode step, over the live keys
of each decoded row: absorbed scores and weighted sum of every head at
the bf16 peak, or each live latent row read once at the HBM peak
(``bench/mla_moe_flops.py``).
"""

from bench import mla_moe_flops as mf

OP = r"^mla_decode$"


def read(r):
    t = r.summary.op_time_s(OP)
    if t <= 0:
        return None
    z = mf.sizes(r.config)
    least = sum(z["n_layers"] * mf.mla_decode_call(tk.decode_lens, z)
                .least_s(r.peaks) for tk in r.ticks if tk.decode_lens)
    return 100.0 * least / t
