"""Share of its roofline that the latent-cache MLA prefill kernel reaches
in a mix of prefill and decode.

Kernel time: the device operations of ``mla_prefill`` in the traced
window. Least time: one call a layer per prefill chunk, over the causal
key pairs of the chunk's valid queries at its offset, with the fewer
operations of the absorbed and the up-projected forms at the bf16 peak,
or each latent row up to the chunk's end read once at the HBM peak
(``bench/mla_moe_flops.py``); the chunk's padding rows are not work the
prompt needs.
"""

from bench import mla_moe_flops as mf

OP = r"^mla_prefill$"


def read(r):
    t = r.summary.op_time_s(OP)
    if t <= 0:
        return None
    z = mf.sizes(r.config)
    least = sum(z["n_layers"] * mf.mla_prefill_call(valid, start, z)
                .least_s(r.peaks)
                for tk in r.ticks for start, valid, _ in tk.chunks)
    return 100.0 * least / t
