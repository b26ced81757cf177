"""Share of its roofline that the fused CIM matmul kernel reaches.

Kernel time: the device operations of ``cim_matmul_fused_pallas`` in the
traced window. Least time: every call the traced ticks made (one decode
forward at M = max_slots when any row decoded, one forward at
M = chunk_size per prefill chunk; 7 linears a layer), each bounded by
int8 MACs at the int8 peak or its bytes at the HBM peak.
"""

KERNEL = r"^cim_matmul_fused_pallas$"


def read(r):
    t = r.summary.op_time_s(KERNEL)
    if t <= 0:
        return None
    f, d, s = r.flops, r.dims, r.shape
    least = 0.0
    for tk in r.ticks:
        if tk.decode_lens:
            least += f.cim_forward_least_s(d, s["max_slots"], r.peaks)
        least += len(tk.chunks) * f.cim_forward_least_s(
            d, s["chunk_size"], r.peaks)
    return 100.0 * least / t
