"""Share of its roofline that the held experts' grouped matmul reaches.

Kernel time: the device operations of ``gmm`` (megablox, three a layer:
gate, up, down) in the traced window, with the operations that stage
their weights (each layer's held experts sliced out of the stacked
weights into a buffer of their own: ``gmm`` then reads VMEM, and the HBM
read is theirs; ``bench/mla_moe_flops.expert_time_s``). Least time: the
rows routed to the held experts, from the program's
``engine.drain.experts`` spans in the window, through the three SwiGLU
matrices at the bf16 peak, or the weights of each (layer, expert) pair
that had rows read once, with each row's input and output, at the HBM
peak (``bench/mla_moe_flops.py``).
"""

from bench import mla_moe_flops as mf
from bench import spans

OP = r"^gmm$"


def read(r):
    if r.summary.op_time_s(OP) <= 0:
        return None
    sp = spans.of(r)
    got = None if sp is None else mf.experts_drained(sp)
    if not got:
        return None
    z = mf.sizes(r.config)
    cost = mf.expert_call(got["routed_rows"], got["expert_groups"], z)
    return 100.0 * cost.least_s(r.peaks) / mf.expert_time_s(r.summary, z)
