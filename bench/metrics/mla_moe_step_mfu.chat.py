"""The MLA + MoE model step's share of the chip's peak while the device is
busy.

Operations that the traced window's real tokens need (decoded tokens
with their attention over their context and the logits head; the valid
rows of each prefill chunk, with the head only for a prompt's last token;
padding rows and idle slots not counted; attention in the up-projected
form), plus the rows routed to the held experts in the window (the
program's ``engine.drain.experts`` spans), as least compute time at the
bf16 peak (``bench/mla_moe_flops.py``), over the device's busy time
(union of operation intervals) in the traced window.
"""

from bench import mla_moe_flops as mf
from bench import spans


def read(r):
    busy = r.summary.busy_s
    if busy <= 0:
        return None
    sp = spans.of(r)
    got = None if sp is None else mf.experts_drained(sp)
    if not got:
        return None
    z = mf.sizes(r.config)
    need = mf.expert_call(got["routed_rows"], 0, z).compute_s(r.peaks)
    for tk in r.ticks:
        for ctx in tk.decode_lens:
            need += mf.token_cost(z, ctx, True).compute_s(r.peaks)
        for start, valid, final in tk.chunks:
            need += mf.chunk_cost(z, start, valid, final).compute_s(r.peaks)
    return 100.0 * need / busy
