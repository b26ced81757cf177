"""Share of the rows the held experts' grouped matmul computed that were
tile padding.

Read from the program's ``engine.drain.experts`` spans in the traced
window: each carries the rows routed to the held experts
(``routed_rows``) and the rows the matmul computed beyond them
(``expert_pad_rows``), summed over layers and over the launches the
drain fetched. Percent of their sum.
"""

from bench import mla_moe_flops as mf
from bench import spans


def read(r):
    sp = spans.of(r)
    got = None if sp is None else mf.experts_drained(sp)
    if not got:
        return None
    rows = got["routed_rows"] + got["expert_pad_rows"]
    if rows <= 0:
        return None
    return 100.0 * got["expert_pad_rows"] / rows
