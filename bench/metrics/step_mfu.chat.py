"""The model step's share of the chip's peak while the device is busy.

Operations that the traced window's real tokens need (decoded tokens with
their attention and logits head; the valid rows of each prefill chunk,
with the head only for a prompt's last token; padding rows and idle slots
not counted), as least compute time at the int8 and bf16 peaks, over the
device's busy time (union of operation intervals) in the traced window.
"""


def read(r):
    busy = r.summary.busy_s
    if busy <= 0:
        return None
    f, d = r.flops, r.dims
    need = 0.0
    for tk in r.ticks:
        for ctx in tk.decode_lens:
            need += f.token_cost(d, ctx, True).compute_s(r.peaks)
        for start, valid, final in tk.chunks:
            need += f.chunk_cost(d, start, valid, final).compute_s(r.peaks)
    if need <= 0:
        return None
    return 100.0 * need / busy
