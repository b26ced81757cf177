"""Device programs launched per scheduler tick that had work.

Programs: the ``XLA Modules`` events of the traced window (first chip).
Ticks: the harness's ``tick`` spans, one per ``Frontend.tick`` call made
while requests were pending.
"""


def read(r):
    ticks = r.summary.span_count("tick")
    if ticks == 0 or not r.summary.modules:
        return None
    first = sorted(r.summary.modules)[0]
    return r.summary.modules[first] / ticks
