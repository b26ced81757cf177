"""Device idle time that the program's host phases hold, per step.

The first chip's idle time inside the union of the program's spans
(``engine.*``, ``frontend.*``) in the traced window, on the trace's one
clock, over the working ``engine.step`` spans (``bench/spans.py``).
"""

from bench import spans


def read(r):
    s = spans.of(r)
    v = None if s is None else s.idle_host_s(r.summary)
    return None if v is None else 1e3 * v
