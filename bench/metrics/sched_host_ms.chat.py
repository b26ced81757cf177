"""Host time before the device can run an iteration, per scheduler step.

Read from the program's spans in the traced window (``bench/spans.py``):
the time of each working ``engine.step`` spent in its ``engine.fill``,
``engine.stage`` and ``engine.launch.*`` spans, averaged over the steps.
"""

from bench import spans


def read(r):
    s = spans.of(r)
    v = None if s is None else s.sched_host_s()
    return None if v is None else 1e3 * v
