"""Device idle time inside the scheduler ticks that had work, per tick.

The time of each ``tick`` span in which no operation ran on the device
(first chip): host scheduling, input staging and the token drain that the
device waits on.
"""


def read(r):
    idle, n = r.summary.idle_in("tick")
    if n == 0 or not r.summary.busy:
        return None
    return 1e3 * idle / n
