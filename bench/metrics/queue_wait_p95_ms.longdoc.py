"""95th percentile of the front end's queue wait (``RequestRecord.
queue_wait_s``, stamped at admission) of the requests submitted while
tracing, nearest rank."""


def read(r):
    waits = sorted(rec.ticket.record.queue_wait_s for rec in r.recs
                   if rec.ticket.record.queue_wait_s is not None)
    if not waits:
        return None
    rank = max(0, min(len(waits) - 1, int(round(0.95 * (len(waits) - 1)))))
    return 1e3 * waits[rank]
