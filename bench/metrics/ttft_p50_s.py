"""Median time to first token over the requests due in the window, from
each one's due time (host clock). A request with no first token when the
drain ends counts at its wait so far."""
from bench.metrics._common import percentile, ttft


def read(rec):
    judged = rec.judged()
    return percentile([ttft(c, rec.stop) for c in judged], 50) \
        if judged else None
