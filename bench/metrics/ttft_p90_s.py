"""90th percentile of time to first token over the requests due in the
window, from each one's due time (host clock)."""
from bench.metrics._common import percentile, ttft


def read(rec):
    judged = rec.judged()
    return percentile([ttft(c, rec.stop) for c in judged], 90) \
        if judged else None
