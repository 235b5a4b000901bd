"""95th percentile over every gap between two output tokens a client of
a request due in the window received (host clock)."""
from bench.metrics._common import gaps_ms, percentile


def read(rec):
    g = [x for c in rec.judged() for x in gaps_ms(c)]
    return percentile(g, 95) if g else None
