"""Max over the held experts of the selections each took, over their
mean, for the requests due in the window (1 = even load)."""
from bench.metrics._held import per_expert


def read(rec):
    tot = per_expert(rec.judged())
    if not tot or not sum(tot):
        return None
    return max(tot) / (sum(tot) / len(tot))
