"""How late the load generator sent a request due in the window, at the
most: its own send time minus the due time (host clock)."""


def read(rec):
    judged = rec.judged()
    return max((c.submit - c.due) * 1e3 for c in judged) if judged else None
