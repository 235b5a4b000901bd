"""Output tokens delivered in the window over the window's seconds (host
clock)."""


def read(rec):
    n = sum(1 for c in rec.clients for t in c.times if rec.w0 < t <= rec.w1)
    return n / (rec.w1 - rec.w0)
