"""Mean host time of one engine interval in the window:
`Engine.step_host_trace` (the interval minus its device fence wait)."""


def read(rec):
    s = rec.window_steps()
    return sum(x.host_s for x in s) / len(s) * 1e3 if s else None
