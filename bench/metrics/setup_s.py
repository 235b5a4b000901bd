"""Process start to the window's first due request: start-up, weights,
compiles or cache loads, warm-up and the ramp (host clock)."""


def read(rec):
    return rec.w0 - rec.t_start
