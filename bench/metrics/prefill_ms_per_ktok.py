"""Device milliseconds of the paged prefill programs per 1000 prompt
tokens they prefilled, over the traced part of the window."""


def read(rec):
    t = rec.trace
    if t is None:
        return None
    toks = sum(s.prefill_tokens for s in rec.trace_steps)
    sec = t.program_seconds("prefill")
    return sec * 1e3 / (toks / 1e3) if toks and sec else None
