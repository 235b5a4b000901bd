"""Mean rows of a decode step in the window (`Engine.batch_trace`)."""


def read(rec):
    rows = [s.decode_rows for s in rec.window_steps() if s.decode_rows]
    return sum(rows) / len(rows) if rows else None
