"""Mean seconds from the first prefill chunk to the first token (the
prompt's chunks and the interval that retires the last) over the judged
requests with a first token, on the engine's clock."""
from bench.metrics._lifecycle import mean_part


def read(rec):
    return mean_part(rec, 2)
