"""Mean seconds from admission to the first prefill chunk (waiting for a
prefill lane) over the judged requests with a first token, on the
engine's clock."""
from bench.metrics._lifecycle import mean_part


def read(rec):
    return mean_part(rec, 1)
