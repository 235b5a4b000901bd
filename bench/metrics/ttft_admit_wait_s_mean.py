"""Mean seconds from arrival to admission (Alg 1's batch cap and the KV
watermark) over the judged requests with a first token, on the engine's
clock."""
from bench.metrics._lifecycle import mean_part


def read(rec):
    return mean_part(rec, 0)
