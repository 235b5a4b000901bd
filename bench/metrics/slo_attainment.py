"""Share of the requests due in the window that met the chat SLO: first
token within `slo.ttft_s` of being due and a mean gap between tokens of
at most `slo.tbt_ms`. A refused request misses, and so does one still
open when the drain ended."""
from bench.metrics._common import ttft


def read(rec):
    slo = rec.mix.get("slo")
    judged = rec.judged()
    if not slo or not judged:
        return None
    met = 0
    for c in judged:
        if not c.complete:
            continue
        n = len(c.times)
        mean_gap = (c.times[-1] - c.times[0]) / (n - 1) * 1e3 if n > 1 else 0
        if ttft(c, rec.stop) <= slo["ttft_s"] and mean_gap <= slo["tbt_ms"]:
            met += 1
    return met / len(judged)
