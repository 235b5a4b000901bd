"""The engine-side parts of time to first token, from the lifecycle
stamps the engine puts on each request, on its own clock: arrival ->
admission (blocks allocated) -> first prefill chunk -> first token."""
from __future__ import annotations

from typing import List, Optional, Tuple


def waits(rec) -> Optional[List[Tuple[float, float, float]]]:
    """(admission wait, lane wait, prefill service) in seconds of each
    judged request with a first token. None where the program stamps no
    admission time."""
    out = []
    for c in rec.judged():
        r = c.r
        admit = getattr(r, "admit_time", None)
        if admit is None:
            return None
        if r.first_token_time < 0:
            continue
        out.append((admit - r.arrival_time,
                    r.prefill_start_time - admit,
                    r.first_token_time - r.prefill_start_time))
    return out


def mean_part(rec, k: int) -> Optional[float]:
    w = waits(rec)
    return sum(p[k] for p in w) / len(w) if w else None
