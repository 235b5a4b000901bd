"""Held-expert selections the program counts per request (`Request.
held_selections`, DESIGN §17), read as `_lifecycle` reads the stamps (not
a metric itself)."""
from __future__ import annotations

from typing import List, Optional


def per_expert(clients) -> Optional[List[int]]:
    """Selections each held expert took over `clients`' requests; None
    where the program counts none."""
    tot: List[int] = []
    for c in clients:
        counts = getattr(c.r, "held_selections", None) if c.r else None
        if not counts:
            continue
        tot = [a + b for a, b in zip(tot, counts)] if tot else list(counts)
    return tot or None


def per_token(rec) -> Optional[float]:
    """Mean held-expert selections per token run through the model, over
    the finished requests: a finished request ran its prompt and every
    served token but the last."""
    sel = toks = 0
    for c in rec.clients:
        counts = getattr(c.r, "held_selections", None) if c.r else None
        if not (c.complete and counts):
            continue
        sel += sum(counts)
        toks += c.r.prompt_len + len(c.r.output_tokens) - 1
    return sel / toks if toks else None
