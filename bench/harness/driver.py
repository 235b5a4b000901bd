"""Drives one cell: builds the program's engine through
`repro.launch.serve.build_engine`, serves it the benchmark's weights,
warms up, sends the traffic and stamps every token on the client side.

Times are `time.perf_counter()` seconds. A token is stamped when the
harness first sees it after an `Engine.step()` returns; a request's waits
run from the time it was due, not the time it was sent."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from bench.harness import traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Client:
    """One request as its client sees it."""
    req: traffic.Req
    due: float                 # absolute perf_counter time it was due
    submit: float = -1.0
    expected: int = 0          # tokens the program should serve
    judged: bool = False       # counts toward the end-to-end metrics
    r: Any = None              # the engine's Request
    times: List[float] = field(default_factory=list)   # one per token
    done: bool = False

    @property
    def failed(self) -> bool:
        return bool(self.r is not None and self.r.rejected)

    @property
    def complete(self) -> bool:
        return self.done and not self.failed \
            and len(self.times) == self.expected


@dataclass
class Step:
    t0: float
    t1: float
    host_s: float
    decode_rows: int           # rows of the decode batch (0: none)
    prefill_tokens: int
    kv_used_tokens: int
    # with a trace: what the step computed, for operations and bytes
    decode_keys: Optional[List[int]] = None
    prefill_keys: Optional[List[int]] = None
    logit_rows: int = 0


@dataclass
class Record:
    """Everything a metric reader may read about one run."""
    cell: str
    config: dict
    dims: Dict[str, int]
    mix: dict
    t_start: float
    w0: float = 0.0            # window start: first due request's time
    w1: float = 0.0            # window end: the step that closed it
    stop: float = 0.0          # end of the drain
    clients: List[Client] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)
    compiles: List[tuple] = field(default_factory=list)  # (time, name)
    pool_tokens: int = 0
    peak: Dict[str, float] = field(default_factory=dict)
    trace: Any = None          # harness.trace.Reduced, with --trace 1
    trace_steps: List[Step] = field(default_factory=list)

    def judged(self) -> List[Client]:
        return [c for c in self.clients if c.judged]

    def failures(self) -> int:
        """Judged requests the engine refused. (One still open when the
        drain ends is slow, not failed: it misses the SLO and counts at
        its wait so far.)"""
        return sum(1 for c in self.judged() if c.failed)

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if s.t0 >= self.w0 and s.t1 <= self.w1]


class Driver:
    """One engine, one cell, one seed."""

    def __init__(self, eng, rec: Record, seed: int, vocab: int,
                 max_context: int, trace_cb=None):
        self.eng = eng
        self.rec = rec
        self.seed = seed
        self.vocab = vocab
        self.max_context = max_context
        self.open: List[Client] = []
        self.trace_cb = trace_cb      # called between steps with now
        self.count_work = False       # per-step keys, while tracing

    # -- sending and stamping ------------------------------------------------
    def send(self, c: Client, now: float) -> None:
        toks = c.req.tokens(self.seed, self.vocab)
        c.expected = max(0, min(c.req.output_len,
                                self.max_context - len(toks) - 1))
        c.submit = now
        c.r = self.eng.submit(toks, max_new_tokens=c.req.output_len,
                              arrival_time=c.due - self.eng.now0)
        self.rec.clients.append(c)
        self.open.append(c)

    def _stamp(self, now: float) -> None:
        still = []
        for c in self.open:
            out = c.r.output_tokens
            n = 0
            for v in out:
                if v is None:
                    break
                n += 1
            # a preempted request recomputes from scratch: tokens the
            # client already holds are not delivered twice
            while len(c.times) < n:
                c.times.append(now)
            if c.r.rejected or (c.r.state.value == "finished"
                                and n == len(out)):
                c.done = True
            else:
                still.append(c)
        self.open = still

    def _busy(self) -> bool:
        e = self.eng
        return bool(e.waiting or e.active or e.prefilling or e.swapped
                    or self.open)

    def _snapshot(self):
        e = self.eng
        return {id(r): (r, len(r.output_tokens), r.prefill_pos, r.state.value)
                for r in list(e.active) + list(e.prefilling)}

    def step(self) -> None:
        e = self.eng
        nb, npf = len(e.batch_trace), len(e.prefill_tokens_trace)
        before = self._snapshot() if self.count_work else None
        t0 = time.perf_counter()
        if self.count_work:
            import jax
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                e.step()
        else:
            e.step()
        t1 = time.perf_counter()
        self._stamp(t1)
        s = Step(t0, t1, e.step_host_trace[-1] if e.step_host_trace else 0.0,
                 e.batch_trace[-1] if len(e.batch_trace) > nb else 0,
                 e.prefill_tokens_trace[-1]
                 if len(e.prefill_tokens_trace) > npf else 0,
                 e.blocks.physical_used_tokens)
        if before is not None:
            self._count(s, before)
            self.rec.trace_steps.append(s)
        self.rec.steps.append(s)

    def _count(self, s: Step, before) -> None:
        """Keys each token of this step attended over, from the requests'
        state before and after it."""
        after = self._snapshot()
        s.decode_keys, s.prefill_keys = [], []
        for k in set(before) | set(after):
            r = (after.get(k) or before.get(k))[0]
            _, n0, p0, st0 = before.get(k, (r, 0, 0, "waiting"))
            n1 = len(r.output_tokens)
            p1 = r.prefill_pos
            if p1 > p0:
                s.prefill_keys.extend(range(p0 + 1, p1 + 1))
            if n1 <= n0:
                continue
            if st0 != "running":
                # promoted this step: the prompt's last position gives the
                # first token, then one decode row
                s.logit_rows += 1
                n0 += 1
            for j in range(n0, n1):
                s.decode_keys.append(r.prompt_len + j)
        s.logit_rows += len(s.decode_keys)

    def _wait(self, seconds: float) -> None:
        """Nothing to serve until the next arrival."""
        if seconds <= 0:
            return
        if self.count_work:
            import jax
            with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
                time.sleep(seconds)
        else:
            time.sleep(seconds)

    # -- open loop -------------------------------------------------------------
    def open_loop(self, ramp: List[traffic.Req], window: List[traffic.Req],
                  drain: List[traffic.Req], start: float, ramp_s: float,
                  seconds: float, drain_s: float) -> None:
        """Requests due at `start + due`: the ramp's in [0, ramp_s), the
        window's from `start + ramp_s` on, judged. The window closes
        `seconds` after its first due request. The drain's arrivals go on
        at the same rate until each judged request has finished, or
        `drain_s` has passed."""
        rec = self.rec
        rec.w0 = start + ramp_s
        queue = [Client(q, start + q.due) for q in ramp]
        for q in window:
            c = Client(q, rec.w0 + q.due)
            c.judged = True
            queue.append(c)
        queue += [Client(q, rec.w0 + seconds + q.due) for q in drain]
        nxt = 0
        closed = False
        while True:
            now = time.perf_counter()
            while nxt < len(queue) and queue[nxt].due <= now:
                self.send(queue[nxt], now)
                nxt += 1
            if not closed and now >= rec.w0 + seconds:
                closed = True
                rec.w1 = now
            if closed:
                left = any(c.judged and not c.done for c in rec.clients) \
                    or any(c.judged for c in queue[nxt:])
                if not left or now >= rec.w1 + drain_s:
                    rec.stop = now
                    return
            if self.trace_cb:
                self.trace_cb(self, now)
            if self._busy():
                self.step()
            elif nxt < len(queue):
                self._wait(min(queue[nxt].due - now, 0.005))
            else:
                raise RuntimeError("open loop ran out of requests")

    # -- backlog ---------------------------------------------------------------
    def backlog(self, reqs: List[traffic.Req], min_waiting: int,
                ramp: dict, seconds: float) -> None:
        """Keep at least `min_waiting` requests queued. The window opens
        once the first request has finished, or after the ramp's
        `max_seconds`, and closes after `seconds`."""
        rec = self.rec
        it = iter(reqs)
        t_ramp = time.perf_counter()
        rec.w0 = 0.0
        while True:
            now = time.perf_counter()
            while len(self.eng.waiting) < min_waiting:
                q = next(it)
                c = Client(q, now)
                c.judged = rec.w0 > 0
                self.send(c, now)
            if rec.w0 == 0.0:
                if any(c.done for c in rec.clients) \
                        or now - t_ramp >= ramp["max_seconds"]:
                    rec.w0 = now
                    for c in self.open:
                        c.judged = True
            elif now >= rec.w0 + seconds:
                rec.w1 = rec.stop = now
                return
            if self.trace_cb:
                self.trace_cb(self, now)
            self.step()
