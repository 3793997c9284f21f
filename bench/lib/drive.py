"""The measured window: traffic offered to a serving scheduler, tick by tick.

The scheduler is driven only through ``submit`` / ``step_tick`` /
``pending``; ``probe`` and ``retired`` (given by the caller) read which
requests are in flight, how far each has come, and which have retired.
Every time here is the host's ``perf_counter``, taken by the benchmark.

The traffic starts ``prime_ticks`` ticks before the window opens, so that
the window finds the scheduler in its steady state (slots busy at every
stage of their requests, a queue where the pool is short) and not empty.
Those ticks are set-up.  The window opens at the end of the last of them
and is made of whole ticks: it closes at the end of the last tick that
ends within ``seconds``.

* Open loop: request ``i`` falls due ``traffic.due(i)`` seconds after the
  traffic starts, is submitted at the first tick boundary after that, and
  is timed from when it fell due.  The window's requests are those due
  inside it; each is followed until it retires, and one still unfinished
  ``drain_s`` after the window counts as failed.
* Closed loop: each client sends its next request as soon as its previous
  one retires.  The window's requests are those sent inside it; after the
  window no request is sent, and ticks go on only until each of them has
  its first token (or ``drain_s`` passes).  Tokens count up to the window's
  end.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Tuple


@dataclasses.dataclass
class ReqRec:
    index: int
    rid: int
    prompt_len: int
    max_new: int
    due: float
    submit: float
    in_window: bool = False
    first: float = math.nan        # tick end that delivered token 1
    last: float = math.nan         # tick end that delivered the last token
    n_tok: int = 0
    n_win: int = 0                 # tokens delivered by the window's end
    last_win: float = math.nan
    retired: bool = False
    retired_at: float = math.nan
    rejected: bool = False


@dataclasses.dataclass
class TickRec:
    t0: float
    t1: float
    n_live: int                                   # slots busy this tick
    decode: List[Tuple[int, int, int]]       # (prompt, earlier tokens, kept)
    chunk: List[Tuple[int, int]]                  # (start, tokens)
    prefill: List[int]                            # bucketed prompt lengths
    traced: bool = False

    @property
    def kind(self) -> str:
        if self.chunk and self.decode:
            return "mixed"
        return "chunk" if self.chunk else "decode"

    @property
    def tokens(self) -> int:
        return sum(k for _, _, k in self.decode)


@dataclasses.dataclass
class Window:
    start: float
    end: float                    # end of the window's last tick
    stop: float                   # when the last tick after the window ended
    reqs: List[ReqRec]            # the window's requests
    ticks: List[TickRec]          # the window's ticks
    lateness: List[float]         # open loop: submit - due, per request
    every_req: List[ReqRec]       # with those sent before the window
    every_tick: List[TickRec]     # with the priming and draining ticks

    @property
    def seconds(self) -> float:
        return self.end - self.start


def drive(sched, traffic, *, seconds: float, drain_s: float,
          probe: Callable[[], Dict[int, Tuple[int, int, int]]],
          retired: Callable[[], Dict[int, Tuple[int, bool]]],
          chunk_above: int, prime_ticks: int = 0,
          on_open: Callable[[], None] = lambda: None,
          trace_at: Optional[Tuple[float, float]] = None,
          start_trace: Callable[[], None] = lambda: None,
          stop_trace: Callable[[], None] = lambda: None,
          span: Callable[[str], contextlib.AbstractContextManager]
          = lambda name: contextlib.nullcontext()) -> Window:
    """Run ``prime_ticks`` ticks of traffic, then one window.  ``probe()``
    maps each in-flight rid to (prompt length, prompt tokens ingested,
    tokens generated); ``retired()`` maps each retired rid to (tokens
    generated, rejected).  ``on_open()`` is called as the window opens.
    ``trace_at`` is (offset, length) of the traced part of the window, in
    seconds."""
    clock = time.perf_counter
    mix = traffic.mix
    closed = traffic.loop == "closed"
    clients: List[Optional[int]] = ([None] * int(mix["clients"])
                                    if closed else [])
    reqs: Dict[int, ReqRec] = {}
    ticks: List[TickRec] = []
    lateness: List[float] = []
    prev: Dict[int, Tuple[int, int]] = {}
    seen_retired = set()
    nxt = 0
    tracing = traced_done = False
    if prime_ticks <= 0:
        on_open()
    origin = clock()
    free_at = [origin] * len(clients)
    start = end = math.inf          # until the window opens
    if prime_ticks <= 0:
        start, end = origin, origin + seconds

    def send(due: float) -> int:
        nonlocal nxt
        r = traffic.request(nxt)
        now = clock()
        rid = sched.submit(r.prompt, r.max_new)
        reqs[rid] = ReqRec(index=nxt, rid=rid, prompt_len=len(r.prompt),
                           max_new=r.max_new, due=due, submit=now,
                           in_window=bool(due >= start))
        if due >= start:
            lateness.append(now - due)
        nxt += 1
        return rid

    while True:
        now = clock()
        if trace_at is not None and not traced_done:
            if not tracing and now >= start + trace_at[0]:
                start_trace()
                tracing = True
            elif tracing and now >= start + trace_at[0] + trace_at[1]:
                stop_trace()
                tracing, traced_done = False, True
        with span("bench.host"):
            if closed and now < end:
                for c, rid in enumerate(clients):
                    if rid is None:
                        clients[c] = send(free_at[c])
            elif not closed:
                while origin + traffic.due(nxt) <= min(now, end) \
                        and origin + traffic.due(nxt) < end:
                    send(origin + traffic.due(nxt))
        if not sched.pending:
            if now >= end:
                break
            if closed:               # every client's request was rejected
                continue
            with span("bench.wait"):
                time.sleep(max(0.0, min(origin + traffic.due(nxt), end)
                               - clock()))
            continue
        t0 = clock()
        with span("bench.tick"):
            sched.step_tick()
        t1 = clock()
        with span("bench.host"):
            rec = TickRec(t0=t0, t1=t1, n_live=0, decode=[], chunk=[],
                          prefill=[], traced=tracing)
            state = dict(probe())
            done = retired()
            for rid in [r for r in done if r not in seen_retired]:
                ntok, rej = done[rid]
                seen_retired.add(rid)
                rq = reqs.get(rid)
                if rq is None:
                    continue
                rq.retired, rq.retired_at, rq.rejected = True, t1, rej
                if not rej:
                    state[rid] = (rq.prompt_len, rq.prompt_len, ntok)
                if closed:
                    c = clients.index(rid)
                    clients[c], free_at[c] = None, t1
            for rid, (plen, pos, ntok) in state.items():
                rq = reqs.get(rid)
                if rq is None:
                    continue
                p_pos, p_tok = prev.get(rid, (0, 0))
                rec.n_live += 1
                if pos > p_pos:
                    if p_pos == 0 and plen <= chunk_above:
                        rec.prefill.append(plen)
                    else:
                        rec.chunk.append((p_pos, pos - p_pos))
                if ntok > p_tok:
                    rec.decode.append((plen, p_tok, ntok - p_tok))
                    if p_tok == 0:
                        rq.first = t1
                    rq.last, rq.n_tok = t1, ntok
                    if t1 <= end:
                        rq.n_win, rq.last_win = ntok, t1
                prev[rid] = (pos, ntok)
            for rid in seen_retired:
                prev.pop(rid, None)
            ticks.append(rec)
        if len(ticks) == prime_ticks:
            on_open()
            start, end = t1, t1 + seconds
        if t1 >= end:
            if closed:
                waiting = [r for r in reqs.values() if r.in_window
                           and not r.retired and math.isnan(r.first)]
            else:
                waiting = [r for r in reqs.values()
                           if r.in_window and not r.retired]
            if not waiting or t1 >= end + drain_s:
                break
    if tracing:
        stop_trace()
    inside = [t for t in ticks if t.t0 >= start and t.t1 <= end]
    every = sorted(reqs.values(), key=lambda r: r.index)
    return Window(start=start, end=inside[-1].t1 if inside else end,
                  stop=clock(), reqs=[r for r in every if r.in_window],
                  ticks=inside, lateness=lateness, every_req=every,
                  every_tick=ticks)
