"""The window's driver against a scheduler stand-in on a clock of its own:
priming ticks before the window, a window of whole ticks, and the closed
loop's requests followed to their first token after it."""

import collections
import math

import pytest

from bench.lib import drive as D
from bench.lib.loadgen import Traffic

MIX = {"loop": "closed", "clients": 4, "block": 4,
       "prompt": {"median": 10, "sigma": 0.3, "min": 4, "max": 20},
       "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}


class Clock:
    t = 100.0

    def __call__(self):
        return self.t


class Sched:
    """Two slots, FIFO admission, two tokens a tick, one second a tick."""

    def __init__(self, clock, slots=2, steps=2):
        self.clock, self.slots, self.steps = clock, slots, steps
        self.queue = collections.deque()
        self.live, self.done, self.n = {}, {}, 0

    def submit(self, prompt, max_new):
        self.n += 1
        self.queue.append((self.n, len(prompt), max_new))
        return self.n

    @property
    def pending(self):
        return len(self.queue) + len(self.live)

    def step_tick(self):
        while self.queue and len(self.live) < self.slots:
            rid, p, m = self.queue.popleft()
            self.live[rid] = [p, m, 0]
        self.clock.t += 1.0
        for rid, s in list(self.live.items()):
            s[2] = min(s[1], s[2] + self.steps)
            if s[2] >= s[1]:
                self.done[rid] = s[2]
                del self.live[rid]

    def probe(self):
        return {rid: (p, p, n) for rid, (p, _, n) in self.live.items()}

    def retired(self):
        return {rid: (n, False) for rid, n in self.done.items()}


def run(monkeypatch, prime, seconds, drain_s=100.0):
    clock = Clock()
    monkeypatch.setattr(D.time, "perf_counter", clock)
    sched = Sched(clock)
    opened = []
    win = D.drive(sched, Traffic(MIX, 2 ** 31 + 5, 50), seconds=seconds,
                  drain_s=drain_s, probe=sched.probe, retired=sched.retired,
                  chunk_above=1 << 30, prime_ticks=prime,
                  on_open=lambda: opened.append(clock.t))
    return win, opened


def test_window_opens_after_the_priming_ticks(monkeypatch):
    win, opened = run(monkeypatch, prime=3, seconds=5.5)
    assert opened == [103.0] and win.start == 103.0
    # whole ticks: the last that ends within 5.5 s ends at 108
    assert win.end == 108.0 and win.seconds == 5.0
    assert [t.t1 for t in win.ticks] == [104.0, 105.0, 106.0, 107.0, 108.0]
    assert len(win.every_tick) > len(win.ticks) + 3 - 1
    # the first clients' requests were sent during priming: not the window's
    assert all(r.due >= win.start and r.in_window for r in win.reqs)
    early = [r for r in win.every_req if not r.in_window]
    assert len(early) >= 4 and all(r.due < win.start for r in early)
    # nothing is sent after the window
    assert all(r.due <= win.end for r in win.every_req)


def test_closed_loop_follows_window_requests_to_first_token(monkeypatch):
    win, _ = run(monkeypatch, prime=2, seconds=6.0)
    assert win.reqs
    assert all(not math.isnan(r.first) for r in win.reqs)
    assert win.stop > win.end
    # tokens after the window's end do not count towards it
    for r in win.reqs:
        assert r.n_win <= r.n_tok
        assert math.isnan(r.last_win) or r.last_win <= win.end


def test_no_priming_opens_at_once(monkeypatch):
    win, opened = run(monkeypatch, prime=0, seconds=4.0)
    assert opened == [100.0] and win.start == 100.0
    assert win.seconds == pytest.approx(4.0)
    assert len(win.reqs) == len(win.every_req)
    assert {r.index for r in win.reqs} >= {0, 1, 2, 3}


def test_drain_is_bounded(monkeypatch):
    win, _ = run(monkeypatch, prime=2, seconds=2.0, drain_s=0.0)
    assert win.stop == win.end == 104.0
    # two slots, four clients: a request sent inside the window is still
    # queued when the drain's bound ends the run
    assert [r for r in win.reqs if math.isnan(r.first)]
