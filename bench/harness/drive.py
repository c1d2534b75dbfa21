"""The load generator: warm-up, lead-in, measured window, drain.

One main thread offers the load through ``AsyncFrontend.submit`` and
collects completions through its ``on_finish`` hook; the front end's
serve thread owns the engine.  Every request is timed from when it was
due (``t_submit`` is backdated to the due time), so a stalled generator or
engine shows in the latencies.  ``hooks`` are ``(seconds after the window
opens, order, fn)``: the generator calls each ``fn`` when it is due.
"""
from __future__ import annotations

import heapq
import itertools
import queue
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.harness import traffic as T

clock = time.perf_counter


@dataclass
class Rec:
    """One request offered by the load generator."""
    due: float
    prompt: np.ndarray
    max_new: int
    owner: int                       # session or group index
    turn: int = 0
    handle: Optional[int] = None
    submitted: float = 0.0
    req: object = None               # the finished Request
    error: Optional[str] = None


@dataclass
class Window:
    """What the measured window recorded."""
    t0: float
    t1: float
    recs: List[Rec]
    stats0: Dict[str, int]
    stats1: Dict[str, int]
    late_max_s: float                # how late the generator submitted
    marks: Dict[str, float] = field(default_factory=dict)
    # requests still decoding at the close, as served so far:
    # (prompt, tokens, their logprobs)
    partial: List[tuple] = field(default_factory=list)


class LoadGen:
    def __init__(self, fe, temperature: float):
        self.fe = fe
        self.temperature = temperature
        self.done: "queue.Queue[Rec]" = queue.Queue()
        self.live: Dict[int, Rec] = {}      # handle -> rec, unfinished
        self.recs: List[Rec] = []
        self.late_max = 0.0

    def submit(self, rec: Rec) -> Rec:
        rec.submitted = clock()
        self.late_max = max(self.late_max, rec.submitted - rec.due)
        rec.handle = self.fe.submit(
            rec.prompt, max_new=rec.max_new,
            temperature=self.temperature,
            t_submit=rec.due, on_finish=lambda req, r=rec: self._fin(r, req))
        self.live[rec.handle] = rec
        self.recs.append(rec)
        return rec

    def _fin(self, rec: Rec, req) -> None:      # serve thread
        rec.req = req
        self.done.put(rec)

    def next_done(self, timeout: float) -> Optional[Rec]:
        """The next finished request, or a failed one; None on timeout."""
        try:
            rec = self.done.get(timeout=max(timeout, 0.0))
        except queue.Empty:
            return self._failed()
        self.live.pop(rec.handle, None)
        return rec

    def _failed(self) -> Optional[Rec]:
        for h, rec in list(self.live.items()):
            p = self.fe.poll(h)
            if p.done and p.error is not None:
                rec.error = repr(p.error)
                self.live.pop(h)
                return rec
        return None

    def cancel_all(self) -> None:
        for h in list(self.live):
            self.fe.cancel(h)
        self.live.clear()


def _run_until(drv: LoadGen, events, until: float, on_done: Callable,
               on_event: Callable, hooks) -> None:
    """Pop due events and completions until ``until`` (host clock)."""
    while True:
        now = clock()
        while hooks and hooks[0][0] <= now:
            heapq.heappop(hooks)[2]()
        while events and events[0][0] <= now and events[0][0] < until:
            on_event(heapq.heappop(events))
        if now >= until:
            return
        nxt = min([until] + [e[0] for e in events[:1]]
                  + [h[0] for h in hooks[:1]])
        rec = drv.next_done(min(nxt - now, 0.25))
        if rec is not None:
            on_done(rec)


def drive_sessions(fe, mix: dict, *, seed: int, vocab: int, rate: float,
                   lead_in: float, seconds: float, drain_s: float,
                   hooks: Optional[list] = None, warmup: bool = False,
                   warm_system: int = 0) -> Window:
    """Agent sessions; with ``warmup`` every script once at max_new 2.
    Otherwise the shared system prompt is served first, alone, so that
    the radix cache holds it as a long-running server's would, and the
    traffic starts once it has finished: this is set-up, not load."""
    drv = LoadGen(fe, mix["temperature"])
    sess = T.sessions(mix, seed, vocab, rate, lead_in + seconds,
                      warmup=warmup, system_tokens=warm_system)
    if not warmup:
        # max_new 1: the cache then holds exactly the system prompt, so a
        # session's first turn matches it to the token and its span is
        # the task context, as in the warm-up
        drv.submit(Rec(due=clock(), prompt=sess[0].first[
            :mix["system_prompt_tokens"]], max_new=1, owner=-1))
        deadline = clock() + drain_s
        while drv.live and clock() < deadline:
            drv.next_done(0.25)
        if drv.live or drv.recs[0].req is None:
            raise RuntimeError("the system prompt was not served")
        drv.recs.clear()
    origin = clock()
    t0, t1 = origin + lead_in, origin + lead_in + seconds
    tie = itertools.count()
    events = [(origin + s.start, next(tie), s, 0, s.first) for s in sess]
    heapq.heapify(events)
    hooks = [(t0 + at, k, f) for at, k, f in hooks or []]
    heapq.heapify(hooks)
    marks: Dict[str, float] = {}
    stats: Dict[str, dict] = {}

    def snap(name):
        def f():
            marks[name] = clock()
            stats[name] = dict(fe.engine.stats)
        return f
    heapq.heappush(hooks, (t0, -1, snap("t0")))
    heapq.heappush(hooks, (t1, -2, snap("t1")))

    def on_event(ev):
        due, _, s, k, prompt = ev
        turn = s.script["turns"][k]
        drv.submit(Rec(due=due, prompt=prompt,
                       max_new=2 if warmup else turn["max_new"],
                       owner=s.idx, turn=k))

    by_owner = {s.idx: s for s in sess}

    def on_done(rec: Rec):
        s = by_owner[rec.owner]
        k = rec.turn + 1
        if rec.req is None or k >= len(s.script["turns"]):
            return
        wait = 0.0 if warmup else s.script["turns"][k]["tool_time_s"]
        due = rec.req.t_finish + wait
        if warmup or due < t1:
            prompt = np.concatenate([rec.prompt, rec.req.out, s.tools[k - 1]])
            heapq.heappush(events, (due, next(tie), s, k, prompt))

    if warmup:
        deadline = origin + drain_s
        while (events or drv.live) and clock() < deadline:
            _run_until(drv, events, min(clock() + 1.0, deadline), on_done,
                       on_event, hooks)
        bad = [r.error for r in drv.recs if r.error]
        if events or drv.live or bad:
            raise RuntimeError(f"warm-up did not finish within {drain_s} s "
                               f"or failed: {bad[:3]}")
        return Window(origin, clock(), drv.recs, {}, {}, drv.late_max)
    _run_until(drv, events, t1, on_done, on_event, hooks)
    while hooks:                       # window-close hooks that are due
        heapq.heappop(hooks)[2]()
    # drain: follow every turn due in the window to its end
    deadline = clock() + drain_s
    pending = lambda: [r for r in drv.live.values()       # noqa: E731
                       if t0 <= r.due < t1]
    while pending() and clock() < deadline:
        drv.next_done(min(deadline - clock(), 0.25))
    for r in pending():
        r.error = r.error or f"not finished {drain_s} s after the window"
    drv.cancel_all()
    return Window(t0, t1, drv.recs, stats.get("t0", {}), stats.get("t1", {}),
                  drv.late_max, marks)


def drive_groups(fe, mix: dict, *, seed: int, vocab: int, outstanding: int,
                 lead_in: float, seconds: float, drain_s: float,
                 hooks: Optional[list] = None, warmup: bool = False
                 ) -> Window:
    """GRPO rollout groups, closed loop at ``outstanding`` requests; with
    ``warmup`` every pool prompt once at max_new 2, then one sibling of
    each (a radix hit: a one-token span after a copy-on-write fork)."""
    drv = LoadGen(fe, mix["temperature"])
    origin = clock()
    if warmup:
        gs = list(T.groups(mix, seed, vocab, warmup=True))
        for _ in range(2):
            for g in gs:
                drv.submit(Rec(due=clock(), prompt=g.prompt, max_new=2,
                               owner=g.idx))
            deadline = clock() + drain_s
            while drv.live and clock() < deadline:
                rec = drv.next_done(0.25)
                if rec is not None and rec.error is not None:
                    raise RuntimeError(f"warm-up request failed: {rec.error}")
            if drv.live:
                raise RuntimeError(f"warm-up did not finish in {drain_s} s")
        return Window(origin, clock(), drv.recs, {}, {}, drv.late_max)
    t0, t1 = origin + lead_in, origin + lead_in + seconds
    gen = T.groups(mix, seed, vocab)
    hooks = [(t0 + at, k, f) for at, k, f in hooks or []]
    heapq.heapify(hooks)
    marks: Dict[str, float] = {}
    stats: Dict[str, dict] = {}

    def snap(name):
        def f():
            marks[name] = clock()
            stats[name] = dict(fe.engine.stats)
        return f
    heapq.heappush(hooks, (t0, -1, snap("t0")))
    heapq.heappush(hooks, (t1, -2, snap("t1")))
    while True:
        now = clock()
        while hooks and hooks[0][0] <= now:
            heapq.heappop(hooks)[2]()
        if now >= t1:
            break
        while len(drv.live) + mix["group_size"] <= outstanding:
            g = next(gen)
            due = clock()
            for k in range(mix["group_size"]):
                drv.submit(Rec(due=due, prompt=g.prompt,
                               max_new=g.max_new[k], owner=g.idx, turn=k))
        nxt = min([t1] + [h[0] for h in hooks[:1]])
        drv.next_done(min(nxt - clock(), 0.25))
    while hooks:
        heapq.heappop(hooks)[2]()
    # a response runs for minutes: the tokens that requests in flight at
    # the close have served so far are checked with the finished ones
    partial: List[tuple] = []
    fe.call(lambda: partial.extend(
        (np.asarray(s.req.prompt), np.asarray(s.out, np.int32),
         np.asarray(s.lps, np.float32))
        for s in fe.engine.slots if s is not None and s.out))
    drv.cancel_all()
    return Window(t0, t1, drv.recs, stats.get("t0", {}), stats.get("t1", {}),
                  drv.late_max, marks, partial)
