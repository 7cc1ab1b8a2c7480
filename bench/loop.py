"""The load loop: offers a run's requests to the system and watches them.

One host thread, as a client library would: submit what is due, step the
engine, note the host time at which each output token came back.  Spans
around the three things the thread does (``bench.submit``,
``bench.engine_step``, ``bench.gen_sleep``) go into the profiler's trace
when one is running.

After each tick it copies every live request's ``queued_s``, the time
the program's scheduler kept it waiting for a slot.

The loop also keeps, per engine tick, the work the scheduler planned:
the context of every decoding row and the prompt range of every prefill
chunk.  It reads them from the tick plan the scheduler hands the engine,
so the operations counted are those the tick was asked to do.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax

from . import system


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    decode: list            # context (keys attended) of each decoding row
    prefill: list           # (start, stop, last) prompt range per chunk row
    admitted: int           # prompt tokens of the requests admitted
    active: int             # slots holding a request after the plan
    waiting: int            # requests still waiting for a slot


class Loop:
    def __init__(self, engine, clock=time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.live: dict[int, tuple] = {}
        self.ticks: list[Tick] = []
        self._plan = None
        sched = engine.scheduler
        plan_tick = sched.plan_tick

        def spy():
            plan = plan_tick()
            # keys each decoding row attends to: its prompt and every token
            # emitted so far, the newest of which this tick writes
            decode = [len(sched.active[s].req.prompt)
                      + len(sched.active[s].req.generated)
                      for s in plan.decode_slots]
            prefill = [(a.start, a.start + a.n_new,
                        a.start + a.n_new >= a.sreq.prompt_len)
                       for a in plan.prefill]
            self._plan = (decode, prefill,
                          sum(s.prompt_len for s in plan.admissions),
                          sum(s is not None for s in sched.active),
                          len(sched.waiting))
            return plan

        sched.plan_tick = spy

    # -- one request ----------------------------------------------------------
    def submit(self, req, now: float) -> None:
        with jax.profiler.TraceAnnotation("bench.submit"):
            preq = system.program_request(req)
            req.submitted = now
            try:
                self.engine.submit(preq)
            except ValueError:
                req.failed = True
                return
        self.live[req.rid] = (req, preq)

    # -- one engine tick ------------------------------------------------------
    def busy(self) -> bool:
        return self.engine.scheduler.pending()

    def step(self) -> list:
        """One engine tick; returns the requests that finished in it."""
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            self.engine.step()
        t1 = self.clock()
        plan, self._plan = self._plan, None
        if plan is not None:
            self.ticks.append(Tick(t0, t1, *plan))
        return self.observe(t1)

    def observe(self, now: float) -> list:
        finished = []
        for rid, (req, preq) in list(self.live.items()):
            n = len(preq.generated)
            while len(req.token_times) < n:
                req.token_times.append(now)
            req.queued_s = preq.queued_s
            if preq.done:
                req.done = True
                req.tokens = list(preq.generated)
                del self.live[rid]
                finished.append(req)
        return finished

    def sleep_until(self, t: float) -> None:
        dt = t - self.clock()
        if dt > 0:
            with jax.profiler.TraceAnnotation("bench.gen_sleep"):
                time.sleep(dt)


def run_open(loop: Loop, requests: list, t0: float, seconds: float,
             drain_cap: float, on_open=None, on_close=None) -> None:
    """Offer ``requests`` (``due`` relative to ``t0``) until the window
    ``[t0, t0 + seconds)`` has closed and every request due in it has its
    first token, or the drain cap is reached.  ``on_open``/``on_close`` are called once,
    at the first iteration at or past the window's start and end."""
    for r in requests:
        r.due_at = t0 + r.due
    pending = deque(sorted(requests, key=lambda r: r.due))
    window = [r for r in requests if 0.0 <= r.due < seconds]
    opened = closed = False
    end = t0 + seconds
    cap = end + drain_cap
    while True:
        now = loop.clock()
        if not opened and now >= t0:
            opened = True
            if on_open:
                on_open()
        if not closed and now >= end:
            closed = True
            if on_close:
                on_close()
        if closed and (now >= cap or all(r.token_times or r.failed
                                         for r in window)):
            break
        while pending and pending[0].due_at <= now:
            loop.submit(pending.popleft(), now)
        if loop.busy():
            loop.step()
        else:
            nxt = t0 + pending[0].due if pending else cap
            for mark in (t0, end):
                if now < mark:
                    nxt = min(nxt, mark)
            loop.sleep_until(min(nxt, cap))

