"""The driver loops: open (fixed arrival times) and backlog (a queue that
never empties), on the host's clock, with every stamp taken when the
token is on the host.

This is a copy of the program's ``runtime.scheduler.run_arrivals`` made
for a wall clock: it waits for the next due time instead of spinning,
and it wraps two executor calls from here, so nothing in the program
changes:

* ``SlotPoolExecutor.admit`` returns after the prefill's token is on the
  host: its return is the first-token stamp, its start ends the queue
  wait;
* ``SlotPoolExecutor.step_round`` returns after a round's tokens are on
  the host: its return stamps each later token it harvested.

Arrival is the due time, never the submit time, so a late generator
shows as latency and as ``lateness_ms``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench.traffic import Planned


class BenchClock:
    """Milliseconds on ``time.perf_counter`` from an origin that
    ``start`` sets at the window's start. Before ``start`` the origin
    lies far ahead, so every reading is negative: no health event of
    the window fires during set-up."""

    def __init__(self):
        self._t0 = time.perf_counter() + 1e6

    def start(self) -> float:
        """Set the origin to now; returns by how many ms it moved."""
        t = time.perf_counter()
        shift, self._t0 = (t - self._t0) * 1e3, t
        return shift

    def now(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3

    def sleep_until(self, t_ms: float):
        dt = (t_ms - self.now()) / 1e3
        if dt > 0:
            time.sleep(dt)


@dataclasses.dataclass(eq=False)           # one request: equal only to itself
class Record:
    planned: Planned
    prompt: np.ndarray
    rid: int = -1
    slot: int = -1
    admit_ms: float | None = None         # admit() called
    token_ms: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def first_ms(self) -> float | None:
        return self.token_ms[0] if self.token_ms else None


def _annotate(trace: bool, name: str, **kw):
    if not trace:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


class Session:
    """Instruments one scheduler and drives it."""

    def __init__(self, sched, clock: BenchClock, traced: bool = False):
        self.sched = sched
        self.clock = clock
        self.traced = traced
        self.records: dict[int, Record] = {}         # by request id
        self.by_idx: dict[int, Record] = {}          # by planned index
        self.rounds: list[tuple[float, float]] = []   # (harvest ms, period)
        self.lateness_ms: list[float] = []
        # host seconds inside admit() and step_round() since the window
        # started: the rest of the window is the loop's own host work
        self.in_admit_s = self.in_round_s = 0.0
        self._t_harvest = 0.0
        ex = sched.executor
        if ex is None:
            raise ValueError("the benchmark drives the batched executor")
        admit, step_round = ex.admit, ex.step_round
        observe = sched.metrics.observe_round_ms

        def timed_admit(slot, prompt, valid, tag=None, extras=None):
            rec = self.records.get(tag)
            t0 = self.clock.now()
            if rec is not None:
                rec.admit_ms, rec.slot = t0, int(slot)
            with _annotate(self.traced, "bench.admit",
                           prompt_len=int(len(prompt))):
                tok = admit(slot, prompt, valid, tag=tag, extras=extras)
            t1 = self.clock.now()
            self.in_admit_s += (t1 - t0) / 1e3
            if rec is not None:
                rec.token_ms.append(t1)
            return tok

        def timed_step_round(valid):
            t0 = self.clock.now()
            with _annotate(self.traced, "bench.round"):
                out = step_round(valid)
            self._t_harvest = self.clock.now()
            self.in_round_s += (self._t_harvest - t0) / 1e3
            return out

        def observe_round(ms):
            # RuntimeMetrics.round_ms's own reading, with its stamp
            self.rounds.append((self.clock.now(), float(ms)))
            observe(ms)

        ex.admit = timed_admit
        ex.step_round = timed_step_round
        sched.metrics.observe_round_ms = observe_round

    # ------------------------------------------------------------ input
    def submit(self, planned: Planned, prompt: np.ndarray):
        now = self.clock.now()
        rec = Record(planned, prompt)
        due = planned.due_ms
        req = self.sched.submit(prompt, planned.output_len,
                                arrival_ms=min(due, now))
        rec.rid = req.rid
        self.records[req.rid] = rec
        self.by_idx[planned.idx] = rec
        self.lateness_ms.append(now - due)
        return rec

    def step(self):
        with _annotate(self.traced, "bench.step"):
            finished = self.sched.step()
        running = [s.request for s in self.sched.slots if s.request]
        for req in running + list(finished):
            rec = self.records.get(req.rid)
            if rec is None:
                continue
            new = len(req.tokens) - len(rec.tokens)
            if new > 0:
                # the first token was stamped by admit; the rest came
                # with this step's harvest
                rec.token_ms.extend([self._t_harvest]
                                    * (len(req.tokens) - len(rec.token_ms)))
                rec.tokens = list(req.tokens)
            rec.done = req.done
        return finished

    def start_window(self):
        """Start the clock's window and move every stamp taken so far
        onto it."""
        shift = self.clock.start()
        for rec in self.records.values():
            if rec.admit_ms is not None:
                rec.admit_ms -= shift
            rec.token_ms = [t - shift for t in rec.token_ms]
        self.rounds = [(t - shift, ms) for t, ms in self.rounds]
        self._t_harvest -= shift
        self.in_admit_s = self.in_round_s = 0.0

    def idle_until(self, t_ms: float):
        with _annotate(self.traced, "bench.idle"):
            self.clock.sleep_until(t_ms)


def run_open(session: Session, planned: list[Planned], prompts,
             seconds: float, drain_s: float, on_tick=None) -> float:
    """Submit each request at its due time (ms from the window start,
    which is now) and step the scheduler; stop once every request due
    in the window has finished, or ``drain_s`` after the window. Returns
    the host ms at which the loop stopped."""
    clock = session.clock
    session.start_window()
    end_ms, stop_ms = seconds * 1e3, (seconds + drain_s) * 1e3
    window = [p.idx for p in planned if p.due_ms < end_ms]
    i = 0
    while True:
        now = clock.now()
        if on_tick is not None:
            on_tick(now)
        while i < len(planned) and planned[i].due_ms <= now:
            session.submit(planned[i], prompts(planned[i]))
            i += 1
        if now >= stop_ms or (now >= end_ms and all(
                j in session.by_idx and session.by_idx[j].done
                for j in window)):
            return now
        if session.sched.busy:
            session.step()
        elif i < len(planned):
            session.idle_until(min(planned[i].due_ms, stop_ms))
        else:
            session.idle_until(stop_ms)


def run_backlog(session: Session, next_request, seconds: float,
                on_tick=None) -> float:
    """Keep a queue of at least ``n_slots`` waiting requests: fill every
    slot first (set-up), then start the window and step until it ends.
    ``next_request()`` gives (Planned, prompt)."""
    sched, clock = session.sched, session.clock
    n = len(sched.slots)

    def top_up():
        while len(sched.queue) < n:
            session.submit(*next_request())

    top_up()
    session.step()                         # every slot admitted
    session.start_window()
    end_ms = seconds * 1e3
    while True:
        now = clock.now()
        if on_tick is not None:
            on_tick(now)
        if now >= end_ms:
            return now
        top_up()
        session.step()
