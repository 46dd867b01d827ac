"""The driver's stamps and the metric arithmetic, on a scheduler stand-in
whose admission and rounds take known host time."""
from __future__ import annotations

import time
from collections import deque
from types import SimpleNamespace

import numpy as np
import pytest

from bench import check, driver, stats, traffic
from bench.traffic import Planned


class FakeExecutor:
    """admit() sleeps ``prefill_s`` and returns a token; step_round()
    sleeps ``round_s`` and returns one token per active slot."""

    def __init__(self, n_slots, prefill_s=0.02, round_s=0.005):
        self.n_slots = n_slots
        self.prefill_s, self.round_s = prefill_s, round_s
        self.active = {}

    def admit(self, slot, prompt, valid, tag=None, extras=None):
        time.sleep(self.prefill_s)
        self.active[slot] = tag
        return 1

    def step_round(self, valid):
        time.sleep(self.round_s)
        return [(s, t, 2) for s, t in self.active.items()]


class FakeScheduler:
    """Queue -> free slot -> admit; one round per step; requests whose
    id is in ``stuck`` never get a second token."""

    def __init__(self, n_slots=2, stuck=(), **kw):
        self.executor = FakeExecutor(n_slots, **kw)
        self.metrics = SimpleNamespace(observe_round_ms=lambda ms: None)
        self.slots = [SimpleNamespace(request=None) for _ in range(n_slots)]
        self.queue = deque()
        self.stuck = set(stuck)
        self._rid = 0

    @property
    def busy(self):
        return bool(self.queue) or any(s.request for s in self.slots)

    def submit(self, prompt, n, arrival_ms=None):
        req = SimpleNamespace(rid=self._rid, tokens=[], n=n, prompt=prompt)
        req.done = False
        self._rid += 1
        self.queue.append(req)
        return req

    def step(self):
        for i, s in enumerate(self.slots):
            if s.request is None and self.queue:
                req = self.queue.popleft()
                req.tokens.append(self.executor.admit(i, req.prompt, [True],
                                                      tag=req.rid))
                s.request = req
        finished = []
        for i, _, tok in self.executor.step_round([True]):
            req = self.slots[i].request
            if req is None or req.rid in self.stuck:
                continue
            req.tokens.append(tok)
            if len(req.tokens) >= req.n:
                req.done = True
                finished.append(req)
                self.slots[i].request = None
                del self.executor.active[i]
        return finished


def _rec(due, first=None, tokens=(), admit=None, done=True, out=4):
    r = driver.Record(Planned(0, due, 8, out), np.zeros(8, np.int32))
    r.admit_ms = admit
    r.token_ms = ([first] if first is not None else []) + list(tokens)
    r.done = done
    return r


def test_ttft_runs_from_the_due_time_not_the_submit():
    r = _rec(due=100.0, first=400.0, tokens=[450.0, 500.0, 550.0],
             admit=250.0)
    assert stats.queue_wait_ms(r, stop_ms=1e9) == pytest.approx(150.0)
    assert stats.ttft_ms(r, stop_ms=1e9) == pytest.approx(300.0)
    assert stats.tpot_ms(r, 1e9) == pytest.approx(50.0)


def test_unfinished_request_is_failed_and_weighs_on_the_tail():
    ok = [_rec(due=10.0 * i, first=10.0 * i + 5) for i in range(9)]
    bad = _rec(due=50.0, done=False)
    recs = ok + [bad]
    assert stats.failed(recs, seconds=1.0) == [bad]
    assert stats.ttft_ms(bad, stop_ms=5000.0) == pytest.approx(4950.0)
    p90 = stats.pct((stats.ttft_ms(r, 5000.0) for r in recs), 90)
    assert p90 > 400.0


def test_tokens_in_counts_only_the_window():
    r = _rec(due=0.0, first=-5.0, tokens=[10.0, 999.0, 1000.0])
    assert stats.tokens_in([r], 0.0, 1000.0) == 2


def test_first_token_is_stamped_after_admit_returns():
    sched = FakeScheduler(n_slots=1, prefill_s=0.05)
    sess = driver.Session(sched, driver.BenchClock())
    sess.start_window()
    t_call = sess.clock.now()
    rec = sess.submit(Planned(0, t_call, 8, 3), np.zeros(8, np.int32))
    sess.step()
    assert rec.admit_ms >= t_call
    assert rec.first_ms - rec.admit_ms >= 50.0
    # the round of that step stamps the second token after the first
    assert len(rec.token_ms) == 2 and rec.token_ms[1] > rec.first_ms


def test_open_loop_follows_window_requests_while_arrivals_go_on():
    mix = {"kind": "open", "order": [[0, 0]], "prompt": {"lengths": [8]},
           "output": {"lengths": [3]}, "gaps": {"values": [1.0]},
           "rate_rps": 50.0}
    planned = traffic.plan(mix, 0.5)
    sched = FakeScheduler(n_slots=1, prefill_s=0.01, round_s=0.005)
    sess = driver.Session(sched, driver.BenchClock())
    stop = driver.run_open(sess, planned, lambda p: np.zeros(8, np.int32),
                           seconds=0.2, drain_s=5.0)
    window = [r for r in sess.records.values() if r.planned.due_ms < 200]
    assert window and all(r.done for r in window)
    # arrivals due after the window were still submitted while draining
    assert any(r.planned.due_ms >= 200 for r in sess.records.values())
    assert stop >= 200.0
    assert all(r.first_ms >= r.planned.due_ms for r in window)


def test_open_loop_gives_up_at_the_drain_limit():
    mix = {"kind": "open", "order": [[0, 0]], "prompt": {"lengths": [8]},
           "output": {"lengths": [3]}, "gaps": {"values": [1.0]},
           "rate_rps": 20.0}
    planned = traffic.plan(mix, 1.0)
    sched = FakeScheduler(n_slots=2, stuck={0}, round_s=0.002)
    sess = driver.Session(sched, driver.BenchClock())
    stop = driver.run_open(sess, planned, lambda p: np.zeros(8, np.int32),
                           seconds=0.1, drain_s=0.3)
    assert 400.0 <= stop < 1000.0
    recs = list(sess.records.values())
    assert [r.rid for r in stats.failed(recs, 0.1)] == [0]


def test_backlog_keeps_every_slot_busy_and_starts_the_window_full():
    mix = {"kind": "backlog", "order": [[0, 0]],
           "prompt": {"lengths": [8]}, "output": {"lengths": [4]}}
    sched = FakeScheduler(n_slots=3, round_s=0.002)
    sess = driver.Session(sched, driver.BenchClock())
    count = iter(range(10 ** 6))

    def nxt():
        return traffic.backlog_request(mix, next(count)), \
            np.zeros(8, np.int32)

    depth = []
    step = sched.step
    sched.step = lambda: (depth.append(len(sched.queue)), step())[1]
    stop = driver.run_backlog(sess, nxt, seconds=0.2)
    assert stop >= 200.0
    assert min(depth) >= 3            # every step finds a full queue
    first3 = sorted(sess.records.values(), key=lambda r: r.rid)[:3]
    assert all(r.first_ms < 0 for r in first3)    # admitted in set-up
    n = stats.tokens_in(sess.records.values(), 0.0, 200.0)
    assert n > 3 * 10


def test_check_samples_every_slot_that_served():
    recs = []
    for i in range(40):
        r = _rec(due=0.0, first=1.0, tokens=[2.0] * (3 + i % 5),
                 done=i % 3 != 0)
        r.rid, r.slot = i, i % 8
        r.prompt = np.zeros(8 + i, np.int32)
        r.tokens = [1] * len(r.token_ms)
        recs.append(r)
    for seed in (1, 2 ** 33 + 7):
        picked = check.sample(recs, seed, 10, None)
        assert {r.slot for r in picked} == set(range(8))
        assert len(picked) == 10 == len({r.rid for r in picked})
        assert max(recs, key=lambda r: len(r.prompt) + len(r.tokens)) \
            in picked
    assert check.sample(recs, 5, 10, None) == check.sample(recs, 5, 10,
                                                            None)
