"""The traffic generator (traffic.py) and the window's arithmetic
(loop.py), driven against a fake front door on a fake clock."""
import collections
import types

import numpy as np
import pytest

import loop
import traffic


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


class FakeFrontend:
    """Serves one request per step, ``per_row_s`` per row; the step that
    starts at or after ``stall_at`` takes ``stall_s`` more."""

    def __init__(self, clock, per_row_s, stall_at=None, stall_s=0.0):
        self.clock, self.per_row_s = clock, per_row_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.queue = collections.deque()

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        if not self.queue:
            return False
        req = self.queue.popleft()
        req.t_first_dispatch = self.clock.t
        if self.stall_at is not None and self.clock.t >= self.stall_at:
            self.clock.t += self.stall_s
            self.stall_at = None
        self.clock.t += self.per_row_s * len(req.images)
        req.logits = np.zeros((len(req.images), 3))
        req.done, req.t_done = True, self.clock.t
        return bool(self.queue)


def new_request(rid, images):
    return types.SimpleNamespace(rid=rid, images=images, done=False,
                                 t_done=None, t_first_dispatch=None,
                                 logits=None)


POOL = np.zeros((16, 1, 1, 1), np.float32)
OPEN = {"loop": "open", "microbatch": 2, "pool_images": 16,
        "size_mix": [[1, 1.0]], "rate_rps": 100}
CLOSED = {"loop": "closed", "microbatch": 4, "pool_images": 16,
          "size_mix": [[4, 1.0]], "backlog_requests": 2}


def test_poisson_plan_is_the_same_work_for_every_seed():
    a = traffic.open_plan(OPEN, 10.0, np.random.default_rng(1))
    b = traffic.open_plan(OPEN, 10.0, np.random.default_rng(2))
    assert len(a) == len(b) == 1000
    assert all(0 < p.due < 10.0 for p in a)
    assert [p.due for p in a] == sorted(p.due for p in a)
    gaps = lambda plan: sorted(np.round(np.diff([0.0] + [p.due for p in plan]), 9))
    assert gaps(a) == gaps(b)                   # same gaps ...
    assert [p.due for p in a] != [p.due for p in b]   # ... other order
    # exponential gaps: their mean is 1/rate, their spread about it too
    g = np.diff([p.due for p in a])
    assert g.mean() == pytest.approx(0.01, rel=0.01)
    assert g.std() == pytest.approx(0.01, rel=0.1)


def test_sizes_keep_the_mix_shares():
    mix = dict(OPEN, size_mix=[[1, 3.0], [4, 1.0]])
    s = traffic.sizes(mix, 100, np.random.default_rng(0))
    assert collections.Counter(s.tolist()) == {1: 75, 4: 25}
    plan = traffic.open_plan(mix, 1.0, np.random.default_rng(0))
    assert all(0 <= p.off <= 16 - p.n for p in plan)


def test_phases_repeat_over_the_window():
    mix = dict(OPEN, phases=[{"seconds": 1.0, "rate_rps": 50},
                             {"seconds": 1.0, "rate_rps": 0}])
    plan = traffic.open_plan(mix, 4.0, np.random.default_rng(0))
    assert len(plan) == 100
    assert all(int(p.due) % 2 == 0 for p in plan)   # only in "on" seconds


def _open(per_row_s, stall_at=None, stall_s=0.0, seconds=2.0):
    clock = Clock()
    fe = FakeFrontend(clock, per_row_s, stall_at, stall_s)
    plan = traffic.open_plan(OPEN, seconds, np.random.default_rng(0))
    win = loop.open_window(fe, plan, POOL, seconds, new_request=new_request,
                           clock=clock, sleep=clock.sleep)
    loop.drain(fe, win, POOL, new_request=new_request, limit_s=60,
               clock=clock)
    return win


def test_latency_runs_from_the_due_time():
    win = _open(0.001)
    lat = loop.latencies_s(win)
    assert len(lat) == 200 == len(win.records)
    # a lone request waits for nothing: its latency is its service time
    assert min(lat) == pytest.approx(0.001)
    assert all(r.done for r in win.records)
    assert loop.rows_per_s(win) == pytest.approx(
        sum(r.req.t_done <= win.t_end for r in win.records) / 2.0)


def test_a_stall_moves_the_tail_and_the_rate():
    # a half-second stall late in the window: the requests due in it and
    # after it wait, and those finished after the close leave the rate
    calm, stalled = _open(0.004), _open(0.004, stall_at=1.7, stall_s=0.5)
    p95 = lambda w: loop.percentile(loop.latencies_s(w), 95)
    # requests due during the stall wait for it although it was not theirs
    assert p95(stalled) > p95(calm) + 0.2
    assert loop.rows_per_s(stalled) < loop.rows_per_s(calm)
    assert loop.percentile(loop.queue_waits_s(stalled), 95) > \
        loop.percentile(loop.queue_waits_s(calm), 95) + 0.2
    # the stall is not counted against the stalled request only
    late = [r for r in stalled.records if 1.7 < r.due < 2.0]
    assert late and all(r.req.t_done > 2.0 for r in late)


def test_closed_loop_keeps_the_backlog_and_counts_the_whole_window():
    clock = Clock()
    fe = FakeFrontend(clock, 0.001)
    stream = traffic.closed_stream(CLOSED, np.random.default_rng(0))
    win = loop.closed_window(fe, CLOSED, stream, POOL, 1.0,
                             new_request=new_request, clock=clock)
    # 4 ms a request of 4 rows: 250 requests in the second; the queue
    # is topped up to 2 before each step, so one is left at the close
    assert loop.rows_per_s(win) == pytest.approx(1000, rel=0.01)
    assert sum(not r.done for r in win.records) == 1
    # due at hand-over, so a closed loop's latency includes its queueing
    assert min(loop.latencies_s(win)) == pytest.approx(0.004)
    clock2 = Clock()
    fe2 = FakeFrontend(clock2, 0.001, stall_at=0.5, stall_s=0.25)
    win2 = loop.closed_window(fe2, CLOSED, traffic.closed_stream(
        CLOSED, np.random.default_rng(0)), POOL, 1.0,
        new_request=new_request, clock=clock2)
    assert loop.rows_per_s(win2) == pytest.approx(750, rel=0.02)


def test_unanswered_requests_count_to_the_end_of_the_drain():
    win = _open(0.001)
    win.records[0].req.done = False
    win.records[0].req.t_done = None
    assert loop.latencies_s(win)[0] == pytest.approx(
        win.drain_end - win.records[0].due)
