"""The open-loop driver, against a fake engine whose clock the test
controls: every latency counts from the DUE time, so a stall lengthens
the latencies of the requests it delayed."""

import statistics
import time

import pytest

from perf import harness, serve_stats
from perf.kinds import serve


class FakeHandle:
    def __init__(self, owed):
        self.tokens, self.owed = [], owed
        self.outcome, self.admit_ts = None, None

    @property
    def done(self):
        return self.outcome is not None


class FakeEngine:
    """One token per live request per step; ``stall_at`` makes one step
    take ``stall_s`` seconds (a stop-the-world pause)."""

    def __init__(self, slots=64, step_s=0.001, stall_at=None, stall_s=0.0):
        self.slots, self.step_s = slots, step_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.live, self.queue, self.steps = [], [], 0

    def submit(self, src, max_new_tokens):
        h = FakeHandle(max_new_tokens)
        self.queue.append(h)
        return h

    def step(self):
        self.steps += 1
        while self.queue and len(self.live) < self.slots:
            h = self.queue.pop(0)
            h.admit_ts = time.perf_counter()
            self.live.append(h)
        time.sleep(self.stall_s if self.steps == self.stall_at
                   else self.step_s)
        for h in list(self.live):
            h.tokens.append(7)
            if len(h.tokens) >= h.owed:
                h.outcome = "length"
                self.live.remove(h)


def schedule(n, gap, owed=3):
    return [{"due": i * gap, "src": [5, 6], "max_new": owed}
            for i in range(n)]


def window_of(recs, seconds):
    run = harness.Run({}, {"name": "fake"}, {}, 0, seconds, False, 0.0)
    run.window = {"requests": recs, "seconds": seconds}
    serve.judge(run, recs)
    return run


def test_a_stall_lengthens_ttft_of_the_requests_it_delayed():
    reqs = schedule(40, 0.01)                       # due over 0.4 s
    calm, _ = serve.drive(FakeEngine(), reqs, 0.4, 2.0)
    stalled, _ = serve.drive(
        FakeEngine(stall_at=20, stall_s=0.25), reqs, 0.4, 2.0)
    calm_run, stall_run = window_of(calm, 0.4), window_of(stalled, 0.4)
    assert calm_run.failed == stall_run.failed == 0
    calm_ttft = serve_stats.ttft_ms(calm_run)
    assert statistics.median(calm_ttft) < 50   # (a loaded test box jitters)
    ttft = serve_stats.ttft_ms(stall_run)
    # requests that came due during the 250 ms stall waited for it: had
    # the clock started at submit(), none would show more than a step
    slow = sum(1 for t in ttft if t > 100)
    assert slow >= 10 and slow >= sum(1 for t in calm_ttft if t > 100) + 8
    assert max(ttft) > 200
    # and the generator says that it, too, ran late
    assert max(serve_stats.late_ms(stall_run)) > 100
    assert max(serve_stats.token_gaps_ms(stall_run)) > 200


def test_latencies_and_counts_of_a_calm_window():
    recs, elapsed = serve.drive(FakeEngine(), schedule(20, 0.01, 5),
                                       0.2, 2.0)
    run = window_of(recs, 0.2)
    assert run.attempted == 20 and run.failed == 0
    assert len(serve_stats.ttft_ms(run)) == 20
    assert len(serve_stats.token_gaps_ms(run)) == 20 * 4
    assert all(q >= 0 for q in serve_stats.queue_wait_ms(run))
    assert 0.19 < elapsed < 5.0


def test_a_request_without_its_tokens_fails_and_counts_as_the_window():
    eng = FakeEngine(slots=1, step_s=0.02)          # far too slow
    recs, _ = serve.drive(eng, schedule(30, 0.005, 8), 0.15, 0.1)
    run = window_of(recs, 0.15)
    assert 0 < run.failed < 30 and not run.correct
    assert serve_stats.ttft_ms(run).count(150.0) == run.failed


def test_closed_loop_keeps_every_slot_full():
    eng = FakeEngine(slots=4)
    recs, _ = serve.drive(eng, schedule(10000, 0.0, 3), 0.2, 1.0,
                                 closed_loop=True)
    assert 10 < len(recs) < 10000
    assert all(r["outcome"] == "length" for r in recs)


@pytest.mark.parametrize("outcome,tokens,ok", [
    ("length", 5, True), ("length", 4, False), ("completed", 0, True),
    ("completed", 3, True), ("expired", 5, False), (None, 0, False)])
def test_judge(outcome, tokens, ok):
    rec = {"due": 0.0, "submitted": 0.0, "admit": 0.0, "stamps": [],
           "done_at": 0.1, "outcome": outcome, "tokens": tokens, "owed": 5}
    run = window_of([rec], 1.0)
    assert rec["ok"] is ok and run.failed == (0 if ok else 1)
