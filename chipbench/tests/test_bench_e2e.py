"""End-to-end arithmetic on synthetic client timelines."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import e2e  # noqa: E402
from drive import Driver, Flight, Record  # noqa: E402
from traffic import Req  # noqa: E402


def timeline(n_req=40, gap=0.1, step=0.02, tokens=20, stall_at=None,
             stall=0.0, window=(0.0, 10.0)):
    """Open-loop requests due every ``gap`` s; each gets its first token
    one ``step`` after it is due and then one token per ``step``.  A stall
    of ``stall`` s at ``stall_at`` delays every token due after it."""
    def shift(t):
        return t + stall if stall_at is not None and t >= stall_at else t

    flights = []
    for i in range(n_req):
        due = 1.0 + i * gap
        arr = [shift(due + step * (k + 1)) for k in range(tokens)]
        flights.append(Flight(req=Req(i, 0, 16, tokens), rid=i, due=due,
                              sent=due, arrivals=arr, done=arr[-1]))
    return Record(flights=flights, t0=window[0], t1=window[1])


def test_rate_counts_tokens_that_arrived_in_the_window():
    rec = timeline()
    assert e2e.output_tokens_per_s(rec) == pytest.approx(40 * 20 / 10.0)
    # closes at 1.21 s: request 0 (due 1.0) has 10 tokens in, request 1
    # (due 1.1) has 5, and no later token counts
    part = timeline(window=(0.0, 1.21))
    assert e2e.output_tokens_per_s(part) == pytest.approx(15 / 1.21)


def test_ttft_is_taken_from_the_due_time():
    rec = timeline()
    for f in rec.flights:
        f.sent = f.due + 5.0        # a late submit does not hide the wait
    assert e2e.ttft_samples(rec) == pytest.approx([0.02] * 40)
    assert e2e.ttft_p95_ms(rec) == pytest.approx(20.0)


def test_tpot_is_per_request_pace():
    rec = timeline()
    assert e2e.tpot_p95_ms(rec) == pytest.approx(20.0)
    assert len(e2e.tpot_samples(rec)) == 40


def test_a_stall_raises_the_tails_and_lowers_the_rate():
    calm = timeline()
    stalled = timeline(stall_at=2.0, stall=1.5)
    assert e2e.ttft_p95_ms(stalled) > e2e.ttft_p95_ms(calm) + 1000
    assert e2e.tpot_p95_ms(stalled) > e2e.tpot_p95_ms(calm)
    short = timeline(window=(0.0, 4.0))
    short_stall = timeline(stall_at=2.0, stall=1.5, window=(0.0, 4.0))
    assert (e2e.output_tokens_per_s(short_stall)
            < e2e.output_tokens_per_s(short))


def test_a_failed_request_counts_as_the_longest_wait():
    rec = timeline()
    rec.flights[0].arrivals = []
    xs = e2e.ttft_samples(rec)
    assert len(xs) == 40 and max(xs) > 5.0


class _Clock:
    """A host clock that moves a nanosecond at every read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-9
        return self.t


class _LockStepRuntime:
    """Every tick takes ``step_s`` on ``clock`` and gives each request in
    flight one token."""

    def __init__(self, clock, step_s):
        self.clock, self.step_s = clock, step_s
        self.request_log, self.all_shed_rids, self.pending = {}, [], False

    def submit(self, rid, prompt, max_new, type_id=0):
        self.request_log[rid] = SimpleNamespace(emitted=[], max_new=max_new)

    def step(self):
        self.clock.t += self.step_s
        for r in self.request_log.values():
            if len(r.emitted) < r.max_new:
                r.emitted.append(0)


@pytest.mark.parametrize("seconds", [10.0, 10.1, 10.25])
def test_closed_window_closes_on_whole_ticks(seconds):
    """The rate reads one tick's tokens over one tick's time, wherever the
    deadline falls inside a tick."""
    clock = _Clock()
    drv = Driver(_LockStepRuntime(clock, 0.25), 64, 7, pages_per_chip=8,
                 page_size=16, clock=clock)
    reqs = [Req(i, 0, 16 * (i + 1), 1000) for i in range(4)]
    rec = drv.run_closed(reqs, 4, seconds, lambda: {"built": 0})
    assert rec.deadline == pytest.approx(rec.t0 + seconds)
    assert rec.t1 >= rec.deadline
    assert (rec.t1 - rec.t0) / 0.25 == pytest.approx(rec.ticks)
    assert e2e.output_tokens_per_s(rec) == pytest.approx(4 / 0.25)
    assert e2e.tpot_p95_ms(rec) == pytest.approx(250.0)
    cut = e2e.at_deadline(rec)["output_tokens_per_s"]
    assert cut == pytest.approx(4 * (rec.ticks - 1) / seconds)
