"""The windows keep steps in flight, and count every step that was sent."""
import time

import pytest

from cb_support import ROOT  # noqa: F401
from chipbench import run


class FakeProgram:
    """Records the order of sends and waits, and which were marked for
    the trace; a wait takes ``step_s``."""

    def __init__(self, step_s=0.001):
        self.step_s = step_s
        self.log = []
        self.in_flight = 0
        self.most_in_flight = 0

    def dispatch(self, s, annotate=False):
        self.log.append(("send", s, annotate))
        self.in_flight += 1
        self.most_in_flight = max(self.most_in_flight, self.in_flight)
        return s, None

    def fetch(self, sent, annotate=False):
        time.sleep(self.step_s)
        self.in_flight -= 1
        self.log.append(("wait", sent[0], annotate))
        return {"skipped": 0.0, "step": sent[0]}

    def steps(self, kind):
        return [s for k, s, _ in self.log if k == kind]


@pytest.mark.parametrize("ahead", [1, 3, 8])
def test_timed_window_waits_for_every_step_sent(ahead):
    prog = FakeProgram()
    times, window, last = run.timed_window(prog, 5, 0.05, ahead)
    sent, waited = prog.steps("send"), prog.steps("wait")
    assert sent == waited == list(range(5, 5 + len(sent)))
    assert len(times) == len(sent) > ahead
    assert last["step"] == sent[-1]
    assert prog.most_in_flight == ahead + 1
    # the window runs to the last step's metrics and holds every interval
    assert window == pytest.approx(sum(times))
    assert window >= len(sent) * prog.step_s


def test_timed_window_waits_only_once_ahead_steps_are_in_flight():
    prog = FakeProgram()
    run.timed_window(prog, 0, 0.05, 4)
    first_wait = prog.log.index(("wait", 0, False))
    assert prog.log[:first_wait] == [("send", s, False) for s in range(5)]


def test_traced_window_is_the_steady_loop(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    prog = FakeProgram()
    run.traced_window(prog, 10, 3, "unused")
    assert calls == ["start", "stop"]
    turns = 1 + run.TRACE_STEPS
    sent, waited = prog.steps("send"), prog.steps("wait")
    assert sent == waited == list(range(10, 10 + 3 + turns))
    assert prog.in_flight == 0
    # three untraced sends fill the loop; each traced turn sends one step
    # and waits for the oldest; the last three are waited for untraced
    marked = [(k, s) for k, s, a in prog.log if a]
    assert marked == [x for i in range(turns)
                      for x in (("send", 13 + i), ("wait", 10 + i))]
    assert [a for _, _, a in prog.log[-3:]] == [False] * 3


@pytest.mark.parametrize("step_s,want", [(0.124, 33), (4.0, 1), (9.0, 1),
                                         (0.001, 4000)])
def test_ahead_steps_make_ahead_seconds(step_s, want):
    assert run.ahead_steps(step_s) == want
