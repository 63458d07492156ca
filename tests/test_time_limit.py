"""The limit a test: ``tests/conftest.py``'s ``limit_one_test``.

A hang in tier-1 costs the driver its whole 1,470 s and leaves no name
(PR 48's run).  Under the limit it costs ``LIMIT`` seconds and fails one
test by name; these cases hold the limit itself, at a fifth of a second.
"""
import signal
import threading
import time

import pytest

import conftest


def test_every_test_runs_under_the_limit():
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.LIMIT
    assert conftest.WAIT < conftest.LIMIT


def test_a_wait_that_never_ends_fails_at_the_limit_by_name(
        request, monkeypatch, tmp_path):
    name = request.node.nodeid
    with open(tmp_path / "stacks", "w+") as stacks:
        monkeypatch.setattr(conftest, "_real_stderr", stacks.fileno())
        # 0.2 s in place of this test's own LIMIT, which is not armed
        # again afterwards: what is left of the body is short
        with pytest.raises(pytest.fail.Exception) as failed:
            with conftest.limit_one_test(name, limit=0.2):
                threading.Event().wait(conftest.WAIT)  # never set
        stacks.seek(0)
        dump = stacks.read()
    assert "limit of 0.2 s" in str(failed.value)
    assert name in str(failed.value)
    # every thread's stack, down to the line that waited
    assert "most recent call first" in dump and __file__ in dump


def test_a_body_that_ends_in_time_leaves_no_clock_behind(request):
    before = signal.getsignal(signal.SIGALRM)
    with conftest.limit_one_test(request.node.nodeid, limit=0.05,
                                 grace=0.05):
        pass  # the body: done at once
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is before
    # a dump left pending would end this process now (exit=True), and
    # an alarm left armed would fail the sleep
    time.sleep(0.3)
