"""The readers of a ``DecodeServer`` turn's spans
(``benchmark/lib/readers_turn.py``) on hand-made span lists."""
import pytest

from benchmark.lib import harness, readers_turn

READERS = ("turn_admit_ms", "turn_dispatch_ms", "turn_copy_ms",
           "turn_deliver_ms", "turn_off_cpu_ms")


def tick(i, dur):
    return {"name": "serving/decode_tick", "id": "t%d" % i, "dur": dur,
            "ts": float(i)}


def leaf(i, phase, dur, **args):
    s = {"name": "serving/decode/" + phase, "id": "%s%d" % (phase, i),
         "parent": "t%d" % i, "dur": dur, "ts": float(i)}
    if args:
        s["args"] = args
    return s


def read(name, spans, trace=None):
    return getattr(readers_turn, name)(trace, spans, {}, {})


def two_turns():
    return [
        tick(0, 0.010),
        leaf(0, "admit_plan", 0.0010, popped=2, lookups=0, cpu_s=0.0004),
        leaf(0, "admit_dispatch", 0.0020, seated=2, dispatches=1),
        leaf(0, "prefill", 0.0005, slot=0, last=True),
        leaf(0, "dispatch", 0.0015, kind="chunk"),
        leaf(0, "wait", 0.0030),
        leaf(0, "copy", 0.0012, bytes=100),
        leaf(0, "deliver", 0.0008, fresh_tokens=8, finished=0, cpu_s=0.0009),
        tick(1, 0.006),
        leaf(1, "dispatch", 0.0005, kind="chunk"),
        leaf(1, "wait", 0.0030),
        leaf(1, "copy", 0.0010, bytes=100),
        leaf(1, "deliver", 0.0015, fresh_tokens=8, finished=2, cpu_s=0.0005),
        # not a turn's: an empty server's wait, another layer's span, a
        # leaf whose tick the stretch cut off
        {"name": "serving/decode/idle_wait", "id": "w", "dur": 0.5,
         "ts": 2.0, "args": {"dropped": False}},
        {"name": "serving/queue_wait", "id": "q", "dur": 0.1, "ts": 0.0},
        dict(leaf(9, "copy", 0.4), parent="t9"),
    ]


@pytest.mark.parametrize("name,ms", [
    ("turn_admit_ms", (1.0 + 2.0) / 2),
    ("turn_dispatch_ms", (0.5 + 1.5 + 0.5) / 2),
    ("turn_copy_ms", (1.2 + 1.0) / 2),
    ("turn_deliver_ms", (0.8 + 1.5) / 2),
    # admit_plan 1.0 - 0.4, deliver 0.8 - 0.9 and 1.5 - 0.5: summed
    # before anything is floored (a coarse thread clock over-counts one
    # span and under-counts the next)
    ("turn_off_cpu_ms", (0.6 - 0.1 + 1.0) / 2),
])
def test_a_phase_is_its_leaves_seconds_a_recorded_tick(name, ms):
    assert read(name, two_turns()) == pytest.approx(ms)


def test_the_phases_and_the_wait_add_up_to_the_ticks():
    spans = two_turns()
    wait = readers_turn._per_turn_ms(None, spans, ("wait",))
    total = sum(read(n, spans) for n in READERS[:4]) + wait
    assert total == pytest.approx((10.0 + 6.0) / 2)


@pytest.mark.parametrize("name,ms", [
    ("turn_admit_ms", 1.0 + 2.0), ("turn_dispatch_ms", 0.5 + 1.5),
    ("turn_copy_ms", 1.2), ("turn_deliver_ms", 0.8),
    ("turn_off_cpu_ms", 0.6 - 0.1),
])
def test_only_the_turns_the_profile_saw_are_read(name, ms):
    """The harness records spans until ``stop_trace`` has returned,
    long after the profile's last event: a tick that starts later than
    the profile's ``window_s`` after the first one is not read."""
    import types

    # the second turn starts 1.0 s after the first: outside a profile
    # of 0.5 s, inside one of 1.5 s
    assert read(name, two_turns(),
                types.SimpleNamespace(window_s=0.5)) == pytest.approx(ms)
    assert read(name, two_turns(), types.SimpleNamespace(
        window_s=1.5)) == pytest.approx(read(name, two_turns()))


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none_and_a_phase_that_never_ran_is_zero(name):
    assert read(name, []) is None
    # a stretch with no tick in it
    assert read(name, [s for s in two_turns()
                       if s["name"] != "serving/decode_tick"]) is None
    # a program that records ticks and no phases (before PR 36)
    assert read(name, [tick(0, 0.01), tick(1, 0.01)]) is None
    # ticks with other phases only: this one took no time
    only = [tick(0, 0.01), leaf(0, "wait", 0.009)]
    assert read(name, only) == 0.0


def test_off_cpu_is_never_negative_and_needs_cpu_seconds():
    spans = [tick(0, 0.01),
             leaf(0, "deliver", 0.001, cpu_s=0.005),
             leaf(0, "admit_plan", 0.002)]    # no cpu_s: nothing known
    assert read("turn_off_cpu_ms", spans) == 0.0
    # a thread clock that ticks in hundredths: most spans read 0 CPU
    # seconds and one reads a whole tick; the sums still compare
    coarse = [tick(i, 0.04) for i in range(10)] + [
        leaf(i, "deliver", 0.002, cpu_s=0.01 if i == 3 else 0.0)
        for i in range(10)]
    assert read("turn_off_cpu_ms", coarse) == pytest.approx(1.0)


def test_every_metric_file_points_at_its_reader():
    import os

    for base in READERS:
        for cells in ("chat", "offline"):
            metric = "%s.%s" % (base, cells)
            mod = harness.load_py(os.path.join(
                harness.BENCH, "layer_metrics", metric + ".py"), metric)
            assert mod.read is getattr(readers_turn, base)
