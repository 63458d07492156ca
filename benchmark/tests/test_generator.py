"""The traffic generator and the submitter-side accounting."""
import collections

import numpy as np
import pytest

from benchmark.lib import loadgen, traffic

MIXES = ["chat_steady", "offline_batch"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = traffic.load_mix(name)
    a = traffic.request_lengths(mix, 3000000001, 700)
    b = traffic.request_lengths(mix, 3000000001, 700)
    assert (a == b).all()
    ta = traffic.prompt_tokens(a[:20, 0], 40478, 3000000001)
    tb = traffic.prompt_tokens(b[:20, 0], 40478, 3000000001)
    assert all((x == y).all() for x, y in zip(ta, tb))
    if mix["kind"] == "open_loop":
        da = traffic.arrival_due_times(mix, 3000000001, 30.0)
        db = traffic.arrival_due_times(mix, 3000000001, 30.0)
        assert (da == db).all()


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_same_multiset_other_order(name):
    mix = traffic.load_mix(name)
    p = int(mix["population"])
    a = traffic.request_lengths(mix, 1, 3 * p)
    b = traffic.request_lengths(mix, 2 ** 31 + 5, 3 * p)
    assert (a != b).any()
    for k in range(3):  # every pass over the population is the multiset
        block = slice(k * p, (k + 1) * p)
        ca = collections.Counter(map(tuple, a[block]))
        cb = collections.Counter(map(tuple, b[block]))
        assert ca == cb == collections.Counter(
            map(tuple, traffic.population(mix)))


@pytest.mark.parametrize("name", MIXES)
def test_population_respects_the_file(name):
    mix = traffic.load_mix(name)
    pop = traffic.population(mix)
    assert len(pop) == mix["population"]
    assert pop[:, 0].min() >= mix["prompt"]["min"]
    assert pop[:, 0].max() <= mix["prompt"]["max"]
    assert pop[:, 1].max() <= mix["output"]["max"]
    assert (pop.sum(1) <= mix["max_total"]).all()
    med = np.median(pop[:, 0])
    assert abs(med - mix["prompt"]["median"]) <= 0.05 * mix["prompt"]["median"]


def test_arrivals_same_gaps_in_another_order():
    mix = traffic.load_mix("chat_steady")
    n, rate = mix["population"], mix["rate_per_s"]
    a = traffic.arrival_due_times(mix, 7, 60.0)
    b = traffic.arrival_due_times(mix, 8, 60.0)
    assert (np.diff(a) > 0).all() and a[-1] < 60.0
    # every pass ends on the same instant whatever the seed ...
    for k in range(1, 4):
        assert a[k * n - 1] == pytest.approx(k * n / rate)
        assert b[k * n - 1] == pytest.approx(k * n / rate)
    # ... so the count in a window differs by a handful, not by percent
    assert abs(len(a) - len(b)) <= 16
    assert abs(len(a) - 60.0 * rate) <= 16
    # the gaps of a pass are one multiset in two orders
    ga = np.diff(np.concatenate([[0.0], a[:n]]))
    gb = np.diff(np.concatenate([[0.0], b[:n]]))
    assert (ga != gb).any()
    assert np.allclose(np.sort(ga), np.sort(gb), rtol=0, atol=1e-9)
    # and they are exponential-like: mean 1/rate, many short, a few long
    gaps = np.diff(np.concatenate([[0.0], a[:n]]))
    assert gaps.mean() == pytest.approx(1.0 / rate)
    assert np.median(gaps) == pytest.approx(np.log(2) / rate, rel=0.05)
    assert gaps.max() > 4.0 / rate
    # a longer horizon extends the schedule without changing its start
    c = traffic.arrival_due_times(mix, 7, 90.0)
    assert (c[:len(a)] == a).all()


def test_train_batches_shapes_and_seed():
    mix = traffic.load_mix("pretrain_s128", rehearse=True)
    a = traffic.train_batches(mix, 5, 211)
    b = traffic.train_batches(mix, 5, 211)
    c = traffic.train_batches(mix, 6, 211)
    n, bs, s, m = (mix[k] for k in ("steps_per_chunk", "batch", "seq_len",
                                     "masks_per_seq"))
    assert a["src"].shape == (n, bs, s) and a["mpos"].shape == (n, bs * m, 1)
    assert all((a[k] == b[k]).all() for k in a)
    assert (a["src"] != c["src"]).any()
    # flattened positions stay inside their own sequence
    rows = a["mpos"].reshape(n, bs, m) // s
    assert (rows == np.arange(bs)[None, :, None]).all()


def _rec(idx, due, sent, first, last, n, end, status="done", plen=10):
    r = loadgen.Rec(idx, due, plen, n)
    r.sent, r.first_t, r.last_t, r.n_tok = sent, first, last, n
    r.end_t, r.status = end, status
    return r


def test_due_time_accounting_and_window_rule():
    recs = [
        # first token and end inside the window; sent 5 ms late
        _rec(0, due=10.000, sent=10.005, first=10.5, last=12.5, n=5, end=12.5),
        # first token before the window, end inside: a tpot sample only
        _rec(1, due=8.0, sent=8.0, first=9.5, last=11.0, n=4, end=11.0),
        # first token inside, still running at the close: ttft only
        _rec(2, due=13.0, sent=13.0, first=14.0, last=14.5, n=2, end=None,
             status="sent"),
        # failed inside the window
        _rec(3, due=11.0, sent=11.0, first=None, last=None, n=0, end=11.2,
             status="failed"),
        # ended after the close: neither attempted nor failed
        _rec(4, due=12.0, sent=12.0, first=12.4, last=15.5, n=9, end=15.5),
    ]
    events = [(9.5, 3), (10.5, 4), (14.9, 2), (15.5, 7)]
    s = loadgen.summarize(recs, events, 10.0, 15.0)
    assert s["attempted"] == 3 and s["failed"] == 1
    assert s["in_flight_at_close"] == 2
    assert sorted(round(x) for x in s["ttft_ms"]) == [400, 500, 1000]
    # ttft counts from when the request was DUE, not from when it was sent
    assert any(abs(x - 500.0) < 1e-6 for x in s["ttft_ms"])
    assert sorted(round(x) for x in s["tpot_ms"]) == [500, 500]
    assert s["tokens_delivered"] == 6
    assert sorted(round(x, 3) for x in s["gen_late_ms"]) == [0, 0, 0, 5]
    lim = {"ttft_base_ms": 450.0, "ttft_ms_per_prompt_token": 10.0,
           "tpot_ms": 600.0}
    s = loadgen.summarize(recs, events, 10.0, 15.0, lim)
    # rec 0 meets both; rec 1's first token took 1.5 s; rec 3 failed
    assert s["attainment"] == pytest.approx(1 / 3)


def test_short_output_counts_as_failed():
    r = _rec(0, 1.0, 1.0, 1.5, 2.0, 3, 2.0)
    r.output_len = 8  # the server stopped early
    s = loadgen.summarize([r], [], 0.0, 5.0)
    assert s["attempted"] == 1 and s["failed"] == 1


def test_closed_loop_source_makes_passes_as_drawn():
    mix = traffic.load_mix("offline_batch")
    p, c = int(mix["population"]), int(mix["clients"])
    a = traffic.ClosedLoopSource(mix, 2 ** 31 + 5, 40478)
    b = traffic.ClosedLoopSource(mix, 2 ** 31 + 5, 40478)
    # any request may be asked for first: the passes before it are made
    far = a.request(5 * p + 3)
    assert len(a.prompts) == 6 * p
    for k in (0, c - 1, c, 5 * p + 3):
        (pa, oa, ta), (pb, ob, tb) = a.request(k), b.request(k)
        assert (pa, oa) == (pb, ob) and (ta == tb).all() and len(ta) == pa
    assert far[0] == a.request(5 * p + 3)[0]
    # every pass after the staggered first wave is the population
    for n in range(1, 6):
        block = collections.Counter(zip(a.prompt_len[n * p:(n + 1) * p],
                                        a.output_len[n * p:(n + 1) * p]))
        assert block == collections.Counter(
            map(tuple, traffic.population(mix).tolist()))
    # the first wave is cut short, client by client
    assert a.output_len[0] < a.output_len[c - 1] or a.prompt_len[0] < 4


class FakeServer:
    """Delivers ``per_turn`` tokens to every live request each
    ``turn_s`` seconds, one chunk per request per turn, as DecodeServer
    does: push, then count."""

    def __init__(self, turn_s, per_turn=2):
        import threading

        self.turn_s, self.per_turn = turn_s, per_turn
        self.live, self.count, self.turns = [], 0, 0
        self.lock, self.stop = threading.Lock(), threading.Event()
        self.thread = threading.Thread(target=self.run, daemon=True)

    def submit(self, prompt, n):
        h = {"q": collections.deque(), "left": int(n)}
        with self.lock:
            self.live.append(h)
        return h

    @staticmethod
    def drain(h):
        out = []
        while h["q"]:
            out.append(h["q"].popleft())
        return out

    def run(self):
        import time

        nxt = time.perf_counter()
        while not self.stop.is_set():
            nxt += self.turn_s
            time.sleep(max(0.0, nxt - time.perf_counter()))
            with self.lock:
                live = list(self.live)
            self.turns += 1
            for h in live:
                k = min(self.per_turn, h["left"])
                h["q"].append(("tokens", np.zeros(k, np.int32)))
                h["left"] -= k
                self.count += k
                if h["left"] == 0:
                    h["q"].append(("end", None))
                    with self.lock:
                        self.live.remove(h)


def _drive(server, n_req, out_len, gap_s):
    import contextlib
    import time

    run = loadgen.LoadRun(server.submit, server.drain,
                          lambda: server.count,
                          lambda name: contextlib.nullcontext())
    sched = {"due_s": np.arange(n_req) * gap_s,
             "prompt_len": [4] * n_req, "output_len": [out_len] * n_req,
             "prompts": [np.zeros(4, np.int32)] * n_req}
    server.thread.start()
    t0 = time.perf_counter()
    run.start_open_loop(sched, t0)
    deadline = t0 + 20.0
    while time.perf_counter() < deadline and not (
            len(run.records) == n_req
            and all(r.end_t is not None for r in run.records)):
        time.sleep(0.02)
    t1 = time.perf_counter()
    run.stop()
    server.stop.set()
    server.thread.join(5.0)
    return run, t0, t1


def test_fast_server_is_stamped_truthfully():
    """Turns 5 ms apart (the sweep used to wait for 30 ms of quiet, which
    never came: every token of a request got one early stamp and the gap
    between tokens read 0)."""
    server = FakeServer(turn_s=0.005, per_turn=2)
    run, t0, t1 = _drive(server, n_req=60, out_len=120, gap_s=0.01)
    # as in a cell, the window opens once traffic has run for a while:
    # the collector learns the server's turn time from its first turns
    w0 = t0 + 0.2
    s = loadgen.summarize(run.records, run.token_events, w0, t1)
    assert s["attempted"] >= 40 and s["failed"] == 0
    # 2 tokens every 5 ms: 2.5 ms a token, give or take the poll
    assert 2.2 <= np.median(s["tpot_ms"]) <= 3.2, np.median(s["tpot_ms"])
    assert min(s["ttft_ms"]) >= 0.0
    faults = loadgen.stamp_faults(run.records, run.sweeps, w0, t1)
    assert faults["sweeps"] > 0.5 * (t1 - w0) / 0.005
    assert faults["ok"], faults


def test_server_too_fast_for_the_collector_fails_the_run():
    """Turns 0.4 ms apart: the counter never rests for a poll, so the
    sweeps run many turns late.  The stamps are then wrong, and the run
    says so instead of reading fast."""
    server = FakeServer(turn_s=0.0004, per_turn=1)
    run, t0, t1 = _drive(server, n_req=20, out_len=1500, gap_s=0.005)
    faults = loadgen.stamp_faults(run.records, run.sweeps, t0, t1)
    assert faults["merged_sweeps"] > 0 and not faults["ok"], faults


def test_stamp_faults_names_an_early_first_token():
    r = _rec(0, due=1.0, sent=1.2, first=1.1, last=2.0, n=5, end=2.0)
    f = loadgen.stamp_faults([r], [(1.1, False), (2.0, False)], 0.0, 5.0)
    assert f["first_token_before_sent"] == 1 and not f["ok"]
    ok = _rec(1, due=1.0, sent=1.0, first=1.1, last=2.0, n=5, end=2.0)
    assert loadgen.stamp_faults([ok], [(1.1, False)] * 99 + [(2.0, True)],
                                0.0, 5.0)["ok"]
    assert not loadgen.stamp_faults(
        [ok], [(1.1, False)] * 9 + [(2.0, True)] * 2, 0.0, 5.0)["ok"]
