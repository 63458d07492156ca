"""Drives a streaming token server from ONE process with two threads,
and stamps every request at the submitter.

* a generator thread walks a schedule decided before the run
  (``traffic.open_loop_schedule``) and submits each request when it is
  DUE; how late it ran is recorded per request (``sent - due``), so a
  starved generator is not read as a fast server;
* a collector thread polls one cheap counter (tokens the server has
  produced) every millisecond.  The poll that first sees it move takes
  the stamp for everything the server delivers in that turn (one clock
  read, no Python thread per request); the sweep that gathers the
  chunks from every request in flight runs once the counter has stood
  still for ``SWEEP_DELAY_S``: by then the server's scheduler has
  dispatched its next step and waits on the device (a sweep right
  behind the delivery held the interpreter lock while the scheduler was
  admitting: a 3 ms stall in every tick, seen in the trace).  Against a
  server whose turns come faster than that, a sweep finds two turns'
  tokens at once (below); when it does, the collector cuts the wait to
  a fifth of a turn as that sweep saw it (at most half of what it was,
  at least one poll), so from its second sweep on it follows such a
  server a millisecond or two behind each turn.  In a closed loop the
  collector also sends a client's next request when it finds its last
  one ended.

A stamp is only as good as the sweep is prompt.  ``drain`` yields at
most one ``tokens`` chunk per request per server turn, so a sweep that
finds two for one request ran a whole turn late and stamped the second
turn's tokens with the first one's time.  Such sweeps are counted
(``merged_sweeps`` of ``sweeps``), as are first tokens stamped before
their request was sent; ``stamp_faults`` reduces both, over the window,
to a verdict that a family puts into ``correct``, so a server too fast
even for a wait of one poll fails the run instead of reading faster
than it is.

The server is reached through three callables the family supplies, so
this file knows nothing of the program under test:

``submit(prompt, max_new) -> handle``   raises if the server refuses
``drain(handle) -> [(kind, value)]``    kind in tokens / end / err,
                                        never blocks
``produced() -> number``                monotonic, moves when tokens do
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

POLL_S = 0.001          # collector poll of the produced() counter
SWEEP_DELAY_S = 0.03    # from the counter standing still to the sweep, at
                        # first: cut whenever a sweep ran a turn late, to
SWEEP_SHARE = 0.2       # ... this share of a turn as that sweep saw it
IDLE_SWEEP_S = 0.25     # sweep anyway this often (failures push nothing,
                        # and a counter that never rests must not starve it)
MERGED_SHARE_MAX = 0.01  # of the window's sweeps may have run a turn late


class Rec:
    """One request as the submitter saw it (perf_counter seconds)."""

    __slots__ = ("idx", "client", "due", "sent", "prompt_len", "output_len",
                 "first_t", "last_t", "n_tok", "max_gap", "end_t", "status",
                 "handle", "keep", "tokens")

    def __init__(self, idx, due, prompt_len, output_len, client=-1,
                 keep=False):
        self.idx, self.client, self.due = idx, client, due
        self.prompt_len, self.output_len = int(prompt_len), int(output_len)
        self.sent = self.first_t = self.last_t = self.end_t = None
        self.n_tok, self.max_gap = 0, 0.0
        self.status = "new"  # sent / done / failed / refused
        self.handle, self.keep, self.tokens = None, keep, []


class LoadRun:
    def __init__(self, submit, drain, produced, annotate):
        self._submit, self._drain, self._produced = submit, drain, produced
        self._annotate = annotate  # context manager factory: name -> cm
        self.records = []          # every Rec ever sent, in send order
        self.token_events = []     # (sweep time, tokens delivered then)
        self.sweeps = []           # (stamp, ran a whole turn late?)
        self.delay_s = SWEEP_DELAY_S  # counter at rest -> sweep; adapts
        self._inbox = collections.deque()
        self._active = []
        self._stop = threading.Event()
        self._threads = []
        self._next = None          # closed loop: () -> Rec or None
        self.errors = []

    # -- sending ---------------------------------------------------------
    def _send(self, rec, prompt):
        rec.sent = time.perf_counter()
        try:
            with self._annotate("bench/submit"):
                rec.handle = self._submit(prompt, rec.output_len)
            rec.status = "sent"
        except Exception as exc:  # refused at the door: a failed request
            rec.status, rec.end_t = "refused", rec.sent
            rec.tokens = repr(exc)
        self.records.append(rec)
        if rec.status == "sent":
            self._inbox.append(rec)

    def start_open_loop(self, schedule, t0, keep_first=0, keep_max_total=0):
        """Submit ``schedule`` (see traffic.open_loop_schedule) on its due
        times counted from ``t0``; the first ``keep_first`` requests no
        longer than ``keep_max_total`` keep their tokens for the check."""
        def gen():
            kept = 0
            for i, due_s in enumerate(schedule["due_s"]):
                due = t0 + float(due_s)
                while not self._stop.is_set():
                    wait = due - time.perf_counter()
                    if wait <= 0:
                        break
                    time.sleep(min(wait, 0.05))
                if self._stop.is_set():
                    return
                p, o = schedule["prompt_len"][i], schedule["output_len"][i]
                keep = kept < keep_first and p + o <= keep_max_total
                kept += int(keep)
                self._send(Rec(i, due, p, o, keep=keep),
                           schedule["prompts"][i])
        self._spawn(gen, "bench-generator")
        self._spawn(self._collect, "bench-collector")

    def start_closed_loop(self, source, clients, keep_first=0,
                          keep_max_total=0):
        """``clients`` submitters; request k of ``source`` (see
        traffic.ClosedLoopSource) goes to whichever client frees up
        k-th.  The source makes its requests pass by pass as they are
        asked for, so a faster server never runs out of them."""
        state = {"k": 0, "kept": 0}

        def next_rec(client):
            k = state["k"]
            state["k"] = k + 1
            p, o, prompt = source.request(k)
            # the staggered first wave is cut short: never a check sample
            keep = (k >= clients and state["kept"] < keep_first
                    and p + o <= keep_max_total)
            state["kept"] += int(keep)
            now = time.perf_counter()
            self._send(Rec(k, now, p, o, client=client, keep=keep), prompt)

        self._next = next_rec
        for c in range(clients):
            next_rec(c)
        self._spawn(self._collect, "bench-collector")

    def _spawn(self, fn, name):
        def guarded():
            try:
                fn()
            except BaseException as exc:  # surfaced by stop()
                self.errors.append(exc)
        t = threading.Thread(target=guarded, name=name, daemon=True)
        self._threads.append(t)
        t.start()

    # -- collecting ------------------------------------------------------
    def _collect(self):
        seen = self._produced()
        stamp = settled = None  # when the counter moved / stood still
        last_sweep = time.perf_counter()
        while not self._stop.is_set():
            time.sleep(POLL_S)
            v = self._produced()
            now = time.perf_counter()
            if v != seen:
                seen, settled = v, None
                if stamp is None:
                    stamp = now
                if now - stamp < IDLE_SWEEP_S:
                    continue
            elif stamp is not None and settled is None:
                settled = now
            due = stamp is not None and (
                now - stamp >= IDLE_SWEEP_S
                or (settled is not None and now - settled >= self.delay_s))
            if due or (stamp is None and now - last_sweep >= IDLE_SWEEP_S):
                with self._annotate("bench/collect"):
                    turns = self._sweep(stamp if due else now)
                if turns > 1:
                    # whole turns late: next time wait a fifth of a turn,
                    # as this sweep saw them, and at most half as long
                    self.delay_s = max(POLL_S, min(
                        self.delay_s / 2.0,
                        SWEEP_SHARE * (now - last_sweep) / turns))
                stamp, settled, last_sweep = None, None, now

    def _sweep(self, now):
        while self._inbox:
            self._active.append(self._inbox.popleft())
        delivered, still, turns = 0, [], 0
        for rec in self._active:
            chunks = 0
            for kind, val in self._drain(rec.handle):
                if kind == "tokens":
                    chunks += 1
                    if rec.first_t is None:
                        rec.first_t = now
                    else:
                        rec.max_gap = max(rec.max_gap, now - rec.last_t)
                    rec.last_t = now
                    rec.n_tok += len(val)
                    delivered += len(val)
                    if rec.keep:
                        rec.tokens.append(np.asarray(val))
                else:
                    rec.end_t = now
                    rec.status = "done" if kind == "end" else "failed"
                    if kind != "end":
                        rec.tokens = repr(val)
            turns = max(turns, chunks)
            if rec.end_t is None:
                still.append(rec)
            elif self._next is not None and not self._stop.is_set():
                self._next(rec.client)
        self._active = still
        self.sweeps.append((now, turns > 1))
        if delivered:
            self.token_events.append((now, delivered))
        return turns

    def halt(self):
        """Ask the threads to end; ``stop`` also waits and re-raises."""
        self._stop.set()

    def stop(self):
        self.halt()
        for t in self._threads:
            t.join(timeout=10.0)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError("load threads did not stop: %s" % alive)
        if self.errors:
            raise self.errors[0]


def stamp_faults(records, sweeps, w0, w1) -> dict:
    """Whether the window's stamps can be trusted (see the module's
    docstring): ``ok`` is False when more than ``MERGED_SHARE_MAX`` of
    the window's sweeps (and more than one) ran a whole server turn
    late, or when any first token in the window is stamped before its
    request was sent."""
    inw = [late for t, late in sweeps if w0 <= t < w1]
    merged = sum(inw)
    early = sum(1 for r in records if r.first_t is not None
                and w0 <= r.first_t < w1 and r.first_t < r.sent)
    return {"sweeps": len(inw), "merged_sweeps": merged,
            "first_token_before_sent": early,
            "ok": bool(inw) and early == 0
            and merged <= max(1, MERGED_SHARE_MAX * len(inw))}


def summarize(records, token_events, w0, w1, limits=None) -> dict:
    """Reduce submitter-side stamps to the window [w0, w1).  A sample
    counts when its DEFINING event falls in the window: the first token
    for time-to-first-token, the last for the gap between tokens, the
    send for generator lateness.  A request that reached no end inside
    the window is neither attempted nor failed; it is counted apart.
    ``limits`` (``ttft_base_ms``, ``ttft_ms_per_prompt_token``,
    ``tpot_ms``): the share of ended requests that met both is
    ``attainment``; a failed request misses."""
    inw = lambda t: t is not None and w0 <= t < w1
    ended = [r for r in records if inw(r.end_t)]
    done = [r for r in ended if r.status == "done"
            and r.n_tok == r.output_len]
    ttft = [(r.first_t - r.due) * 1e3 for r in records if inw(r.first_t)]
    tpot = [(r.last_t - r.first_t) * 1e3 / (r.n_tok - 1)
            for r in done if r.n_tok > 1]
    late = [(r.sent - r.due) * 1e3 for r in records if inw(r.sent)]
    tokens = sum(n for t, n in token_events if w0 <= t < w1)
    total = [r.prompt_len + r.n_tok for r in done]
    out = {
        "window_s": w1 - w0,
        "attempted": len(ended),
        "failed": len(ended) - len(done),
        "sent_in_window": len(late),
        "in_flight_at_close": sum(
            1 for r in records if r.sent is not None and r.sent < w1
            and (r.end_t is None or r.end_t >= w1)),
        "tokens_delivered": int(tokens),
        "ttft_ms": ttft, "tpot_ms": tpot, "gen_late_ms": late,
        "stall_ms": [r.max_gap * 1e3 for r in done],
        # sum over finished requests of the positions each held, step by
        # step: L (L + 1) / 2 for a request that ended L positions long
        "position_steps": float(sum(n * (n + 1) / 2.0 for n in total)),
        "row_steps": float(sum(total)),
        "prompt_len_done": [r.prompt_len for r in done],
        "output_len_done": [r.n_tok for r in done],
    }
    if limits:
        met = sum(
            1 for r in done if r.n_tok > 1
            and (r.first_t - r.due) * 1e3 <= (
                limits["ttft_base_ms"]
                + limits["ttft_ms_per_prompt_token"] * r.prompt_len)
            and (r.last_t - r.first_t) * 1e3 / (r.n_tok - 1)
            <= limits["tpot_ms"])
        out["attainment"] = met / len(ended) if ended else None
    return out
