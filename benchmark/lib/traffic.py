"""The one general traffic generator: a traffic mix is a data file under
``benchmark/traffic/``; this module turns it and ``--seed`` into inputs.

Steadiness rule (PERF.md section 2): the seed never changes the WORK.  A
serving mix enumerates a fixed multiset of (prompt, output) lengths from
the quantiles of its clipped log-normals; a seed only shuffles the order
inside each pass over that population, draws the token ids and orders
the gaps between arrivals (themselves a fixed multiset).  A training mix fixes the shapes; a seed draws the ids.

Kinds of mix (``"kind"`` in the file):

``train_chunks``  ``seq_len``, ``batch``, ``steps_per_chunk``,
                  ``masks_per_seq``: stacked per-step batches for
                  ``Executor.run(steps=, per_step_feed=True)``.
``open_loop``     arrivals at ``rate_per_s`` with exponential gaps (see
                  ``arrival_due_times``) over ``ramp_s`` + window,
                  lengths from ``prompt`` / ``output``.
``closed_loop``   ``clients`` submitters, each sending its next request
                  when the last finished; same length rule, requests
                  made pass by pass as they are drawn.

A length distribution is ``{"median":, "sigma":, "min":, "max":}``: a
log-normal clipped to [min, max].  ``population`` is the multiset's size
and ``max_total`` clips prompt + output (the output gives way).
"""
from __future__ import annotations

import json
import math
import os
import statistics

import numpy as np

from benchmark.lib.harness import BENCH


# seeds reach a little over 2**31: fold to what RandomState takes
_SEED_MOD = 2 ** 32


def load_mix(name: str, rehearse: bool = False) -> dict:
    """Read ``benchmark/traffic/<name>.json``; with ``rehearse`` the
    file's ``"rehearse"`` group overrides the real parameters."""
    path = os.path.join(BENCH, "traffic", name + ".json")
    with open(path) as f:
        mix = json.load(f)
    tiny = mix.pop("rehearse", {})
    if rehearse:
        mix.update(tiny)
    mix["name"] = name
    return mix


def rng_for(seed: int, stream: str) -> np.random.RandomState:
    """Independent stream per purpose, so adding a draw to one never
    shifts another."""
    h = sum((i + 1) * b for i, b in enumerate(stream.encode())) % 9973
    return np.random.RandomState((int(seed) * 10007 + h) % _SEED_MOD)


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of a log-normal
    (median, sigma), clipped to [min, max]: the same multiset always."""
    mu = math.log(float(dist["median"]))
    sig = float(dist["sigma"])
    inv = statistics.NormalDist().inv_cdf
    out = [int(round(math.exp(mu + sig * inv((i + 0.5) / n))))
           for i in range(n)]
    return np.clip(np.asarray(out, np.int64), int(dist["min"]),
                   int(dist["max"]))


def population(mix: dict) -> np.ndarray:
    """The fixed [P, 2] multiset of (prompt, output) lengths.  Prompts
    and outputs are paired by a permutation fixed in the mix (its
    ``pairing_seed``), never by ``--seed``."""
    n = int(mix["population"])
    prompts = quantile_lengths(mix["prompt"], n)
    outputs = quantile_lengths(mix["output"], n)
    pair = np.random.RandomState(int(mix.get("pairing_seed", 0)))
    outputs = outputs[pair.permutation(n)]
    room = int(mix["max_total"]) - prompts
    outputs = np.maximum(1, np.minimum(outputs, room))
    return np.stack([prompts, outputs], axis=1)


def request_lengths(mix: dict, seed: int, n: int) -> np.ndarray:
    """[n, 2] lengths: whole passes over the population, each pass in a
    seeded order — every block of ``population`` requests is the same
    multiset whatever the seed."""
    pop = population(mix)
    rng = rng_for(seed, "order")
    passes = [pop[rng.permutation(len(pop))]
              for _ in range(-(-n // len(pop)))]
    return np.concatenate(passes)[:n]


def arrival_due_times(mix: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Due times (seconds from the start of the ramp) of an open loop at
    ``rate_per_s`` up to ``horizon_s``.  The gaps between arrivals are
    exponential, as a Poisson process's are, but ENUMERATED: one pass is
    the ``population`` mid-quantiles of the exponential distribution,
    scaled to sum to population / rate, in a seeded order.  So every
    pass brings the same arrivals in the same time whatever the seed
    (a drawn Poisson count over 40 s moves by 2.7%, and the server's
    step time moves with it), while the bursts and lulls inside a pass
    still fall differently for each seed."""
    n, rate = int(mix["population"]), float(mix["rate_per_s"])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= (n / rate) / gaps.sum()
    rng = rng_for(seed, "arrivals")
    passes = int(horizon_s * rate / n) + 2
    t = np.cumsum(np.concatenate(
        [gaps[rng.permutation(n)] for _ in range(passes)]))
    return t[t < horizon_s]


def prompt_tokens(lengths: np.ndarray, vocab: int, seed: int) -> list:
    """One int32 id array per request, ids uniform in [0, vocab)."""
    rng = rng_for(seed, "tokens")
    return [rng.randint(0, vocab, int(n)).astype(np.int32)
            for n in lengths]


def open_loop_schedule(mix: dict, seed: int, seconds: float,
                       vocab: int) -> dict:
    """Everything an open-loop run sends, decided before it starts."""
    horizon = float(mix["ramp_s"]) + float(seconds)
    due = arrival_due_times(mix, seed, horizon)
    lens = request_lengths(mix, seed, len(due))
    return {"due_s": due, "prompt_len": lens[:, 0], "output_len": lens[:, 1],
            "prompts": prompt_tokens(lens[:, 0], vocab, seed)}


class ClosedLoopSource:
    """The requests of a closed loop in the order the clients will draw
    them, made one pass over the population at a time as they are asked
    for (a faster server draws more of them).  The first ``clients``
    are cut short by a fixed stagger (client i gets (i + 0.5) / clients
    of its lengths) so the pool starts out of phase, as a long-running
    job is, instead of with every slot at position 0."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self._pop = population(mix)
        self._clients = int(mix["clients"])
        self._vocab = int(vocab)
        self._order = rng_for(seed, "order")
        self._tokens = rng_for(seed, "tokens")
        self.prompt_len, self.output_len, self.prompts = [], [], []

    def _next_pass(self):
        c = self._clients
        for p, o in self._pop[self._order.permutation(len(self._pop))]:
            k = len(self.prompts)
            if k < c:
                p, o = (max(1, int(n * ((k + 0.5) / c))) for n in (p, o))
            self.prompt_len.append(int(p))
            self.output_len.append(int(o))
            self.prompts.append(self._tokens.randint(
                0, self._vocab, int(p)).astype(np.int32))

    def request(self, k: int):
        """(prompt length, output length, prompt ids) of request ``k``."""
        while k >= len(self.prompts):
            self._next_pass()
        return self.prompt_len[k], self.output_len[k], self.prompts[k]


def train_batches(mix: dict, seed: int, vocab: int) -> dict:
    """``steps_per_chunk`` distinct BERT pretraining batches stacked on a
    leading axis (the shapes ``models.bert_pretrain`` feeds on): uniform
    token ids, ``masks_per_seq`` masked positions per sequence, no padding
    unless the mix has ``min_len_share`` (lengths uniform from that share
    of ``seq_len`` up to it)."""
    n, b, s = (int(mix[k]) for k in ("steps_per_chunk", "batch", "seq_len"))
    m = int(mix["masks_per_seq"])
    rng = rng_for(seed, "train")
    mask = np.ones((n, b, s), np.float32)
    if "min_len_share" in mix:
        # sequences of unequal length: the tail is padding (mask 0)
        lens = rng_for(seed, "lengths").randint(
            int(float(mix["min_len_share"]) * s), s + 1, (n, b))
        mask = (np.arange(s)[None, None, :] < lens[:, :, None]).astype(
            np.float32)
    return {
        "src": rng.randint(0, vocab, (n, b, s)).astype(np.int32),
        "sent": rng.randint(0, 2, (n, b, s)).astype(np.int32),
        "mask": mask,
        # flattened positions into [batch * seq_len]
        "mpos": (np.arange(b)[None, :, None] * s
                 + rng.randint(0, s, (n, b, m))
                 ).reshape(n, -1, 1).astype(np.int32),
        "mlab": rng.randint(0, vocab, (n, b * m, 1)).astype(np.int32),
        "nlab": rng.randint(0, 2, (n, b, 1)).astype(np.int32),
    }


def length_summary(lengths) -> dict:
    a = np.asarray(lengths)
    if a.size == 0:
        return {"n": 0}
    q = np.percentile(a, [50, 95])
    return {"n": int(a.size), "mean": float(a.mean()), "p50": float(q[0]),
            "p95": float(q[1]), "max": int(a.max())}
