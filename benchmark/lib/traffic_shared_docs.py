"""Traffic kind ``closed_loop_shared_docs``: many short questions
against a fixed corpus of long documents (``benchmark/lib/traffic``'s
steadiness rule kept: the seed never changes the WORK).

The mix fixes the corpus (``documents``: a multiset of lengths) and a
population of ``population`` requests, each one document + a question +
an answer length: every document ``population / len(documents)`` times,
question and answer lengths the mid-quantiles of their clipped
log-normals, paired by permutations fixed in the mix (``pairing_seed``).
A seed draws the documents' and the questions' token ids and shuffles
the order inside each pass over the population.  The first ``clients``
requests have their answers cut by a fixed stagger, so the pool starts
out of phase, as a long-running job is.
"""
from __future__ import annotations

import numpy as np

from benchmark.lib import traffic


def population(mix: dict) -> np.ndarray:
    """The fixed ``[P, 3]`` multiset of (document index, question
    length, answer length)."""
    n, docs = int(mix["population"]), list(mix["documents"])
    if n % len(docs):
        raise ValueError("population must be a multiple of the corpus size")
    pair = np.random.RandomState(int(mix.get("pairing_seed", 0)))
    which = np.repeat(np.arange(len(docs)), n // len(docs))
    quest = traffic.quantile_lengths(mix["question"], n)[pair.permutation(n)]
    out = traffic.quantile_lengths(mix["output"], n)[pair.permutation(n)]
    room = int(mix["max_total"]) - np.asarray(docs)[which] - quest
    return np.stack([which, quest, np.maximum(1, np.minimum(out, room))],
                    axis=1)


class SharedDocsSource:
    """The requests of the closed loop in the order the clients draw
    them (the interface of ``traffic.ClosedLoopSource``), plus the corpus
    itself for the pilots."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self._pop = population(mix)
        self._clients = int(mix["clients"])
        self._vocab = int(vocab)
        self._order = traffic.rng_for(seed, "order")
        self._tokens = traffic.rng_for(seed, "tokens")
        corpus = traffic.rng_for(seed, "corpus")
        self.documents = [corpus.randint(0, vocab, int(n)).astype(np.int32)
                          for n in mix["documents"]]
        self.prompt_len, self.output_len, self.prompts = [], [], []
        self.doc_of = []

    def question(self, n: int) -> np.ndarray:
        return self._tokens.randint(0, self._vocab, int(n)).astype(np.int32)

    def _next_pass(self):
        c = self._clients
        for doc, q, o in self._pop[self._order.permutation(len(self._pop))]:
            k = len(self.prompts)
            if k < c:
                o = max(1, int(o * ((k + 0.5) / c)))
            prompt = np.concatenate([self.documents[doc], self.question(q)])
            self.prompt_len.append(len(prompt))
            self.output_len.append(int(o))
            self.prompts.append(prompt)
            self.doc_of.append(int(doc))

    def request(self, k: int):
        """(prompt length, output length, prompt ids) of request ``k``."""
        while k >= len(self.prompts):
            self._next_pass()
        return self.prompt_len[k], self.output_len[k], self.prompts[k]
