"""Reader of the per-layer metric the ``kimi_linear_48b_a3b`` cell adds
(``read(trace, spans, counters, cell)``, as ``lib/readers``): it returns
None where the program or the counters hold nothing to read — a program
whose delta-rule builder has no chunked prefill, as every commit before
PR 63 is, or a family that prefills no documents in set-up.
"""
from __future__ import annotations


def doc_prefill_tokens_per_s(trace, spans, counters, cell):
    """Prompt tokens the server's own counter
    (``serving_decode_prefill_tokens_total``) booked while the pilots
    prefilled the documents, over that phase's seconds on the host's
    clock LESS the pool's birth (the server makes its pool at its first
    admission, inside the phase: ``serving_pool_state_seconds_total``
    over the phase, which is ``setup_pool_state_s``'s series): the one
    place the delta rule's chunkwise form and the expanded latent prefill
    are timed.  A turn of the phase is one prefill chunk, and the step's
    chunk behind it only while an earlier pilot answers (a few turns a
    document); each chunk is dispatched and waited for in turn, so the
    host's share of a turn is in the rate."""
    tokens = counters.get("doc_prefill_tokens")
    seconds = counters.get("doc_prefill_seconds")
    if not tokens or not seconds:
        return None
    return tokens / seconds
