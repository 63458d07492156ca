"""Readers of the per-layer metrics the ``deepseek_v3_2`` cell adds
(``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without latent leaves and a learned selection, as
every commit before PR 54 is.

The device trace names an operation by its HLO text (shapes; not
``jax.named_scope`` names), so a stage's work is found by the shapes
only ITS tensors have — needles of two or more dims, listed by the
family in ``counters`` from the pool's own sizes (README_deepseek_v3_2.md
lists what each matched on the chip).  The three stages are told apart
in order: an instruction that bears a SCORE shape (the ``[slots, index
heads, rung]`` products, the index-key leaf) is the scoring's whatever
else it bears; of the rest, one that bears an ATTEND shape (the latent
leaf, the gathered rows, the ``[slots, heads, top-k]`` scores) is the
read's; of the rest, one that bears a SELECT shape (the ``[slots,
rung]`` scores and the ``[slots, top-k]`` lists: the sort) is the
selection's.
"""
from __future__ import annotations

from benchmark.lib.readers_sparse_linear import _needle, _steps_traced

_STAGES = ("index_score_shapes", "latent_attend_shapes",
           "index_select_shapes")


def _stage_seconds(trace, counters, stage):
    """Device seconds of the instructions that are ``stage``'s by the
    order above; None where there is nothing to look for or to read."""
    if trace is None or not trace.busy_s or not counters.get(stage):
        return None
    needles = {k: [_needle(s) for s in counters.get(k) or []]
               for k in _STAGES}
    earlier = [n for k in _STAGES[:_STAGES.index(stage)]
               for n in needles[k]]
    mine = needles[stage]
    secs = trace.seconds_of_instructions(
        lambda text: any(n in text for n in mine)
        and not any(n in text for n in earlier))
    return secs or None


def _share(trace, counters, stage):
    secs = _stage_seconds(trace, counters, stage)
    if secs is None:
        return None
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)


def index_score_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the indexer's scoring: the
    products of the fresh index queries with the slot's index keys, the
    ReLU and the weighed sum over the index heads (the index key's
    append among it)."""
    return _share(trace, counters, "index_score_shapes")


def index_select_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the selection: the mask and
    the top-k over the rung's scores."""
    return _share(trace, counters, "index_select_shapes")


def latent_attention_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the latent row's append, the
    gather of the selected rows and the absorbed attend over them."""
    return _share(trace, counters, "latent_attend_shapes")


def latent_attention_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the scoring and the selected
    read of the steps traced (``costs_latent_sparse.
    latent_attention_min_bytes``: live index keys + selected rows + the
    appends, over the HBM bandwidth) over the device time the three
    stages took — the same count whatever implements them."""
    secs = [_stage_seconds(trace, counters, k) for k in _STAGES]
    if (secs[0] is None or secs[1] is None or not cell.get("peaks")
            or not counters.get("latent_attention_min_bytes")):
        return None
    steps = _steps_traced(trace, counters)
    if not steps:
        return None
    least = (counters["latent_attention_min_bytes"] * steps
             / cell["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / sum(s or 0.0 for s in secs)


def index_selected_share(trace, spans, counters, cell):
    """Latent positions the steps' reads were told to read over the
    positions scored (live) for them, from the program's two counters:
    how sparse the traffic makes the read."""
    sel, scored = counters.get("latent_positions_selected"), counters.get(
        "index_positions_scored")
    if not sel or not scored:
        return None
    return 100.0 * sel / scored
