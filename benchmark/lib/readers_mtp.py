"""Readers of the per-layer metrics the ``k_exaone_236b_a23b`` cell adds
(``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without a self-drafting round, as every commit
before PR 47 is.

The device trace names an operation by its HLO text (shapes; not
``jax.named_scope`` names), so the shared expert's products are found by
the shapes only ITS tensors have (its two matrices, the gate-and-up rows
of every row computed) — the family lists them in ``counters`` from the
configuration's own sizes.
"""
from __future__ import annotations

from benchmark.lib.readers_sparse_linear import _share


def mtp_accept_rate(trace, spans, counters, cell):
    """Drafted tokens the target accepted over those proposed in the
    window, from the program's two counters."""
    proposed = counters.get("spec_proposed")
    if not proposed:
        return None
    return 100.0 * counters.get("spec_accepted", 0) / proposed


def spec_tokens_per_row_round(trace, spans, counters, cell):
    """Generated tokens over the slots that advanced in a round, summed
    over the window's rounds: 1 + the acceptance rate, less the rounds a
    prompt walked."""
    row_rounds = counters.get("spec_row_rounds")
    if not row_rounds:
        return None
    return counters.get("generated_tokens", 0) / row_rounds


def spec_round_roofline(trace, spans, counters, cell):
    """Least time the chip could take for one round (``costs_mtp.
    round_min_bytes`` over the HBM bandwidth) over the median device time
    of one run of the round's program."""
    if (trace is None or not cell.get("peaks")
            or not counters.get("round_min_bytes")
            or not counters.get("spec_rounds")):
        return None
    main = trace.main_module()
    if main is None:
        return None
    _, one_run_s, _ = main
    least = counters["round_min_bytes"] / cell["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / one_run_s


def shared_expert_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the shared experts' products
    (the ops that bear a shape only their tensors have)."""
    return _share(trace, counters, "shared_expert_scopes",
                  "shared_expert_shapes")
