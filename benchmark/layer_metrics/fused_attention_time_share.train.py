"""Per-layer metric ``fused_attention_time_share.train``: share of the
device's busy time spent in the ``fused_attention`` op's own kernels,
found by kernel name — the Pallas calls ``fused_attention_fwd`` and
``fused_attention_bwd`` of ``paddle_tpu/fused_attention.py``, which
appear in the trace as instructions of that name, as
``ragged_decode_attention`` does in the serving cells.

It exists beside ``attention_time_share.train`` because that metric
finds attention by the score shape ``[B, heads, S, S]`` in an
instruction's text, and reads 0 once no such tensor exists.  This one
reads None where no such kernel ran: a program before PR 28, or an op
whose rule chose its XLA form (whose instructions carry no name of
their own).  Not counted: the small XLA fusion that sums ``dO * O``
into the backward kernel's ``delta`` input.
"""
import re

_KERNEL = re.compile(r"^%?fused_attention_(fwd|bwd)\b")


def read(trace, spans, counters, cell):
    if trace is None or not trace.busy_s:
        return None
    secs = trace.seconds_of_instructions(
        lambda text: _KERNEL.match(text) is not None)
    if not secs:
        return None
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)
