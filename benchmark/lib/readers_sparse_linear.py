"""Readers of the per-layer metrics the ``minicpm_sala`` cells add
(``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without the lightning and block-sparse layers, as
every commit before PR 31 is.

The device trace names an operation by its HLO text, which carries
shapes and not ``jax.named_scope`` names (seen in the recorded fixture
and on the chip, PR 31), so a layer's instructions are found as
``ssm_update_time_share.serve`` finds its own: by the scope's name where
a trace does carry it, else by the shapes only that layer's tensors
have — the family lists them in ``counters`` from the pool's own sizes.
"""
from __future__ import annotations


def _needle(shape):
    return "[%s]" % ",".join(str(int(d)) for d in shape)


def _seconds(trace, counters, scopes_key, shapes_key):
    scopes = counters.get(scopes_key) or []
    needles = [_needle(s) for s in counters.get(shapes_key) or []]
    if trace is None or not trace.busy_s or not (scopes or needles):
        return None
    secs = trace.seconds_of_instructions(
        lambda text: any(s in text for s in scopes)
        or any(n in text for n in needles))
    return secs or None


def _steps_traced(trace, counters):
    main = trace.main_module()
    if main is None:
        return None
    _, one_run_s, _ = main
    return (sum(trace.modules[main[0]]) / one_run_s
            * counters["steps_per_dispatch"])


def _share(trace, counters, scopes_key, shapes_key):
    secs = _seconds(trace, counters, scopes_key, shapes_key)
    if secs is None:
        return None
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)


def _roofline(trace, counters, cell, scopes_key, shapes_key, bytes_key):
    secs = _seconds(trace, counters, scopes_key, shapes_key)
    if secs is None or not cell.get("peaks") or not counters.get(bytes_key):
        return None
    steps = _steps_traced(trace, counters)
    if not steps:
        return None
    least = counters[bytes_key] * steps / cell["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs


def sparse_attention_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the block-sparse layers'
    select-and-attend (the K/V append and the compressed-key update
    among it)."""
    return _share(trace, counters, "sparse_scopes", "sparse_shapes")


def sparse_attention_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the select-and-attend of the
    steps traced (``costs_sparse_linear.sparse_min_bytes`` over the HBM
    bandwidth) over the device time it took."""
    return _roofline(trace, counters, cell, "sparse_scopes", "sparse_shapes",
                     "sparse_min_bytes")


def linear_state_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the lightning state update."""
    return _share(trace, counters, "linear_state_scopes",
                  "linear_state_shapes")


def linear_state_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the state updates traced (each
    stepped row's state read and written once, plus its inputs) over the
    device time they took."""
    return _roofline(trace, counters, cell, "linear_state_scopes",
                     "linear_state_shapes", "linear_state_min_bytes")


def sparse_read_share(trace, spans, counters, cell):
    """K/V positions the sparse layers' decode reads were told to read
    over the positions live for them, from the program's two counters:
    how sparse the traffic makes the layer."""
    read, live = counters.get("sparse_positions_read"), counters.get(
        "sparse_positions_live")
    if not read or not live:
        return None
    return 100.0 * read / live
