"""Bytes a pooled decode step of the gated delta-rule hybrid decoder
(``olmo_hybrid``: linear layers that keep a ``[heads, dk, dv]`` state a
slot, full multi-head attention layers that keep K/V, a SwiGLU after
each) NEEDS, from the configuration's sizes alone: the numerators of
``decode_step_roofline.offline`` and ``delta_state_roofline.serve`` in
the ``olmo_hybrid_7b`` cell, and the pool's bytes the family holds the
program's gauges to.  What the algorithm requires, not what the program
happens to move: a byte read twice counts once, a K/V row the mask hides
is not read, and nothing here knows how a leaf is tiled or which
implementation runs the step.
"""
from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "vocab_size",
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim")}
    s["head_dim"] = int(cfg.get("head_dim")
                        or s["hidden_size"] // s["num_attention_heads"])
    kinds = list(cfg["layer_types"])
    s["linear_layers"], s["full_layers"] = kinds.count(LINEAR), kinds.count(
        FULL)
    s["d_key"] = s["linear_num_value_heads"] * s["linear_key_head_dim"]
    s["d_value"] = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    s["d_qkv"] = 2 * s["d_key"] + s["d_value"]
    return s


def layer_parameters(cfg: dict, kind: str) -> dict:
    """Parameters of ONE layer of ``kind``, by part; ``float32`` counts
    those of them kept in float32 (norms, the conv kernel, ``A_log``,
    ``dt_bias``), the rest are bf16."""
    s = _sizes(cfg)
    d, h = s["hidden_size"], s["linear_num_value_heads"]
    if kind == LINEAR:
        small = (s["linear_conv_kernel_dim"] * s["d_qkv"] + 2 * h
                 + s["linear_value_head_dim"])
        mixer = d * (2 * s["d_key"] + 3 * s["d_value"] + 2 * h) + small
    else:
        nq = s["num_attention_heads"] * s["head_dim"]
        nkv = s["num_key_value_heads"] * s["head_dim"]
        small = nq + nkv
        mixer = 2 * d * nq + 2 * d * nkv + small
    return {"mixer": mixer, "mlp": 3 * d * s["intermediate_size"],
            "norms": 2 * d, "float32": small + 2 * d}


def parameters(cfg: dict) -> dict:
    """Parameters by part over the whole cut (the head is untied)."""
    s = _sizes(cfg)
    lin, full = layer_parameters(cfg, LINEAR), layer_parameters(cfg, FULL)
    body = lambda p: p["mixer"] + p["mlp"] + p["norms"]       # noqa: E731
    return {"linear_layers": s["linear_layers"] * body(lin),
            "full_layers": s["full_layers"] * body(full),
            "final_norm": s["hidden_size"],
            "embedding": s["vocab_size"] * s["hidden_size"],
            "head": s["vocab_size"] * s["hidden_size"],
            "float32": (s["linear_layers"] * lin["float32"]
                        + s["full_layers"] * full["float32"]
                        + s["hidden_size"])}


def weight_bytes_held(cfg: dict) -> int:
    """Bytes of every weight as stored: bf16 matrices, float32 vectors."""
    p = parameters(cfg)
    total = sum(v for k, v in p.items() if k != "float32")
    return 2 * total + 2 * p["float32"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights one decode step must read, as stored: every
    layer, the final norm and the output head once; of the embedding
    only the rows looked up (counted 0: 80 rows of 7.7 KB)."""
    s = _sizes(cfg)
    return weight_bytes_held(cfg) - 2 * s["vocab_size"] * s["hidden_size"]


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V one cached position holds across the full
    layers."""
    s = _sizes(cfg)
    return (2 * s["full_layers"] * s["num_key_value_heads"] * s["head_dim"]
            * bytes_per_value)


def delta_state_bytes_per_slot(cfg: dict, bytes_per_value: int = 4) -> int:
    """Bytes of the delta-rule state ``[heads, dk, dv]`` of one slot
    across the linear layers."""
    s = _sizes(cfg)
    return (s["linear_layers"] * s["linear_num_value_heads"]
            * s["linear_key_head_dim"] * s["linear_value_head_dim"]
            * bytes_per_value)


def conv_state_bytes_per_slot(cfg: dict, bytes_per_value: int = 4) -> int:
    """Bytes of the conv windows (the last ``K - 1`` projected rows
    ``[q; k; v]``) of one slot across the linear layers."""
    s = _sizes(cfg)
    return (s["linear_layers"] * (s["linear_conv_kernel_dim"] - 1)
            * s["d_qkv"] * bytes_per_value)


def recurrent_state_bytes_per_slot(cfg: dict) -> int:
    return delta_state_bytes_per_slot(cfg) + conv_state_bytes_per_slot(cfg)


def pool_bytes(cfg: dict, slots: int, rung: int,
               kv_bytes: int = 2) -> int:
    """Bytes of the whole pool at one rung pair."""
    return int(slots) * (kv_bytes_per_position(cfg, kv_bytes) * int(rung)
                         + recurrent_state_bytes_per_slot(cfg))


def delta_update_min_bytes(cfg: dict, rows_stepped: float) -> float:
    """The least HBM traffic of the delta rule of ONE step over the
    linear layers: each stepped row's state read once and written once,
    plus the rule's inputs (q, k, v and the two gates) and its output o,
    in float32.  The same work whatever implements the step."""
    s = _sizes(cfg)
    io = 4 * (s["d_qkv"] + s["d_value"] + 2 * s["linear_num_value_heads"])
    return float(rows_stepped) * (2 * delta_state_bytes_per_slot(cfg)
                                  + s["linear_layers"] * io)


def step_min_bytes(cfg: dict, live_positions: float, rows_stepped: float,
                   kv_bytes: int = 2) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights as
    stored, the recurrent state of every row that stepped read and
    written, the K/V of every live position read once, one new K/V
    position written per row that stepped.  Bandwidth-bound: at 80 rows
    the step's 2 * params * rows FLOPs are 2.4 ms of the bf16 peak
    against 13 ms+ for the bytes."""
    per_pos = kv_bytes_per_position(cfg, kv_bytes)
    return (weight_bytes(cfg)
            + 2.0 * recurrent_state_bytes_per_slot(cfg) * float(rows_stepped)
            + per_pos * float(live_positions)
            + per_pos * float(rows_stepped))
