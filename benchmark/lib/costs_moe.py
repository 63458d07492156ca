"""Bytes a pooled decode step of the routed-experts decoder
(``lfm2_moe``: gated short convolutions beside grouped-query attention,
a dense SwiGLU or a mixture of routed experts after each) NEEDS, from
the configuration's sizes and the step's own counts: the numerators of
``decode_step_roofline.offline`` and ``moe_experts_roofline.serve`` in
the ``lfm2_24b_a2b`` cell.  What the algorithm requires, not what the
program happens to move: a byte read twice counts once, an expert no row
chose is not read at all — and nothing here knows a kernel's tiles, so
the same work reads the same whatever implements it.
"""
from __future__ import annotations

CONV, ATTENTION = "conv", "full_attention"


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "num_dense_layers", "num_attention_heads",
        "num_key_value_heads", "num_experts", "num_experts_per_tok",
        "conv_L_cache", "vocab_size")}
    s["head_dim"] = int(cfg.get("head_dim")
                        or s["hidden_size"] // s["num_attention_heads"])
    s["kinds"] = list(cfg["layer_types"])
    s["expert_layers"] = s["num_hidden_layers"] - s["num_dense_layers"]
    return s


def expert_parameters(cfg: dict) -> int:
    """Parameters of ONE expert: gate, up and down."""
    s = _sizes(cfg)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def parameters(cfg: dict) -> dict:
    """Parameters by part, over the whole cut (the head is the
    embedding: counted once, under ``embedding``)."""
    s = _sizes(cfg)
    d, dh = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"] * dh, s["num_key_value_heads"] * dh
    n_conv, n_attn = s["kinds"].count(CONV), s["kinds"].count(ATTENTION)
    return {
        "conv": n_conv * (3 * d * d + s["conv_L_cache"] * d + d * d),
        "attention": n_attn * (d * nq + 2 * d * nkv + nq * d + 2 * dh),
        "norms": s["num_hidden_layers"] * 2 * d + d,
        "dense_ffn": s["num_dense_layers"] * 3 * d * s["intermediate_size"],
        "routers": s["expert_layers"] * (d * s["num_experts"]
                                         + s["num_experts"]),
        "experts": s["expert_layers"] * s["num_experts"]
        * expert_parameters(cfg),
        "embedding": s["vocab_size"] * d,
    }


def float32_parameters(cfg: dict) -> int:
    """Those of them stored in float32 (norms, conv kernels, routers and
    their biases); the rest are bf16."""
    s = _sizes(cfg)
    p = parameters(cfg)
    return (p["norms"] + p["routers"]
            + s["kinds"].count(ATTENTION) * 2 * s["head_dim"]
            + s["kinds"].count(CONV) * s["conv_L_cache"] * s["hidden_size"])


def weight_bytes_outside_experts(cfg: dict) -> int:
    """Bytes, as stored, of every weight a step reads whoever is routed
    where: all but the experts, the tied matrix once (as the head; of the
    embedding only the rows looked up, counted 0)."""
    p = parameters(cfg)
    return 2 * (sum(p.values()) - p["experts"]) + 2 * float32_parameters(cfg)


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V one cached position holds across the attention
    layers."""
    s = _sizes(cfg)
    return (2 * s["kinds"].count(ATTENTION) * s["num_key_value_heads"]
            * s["head_dim"] * bytes_per_value)


def recurrent_state_bytes_per_slot(cfg: dict, conv_bytes: int = 4) -> int:
    """The conv layers' windows (the last ``conv_L_cache - 1`` inputs of
    the depthwise convolution) of one slot."""
    s = _sizes(cfg)
    return (s["kinds"].count(CONV) * (s["conv_L_cache"] - 1)
            * s["hidden_size"] * conv_bytes)


def expert_stats_bytes(cfg: dict) -> int:
    """The counts the steps keep on the device: ``[expert layers, 4]``
    int32, carried by the pool beside the recurrent leaves."""
    return _sizes(cfg)["expert_layers"] * 4 * 4


def experts_min_bytes(cfg: dict, experts_touched: float,
                      rows_stepped: float) -> float:
    """The least HBM traffic of the experts' products of ONE step over
    all expert layers: the matrices of the experts that got a row, once
    each (``experts_touched`` summed over the layers), plus the (row,
    choice) pairs' inputs in bf16 and outputs in float32."""
    s = _sizes(cfg)
    pairs = float(rows_stepped) * s["num_experts_per_tok"]
    return (float(experts_touched) * expert_parameters(cfg) * 2
            + s["expert_layers"] * pairs * s["hidden_size"] * (2 + 4))


def step_min_bytes(cfg: dict, live_positions: float, rows_stepped: float,
                   experts_touched: float, kv_bytes: int = 2) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights
    outside the experts as stored, the matrices of the experts touched,
    the K/V of every live position read once and one new position
    written per row that stepped, the conv state of every row that
    stepped read and written.  Bandwidth-bound: at 256 rows an expert
    sees 16 rows against the 240 a v5e needs to leave the bandwidth
    roof."""
    per_pos = kv_bytes_per_position(cfg, kv_bytes)
    return (weight_bytes_outside_experts(cfg)
            + float(experts_touched) * expert_parameters(cfg) * 2
            + per_pos * (float(live_positions) + float(rows_stepped))
            + 2.0 * recurrent_state_bytes_per_slot(cfg) * float(rows_stepped))
