"""Bytes and operations a pooled decode step of the ``solar_open2``
decoder NEEDS (K layers: Kimi Delta Attention, a ``[heads, dk, dv]``
state a slot decayed a CHANNEL; G layers: gated position-free GQA that
keeps K/V; routed experts beside a shared expert after every mixer, a
share of the experts held), from the configuration's sizes alone: the
numerators of ``decode_step_roofline.offline``,
``delta_state_roofline.serve`` and ``moe_experts_roofline.serve`` in the
``solar_open2_250b`` cell, and the pool's bytes the family holds the
program's gauges to.  What the algorithm requires, not what the program
happens to move: a byte read twice counts once, a K/V row the mask hides
is not read, and nothing here knows how a leaf is tiled or which
implementation runs the step.
"""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "vocab_size",
        "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok")}
    lin = cfg["linear_attn_config"]
    s["lin_heads"], s["dk"] = int(lin["num_heads"]), int(lin["head_dim"])
    s["conv_len"] = int(lin["short_conv_kernel_size"])
    s["n_experts_all"] = int(cfg.get("n_routed_experts_all",
                                     cfg["n_routed_experts"]))
    s["g_layers"] = len(cfg["gqa_layers"])
    s["k_layers"] = s["num_hidden_layers"] - s["g_layers"]
    s["d_key"] = s["lin_heads"] * s["dk"]          # = d_value: dv = dk
    s["d_qkv"] = 3 * s["d_key"]
    s["rank"] = s["dk"]                            # of both low-rank pairs
    return s


def expert_parameters(cfg: dict) -> int:
    """Parameters of ONE routed expert (gate, up, down)."""
    s = _sizes(cfg)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one layer by part; ``float32`` counts those kept in
    float32 (norms, the conv kernel, ``A_log``, ``dt_bias``, the router
    and its bias) of ONE K layer and of ONE G layer, the rest are bf16."""
    s = _sizes(cfg)
    d, h = s["hidden_size"], s["lin_heads"]
    k_small = s["conv_len"] * s["d_qkv"] + h + s["d_key"] + s["dk"]
    k_mixer = (4 * d * s["d_key"] + 2 * (d * s["rank"]
                                         + s["rank"] * s["d_key"])
               + d * h + k_small)
    nq = s["num_attention_heads"] * s["head_dim"]
    nkv = s["num_key_value_heads"] * s["head_dim"]
    g_mixer = 2 * d * nq + 2 * d * nkv
    if cfg.get("use_gqa_gate", False):
        g_mixer += d * nq
    router = d * s["n_experts_all"] + s["n_experts_all"]
    return {"k_mixer": k_mixer, "g_mixer": g_mixer, "router": router,
            "shared": s["n_shared_experts"] * expert_parameters(cfg),
            "routed": s["n_routed_experts"] * expert_parameters(cfg),
            "norms": 2 * d,
            "k_float32": k_small + router + 2 * d,
            "g_float32": router + 2 * d}


def parameters(cfg: dict) -> dict:
    """Parameters by part over the whole cut (the head is untied)."""
    s, p = _sizes(cfg), layer_parameters(cfg)
    n = s["num_hidden_layers"]
    return {"k_mixers": s["k_layers"] * p["k_mixer"],
            "g_mixers": s["g_layers"] * p["g_mixer"],
            "routers": n * p["router"], "shared_experts": n * p["shared"],
            "routed_experts": n * p["routed"], "norms": n * p["norms"],
            "final_norm": s["hidden_size"],
            "embedding": s["vocab_size"] * s["hidden_size"],
            "head": s["vocab_size"] * s["hidden_size"],
            "float32": (s["k_layers"] * p["k_float32"]
                        + s["g_layers"] * p["g_float32"]
                        + s["hidden_size"])}


def weight_bytes_held(cfg: dict) -> int:
    """Bytes of every weight as stored: bf16 matrices, float32 vectors,
    conv kernels, routers and biases."""
    p = parameters(cfg)
    total = sum(v for k, v in p.items() if k != "float32")
    return 2 * total + 2 * p["float32"]


def weight_bytes_outside_routed_experts(cfg: dict) -> int:
    """Bytes, as stored, of every weight a step reads whoever is routed
    where: all but the routed experts and the embedding (of which only
    the rows looked up are read, counted 0)."""
    p = parameters(cfg)
    return weight_bytes_held(cfg) - 2 * (p["routed_experts"]
                                         + p["embedding"])


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V one cached position holds across the G layers."""
    s = _sizes(cfg)
    return (2 * s["g_layers"] * s["num_key_value_heads"] * s["head_dim"]
            * bytes_per_value)


def delta_state_bytes_per_slot(cfg: dict, bytes_per_value: int = 4) -> int:
    """Bytes of the delta-rule state ``[heads, dk, dv]`` of one slot
    across the K layers."""
    s = _sizes(cfg)
    return s["k_layers"] * s["lin_heads"] * s["dk"] * s["dk"] \
        * bytes_per_value


def conv_state_bytes_per_slot(cfg: dict, bytes_per_value: int = 4) -> int:
    """Bytes of the conv windows (the last ``K - 1`` projected rows
    ``[q; k; v]``) of one slot across the K layers."""
    s = _sizes(cfg)
    return s["k_layers"] * (s["conv_len"] - 1) * s["d_qkv"] * bytes_per_value


def recurrent_state_bytes_per_slot(cfg: dict) -> int:
    return delta_state_bytes_per_slot(cfg) + conv_state_bytes_per_slot(cfg)


def expert_stats_bytes(cfg: dict) -> int:
    """The counts the steps keep on the device: ``[layers, 4]`` int32,
    carried by the pool beside the recurrent leaves."""
    return _sizes(cfg)["num_hidden_layers"] * 4 * 4


def pool_bytes(cfg: dict, slots: int, rung: int, kv_bytes: int = 2) -> int:
    """Bytes of the whole pool at one rung pair."""
    return (int(slots) * (kv_bytes_per_position(cfg, kv_bytes) * int(rung)
                          + recurrent_state_bytes_per_slot(cfg))
            + expert_stats_bytes(cfg))


def delta_update_min_bytes(cfg: dict, rows_stepped: float) -> float:
    """The least HBM traffic of the delta rule of ONE step over the K
    layers: each stepped row's state read once and written once, plus the
    rule's inputs — q, k, v, the decay COLUMN (a factor a key channel:
    as wide as k) and the step gate a head — and its output o, in
    float32.  The same work whatever implements the step."""
    s = _sizes(cfg)
    io = 4 * (s["d_qkv"] + s["d_key"] + s["lin_heads"] + s["d_key"])
    return float(rows_stepped) * (2 * delta_state_bytes_per_slot(cfg)
                                  + s["k_layers"] * io)


def held_pairs(cfg: dict, rows: float) -> float:
    """(row, choice) pairs of ``rows`` rows that fall to a held expert
    under even routing: the arithmetic ``expert_rows_per_held.serve`` is
    read against is this over the held experts."""
    s = _sizes(cfg)
    return (float(rows) * s["num_experts_per_tok"] * s["n_routed_experts"]
            / s["n_experts_all"])


def experts_min_bytes(cfg: dict, experts_touched: float,
                      rows_stepped: float) -> float:
    """The least HBM traffic of the ROUTED experts' products of ONE step
    over all layers: the matrices of the held experts that got a row,
    once each (``experts_touched`` summed over the layers), plus the
    pairs that fall to a held expert, in bf16 in and float32 out."""
    s = _sizes(cfg)
    return (float(experts_touched) * expert_parameters(cfg) * 2
            + s["num_hidden_layers"] * held_pairs(cfg, rows_stepped)
            * s["hidden_size"] * (2 + 4))


def experts_flops(cfg: dict, rows_stepped: float) -> float:
    """Multiply-adds x 2 of the routed experts' products of ONE step: the
    pairs that fall to a held expert through gate, up and down."""
    s = _sizes(cfg)
    return (2.0 * s["num_hidden_layers"] * held_pairs(cfg, rows_stepped)
            * expert_parameters(cfg))


def step_min_bytes(cfg: dict, live_positions: float, rows_stepped: float,
                   experts_touched: float, kv_bytes: int = 2) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights
    outside the routed experts as stored, the touched experts' matrices,
    the recurrent state of every row that stepped read and written, the
    K/V of every live position read once, one new K/V position written
    per row that stepped.  Bandwidth-bound: at 256 rows the step's ~2 x
    0.4 G active parameters x 256 FLOPs are ~1 ms of the bf16 peak
    against 16 ms+ for the bytes."""
    per_pos = kv_bytes_per_position(cfg, kv_bytes)
    return (weight_bytes_outside_routed_experts(cfg)
            + float(experts_touched) * expert_parameters(cfg) * 2
            + 2.0 * recurrent_state_bytes_per_slot(cfg) * float(rows_stepped)
            + per_pos * float(live_positions)
            + per_pos * float(rows_stepped))
