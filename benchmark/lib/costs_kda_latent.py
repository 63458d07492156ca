"""Bytes and operations a pooled decode step of the ``kimi_linear``
decoder NEEDS (K layers: Kimi Delta Attention, a ``[heads, dk, dv]``
state a slot decayed a CHANNEL; M layers: multi-head latent attention
read over EVERY live position, one compressed row a position; a leading
dense FFN, then routed experts beside a shared expert, a share of the
experts held), from the configuration's sizes alone: the numerators of
``decode_step_roofline.offline``, ``delta_state_roofline.serve``,
``dense_latent_roofline.serve`` and ``moe_experts_roofline.serve`` in the
``kimi_linear_48b_a3b`` cell, and the pool's bytes the family holds the
program's gauges to.  What the algorithm requires, not what the program
happens to move: a byte read twice counts once (a position's row is read
ONCE a step for all 32 heads), an expert no row chose is not read at all,
a position past a row's own is neither read nor multiplied — and nothing
here knows a kernel's tiles, a key block or how a leaf is tiled.
"""
from __future__ import annotations

from benchmark.lib.costs_latent_sparse import whole_tiles


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "num_experts", "num_experts_per_token",
        "num_shared_experts", "vocab_size")}
    lin = cfg["linear_attn_config"]
    s["lin_heads"], s["dk"] = int(lin["num_heads"]), int(lin["head_dim"])
    s["conv_len"] = int(lin["short_conv_kernel_size"])
    s["num_experts_all"] = int(cfg.get("num_experts_all",
                                       cfg["num_experts"]))
    s["k_layers"] = len(lin["kda_layers"])
    s["m_layers"] = len(lin["full_attn_layers"])
    s["sparse_layers"] = s["num_hidden_layers"] - s["first_k_dense_replace"]
    s["d_key"] = s["lin_heads"] * s["dk"]          # = d_value: dv = dk
    s["d_qkv"] = 3 * s["d_key"]
    s["rank"] = s["dk"]                            # of both low-rank pairs
    s["latent_lanes"] = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return s


def expert_parameters(cfg: dict) -> int:
    """Parameters of ONE expert (routed or shared): gate, up and down."""
    s = _sizes(cfg)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def layer_parameters(cfg: dict) -> dict:
    """Parameters of one layer's parts; ``*_float32`` counts those of a
    part kept in float32 (norms, the conv kernel, ``A_log``, ``dt_bias``,
    the router and its bias), the rest are bf16."""
    s = _sizes(cfg)
    d, h = s["hidden_size"], s["lin_heads"]
    k_small = s["conv_len"] * s["d_qkv"] + h + s["d_key"] + s["dk"]
    k_mixer = (4 * d * s["d_key"] + 2 * (d * s["rank"]
                                         + s["rank"] * s["d_key"])
               + d * h + k_small)
    nh = s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    m_mixer = (d * nh * qk + d * s["latent_lanes"] + s["kv_lora_rank"]
               + nh * s["kv_lora_rank"] * (s["qk_nope_head_dim"]
                                          + s["v_head_dim"])
               + nh * s["v_head_dim"] * d)
    router = d * s["num_experts_all"] + s["num_experts_all"]
    return {"k_mixer": k_mixer, "m_mixer": m_mixer, "router": router,
            "dense_ffn": 3 * d * s["intermediate_size"],
            "shared": s["num_shared_experts"] * expert_parameters(cfg),
            "routed": s["num_experts"] * expert_parameters(cfg),
            "norms": 2 * d, "k_float32": k_small,
            "m_float32": s["kv_lora_rank"]}


def parameters(cfg: dict) -> dict:
    """Parameters by part over the whole cut (the head is untied);
    ``float32``: those of them stored in float32."""
    s, p = _sizes(cfg), layer_parameters(cfg)
    n, sparse = s["num_hidden_layers"], s["sparse_layers"]
    return {"k_mixers": s["k_layers"] * p["k_mixer"],
            "m_mixers": s["m_layers"] * p["m_mixer"],
            "dense_ffn": s["first_k_dense_replace"] * p["dense_ffn"],
            "routers": sparse * p["router"],
            "shared_experts": sparse * p["shared"],
            "routed_experts": sparse * p["routed"],
            "norms": n * p["norms"] + s["hidden_size"],
            "embedding": s["vocab_size"] * s["hidden_size"],
            "head": s["vocab_size"] * s["hidden_size"],
            "float32": (s["k_layers"] * p["k_float32"]
                        + s["m_layers"] * p["m_float32"]
                        + sparse * p["router"] + n * p["norms"]
                        + s["hidden_size"])}


def weight_bytes_as_stored(cfg: dict) -> int:
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
    return weight_bytes_as_stored(cfg) - 2 * (p["routed_experts"]
                                              + p["embedding"])


def latent_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes the M layers' leaves HOLD of one cached position: a latent
    row (c and the shared lanes) in whole 128-lane tiles as the chip
    stores a row (576 -> 640), a leaf an M layer.  What a read NEEDS is
    the bare width (:func:`dense_read_min_bytes`)."""
    s = _sizes(cfg)
    return whole_tiles(s["latent_lanes"]) * bytes_per_value * s["m_layers"]


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
    """The counts the steps keep on the device: ``[sparse layers, 4]``
    int32, carried by the pool beside the recurrent leaves."""
    return _sizes(cfg)["sparse_layers"] * 4 * 4


def slot_bytes(cfg: dict, rung: int) -> int:
    """Everything ONE slot holds at length rung ``rung``: what a
    whole-row snapshot copies."""
    return (latent_bytes_per_position(cfg) * int(rung)
            + recurrent_state_bytes_per_slot(cfg))


def pool_bytes(cfg: dict, slots: int, rung: int) -> int:
    """Bytes of the whole pool at one rung pair."""
    return int(slots) * slot_bytes(cfg, rung) + expert_stats_bytes(cfg)


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


def dense_read_min_bytes(cfg: dict, positions: float, rows: float,
                         bytes_per_value: int = 2) -> float:
    """The least HBM traffic of ONE step's appends and dense reads over
    the M layers' leaves: each live position's latent row ONCE a step and
    leaf, whatever the heads that read it (``positions``: live positions
    of the rows that stepped, summed over the leaves), plus the row each
    stepped row appends to each leaf."""
    s = _sizes(cfg)
    return ((float(positions) + float(rows) * s["m_layers"])
            * s["latent_lanes"] * bytes_per_value)


def dense_read_flops(cfg: dict, row_positions: float) -> float:
    """Absorbed: every head of every stepped row scores each position it
    may read over the latent lanes and sums its ``kv_lora_rank`` lanes
    back (``row_positions``: positions read, summed over the rows and the
    leaves — the program's counter)."""
    s = _sizes(cfg)
    return 2.0 * float(row_positions) * s["num_attention_heads"] * (
        s["latent_lanes"] + s["kv_lora_rank"])


def held_pairs(cfg: dict, rows: float) -> float:
    """(row, choice) pairs of ``rows`` rows that fall to a held expert
    under even routing."""
    s = _sizes(cfg)
    return (float(rows) * s["num_experts_per_token"] * s["num_experts"]
            / s["num_experts_all"])


def experts_min_bytes(cfg: dict, experts_touched: float,
                      rows_stepped: float) -> float:
    """The least HBM traffic of the ROUTED experts' products of ONE step
    over all sparse layers: the matrices of the held experts that got a
    row, once each (``experts_touched`` summed over the layers), plus the
    pairs that fall to a held expert, in bf16 in and float32 out."""
    s = _sizes(cfg)
    return (float(experts_touched) * expert_parameters(cfg) * 2
            + s["sparse_layers"] * held_pairs(cfg, rows_stepped)
            * s["hidden_size"] * (2 + 4))


def experts_flops(cfg: dict, rows_stepped: float) -> float:
    """Multiply-adds x 2 of the routed experts' products of ONE step: the
    pairs that fall to a held expert through gate, up and down."""
    s = _sizes(cfg)
    return (2.0 * s["sparse_layers"] * held_pairs(cfg, rows_stepped)
            * expert_parameters(cfg))


def step_min_bytes(cfg: dict, positions: float, rows_stepped: float,
                   experts_touched: float) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights
    outside the routed experts as stored, the touched experts' matrices,
    the recurrent state of every row that stepped read and written, and
    :func:`dense_read_min_bytes`.  Bandwidth-bound: at 96 rows the step's
    ~2 x 0.5 G active parameters x 96 FLOPs and the read's ~0.3 TFLOP are
    ~2 ms of the bf16 peak against 12 ms+ for the bytes."""
    return (weight_bytes_outside_routed_experts(cfg)
            + float(experts_touched) * expert_parameters(cfg) * 2
            + 2.0 * recurrent_state_bytes_per_slot(cfg) * float(rows_stepped)
            + dense_read_min_bytes(cfg, positions, rows_stepped))
