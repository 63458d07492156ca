"""Bytes and FLOPs a pooled decode step of the latent sparse decoder
(``deepseek_v32``: latent attention read through a lightning indexer's
top-k, a dense layer, group-limited routed experts beside a shared
expert) NEEDS, from the configuration's sizes and the step's own counts:
the numerators of ``latent_attention_roofline.serve``,
``decode_step_roofline.offline`` and ``moe_experts_roofline.serve`` in
the ``deepseek_v3_2`` cell, and the pool's bytes the family holds the
program's gauges to.  What the algorithm requires, not what the program
happens to move: a byte read twice counts once, an expert no row chose is
not read at all, an index key past a row's position is not scored, a
latent row the selection did not name is not read — and nothing here
knows a kernel's tiles, a sort's passes or a rung.
"""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim",
        "index_topk", "n_routed_experts", "num_experts_per_tok",
        "n_shared_experts", "vocab_size")}
    s["n_routed_experts_all"] = int(cfg.get("n_routed_experts_all",
                                            cfg["n_routed_experts"]))
    s["sparse_layers"] = s["num_hidden_layers"] - s["first_k_dense_replace"]
    s["latent_lanes"] = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return s


def expert_parameters(cfg: dict) -> int:
    """Parameters of ONE expert (routed or shared): gate, up and down."""
    s = _sizes(cfg)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def parameters(cfg: dict) -> dict:
    """Parameters by part over the whole cut; ``float32``: those of them
    stored in float32 (norms, the indexer's LayerNorm, routers, selection
    biases), the rest are bf16."""
    s = _sizes(cfg)
    d, n, h = s["hidden_size"], s["num_hidden_layers"], s[
        "num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    norms = n * (2 * d + s["q_lora_rank"] + s["kv_lora_rank"]
                 + 2 * s["index_head_dim"]) + d
    routers = s["sparse_layers"] * (d + 1) * s["n_routed_experts_all"]
    return {
        "attention": n * (d * s["q_lora_rank"] + s["q_lora_rank"] * h * qk
                          + d * s["latent_lanes"]
                          + h * s["kv_lora_rank"] * (s["qk_nope_head_dim"]
                                                     + s["v_head_dim"])
                          + h * s["v_head_dim"] * d),
        "indexer": n * (s["q_lora_rank"] * s["index_n_heads"]
                        * s["index_head_dim"] + d * s["index_head_dim"]
                        + d * s["index_n_heads"]),
        "dense_ffn": s["first_k_dense_replace"] * 3 * d
        * s["intermediate_size"],
        "routed_experts": s["sparse_layers"] * s["n_routed_experts"]
        * expert_parameters(cfg),
        "shared_experts": s["sparse_layers"] * s["n_shared_experts"]
        * expert_parameters(cfg),
        "embedding": s["vocab_size"] * d,
        "head": s["vocab_size"] * d,
        "norms": norms, "routers": routers, "float32": norms + routers,
    }


def weight_bytes_as_stored(cfg: dict) -> int:
    p = parameters(cfg)
    return 2 * (sum(p.values()) - 2 * p["float32"]) + 4 * p["float32"]


def weight_bytes_outside_experts(cfg: dict) -> int:
    """Bytes, as stored, of every weight a step reads whoever is routed
    where: all but the routed experts and the embedding (of which only
    the rows looked up are read, counted 0)."""
    p = parameters(cfg)
    rest = (sum(p.values()) - 2 * p["float32"] - p["routed_experts"]
            - p["embedding"])
    return 2 * rest + 4 * p["float32"]


_LANE_TILE = 128


def whole_tiles(lanes: int) -> int:
    """``lanes`` rounded up to whole 128-lane tiles."""
    return -(-int(lanes) // _LANE_TILE) * _LANE_TILE


def cache_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes ONE layer HOLDS of one cached position: the latent row (c
    and the rotated lanes) and the index key, each in whole 128-lane
    tiles as the chip stores a row (576 -> 640).  What a read NEEDS is
    the bare widths (:func:`selected_read_min_bytes`)."""
    s = _sizes(cfg)
    return (whole_tiles(s["latent_lanes"])
            + whole_tiles(s["index_head_dim"])) * bytes_per_value


def cache_bytes_needed_per_position(cfg: dict,
                                    bytes_per_value: int = 2) -> int:
    """Bytes of one cached position of ONE layer at the bare widths: what
    an append writes and a read of the whole row needs."""
    s = _sizes(cfg)
    return (s["latent_lanes"] + s["index_head_dim"]) * bytes_per_value


def cache_bytes_per_slot(cfg: dict, rung: int,
                         bytes_per_value: int = 2) -> int:
    s = _sizes(cfg)
    return (cache_bytes_per_position(cfg, bytes_per_value)
            * s["num_hidden_layers"] * int(rung))


def index_score_min_bytes(cfg: dict, scored: float,
                          bytes_per_value: int = 2) -> float:
    """The least HBM traffic of ONE step's index scoring over all
    layers: every scored (live) position's index key, once (``scored``:
    positions scored a step, summed over the layers — the program's
    counter)."""
    return float(scored) * _sizes(cfg)["index_head_dim"] * bytes_per_value


def index_score_flops(cfg: dict, scored: float) -> float:
    """Multiply-adds x 2 of the scoring: every scored position against
    every index head."""
    s = _sizes(cfg)
    return 2.0 * float(scored) * s["index_n_heads"] * s["index_head_dim"]


def index_select_min_bytes(cfg: dict, scored: float) -> float:
    """The least traffic of the selection itself if the float32 scores
    went through HBM once (written by the scoring, read by the choice);
    a fused scorer-and-selector needs none: counted in no roofline."""
    return float(scored) * 4 * 2


def selected_read_min_bytes(cfg: dict, selected: float, rows_stepped: float,
                            bytes_per_value: int = 2) -> float:
    """The least HBM traffic of ONE step's appends and selected reads
    over all layers: each selected position's latent row, once
    (``selected``: per step, summed over the layers — the program's
    counter), plus the row and the index key each stepped row appends in
    each layer."""
    s = _sizes(cfg)
    return (float(selected) * s["latent_lanes"] * bytes_per_value
            + float(rows_stepped) * s["num_hidden_layers"]
            * cache_bytes_needed_per_position(cfg, bytes_per_value))


def selected_read_flops(cfg: dict, selected: float) -> float:
    """Absorbed: every head scores each selected row over the latent
    lanes and sums its ``kv_lora_rank`` lanes back."""
    s = _sizes(cfg)
    return 2.0 * float(selected) * s["num_attention_heads"] * (
        s["latent_lanes"] + s["kv_lora_rank"])


def latent_attention_min_bytes(cfg: dict, scored: float, selected: float,
                               rows_stepped: float) -> float:
    """What scoring and the selected read need together: live index keys
    + selected rows + the appends — not the rung, not the score matrix."""
    return (index_score_min_bytes(cfg, scored)
            + selected_read_min_bytes(cfg, selected, rows_stepped))


def experts_min_bytes(cfg: dict, experts_touched: float,
                      rows_stepped: float) -> float:
    """The least HBM traffic of the routed experts' products of ONE step
    over all layers: the matrices of the held experts that got a row,
    once each (``experts_touched`` summed over the layers), plus the
    held (row, choice) pairs' inputs in bf16 and outputs in float32 (a
    row's pairs fall on a held expert ``held / all`` of the time)."""
    s = _sizes(cfg)
    pairs = (float(rows_stepped) * s["num_experts_per_tok"]
             * s["n_routed_experts"] / s["n_routed_experts_all"])
    return (float(experts_touched) * expert_parameters(cfg) * 2
            + s["sparse_layers"] * pairs * s["hidden_size"] * (2 + 4))


def step_min_bytes(cfg: dict, scored: float, selected: float,
                   rows_stepped: float, experts_touched: float) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights
    outside the routed experts as stored, the matrices of the experts
    touched, and :func:`latent_attention_min_bytes`."""
    return (weight_bytes_outside_experts(cfg)
            + float(experts_touched) * expert_parameters(cfg) * 2
            + latent_attention_min_bytes(cfg, scored, selected, rows_stepped))
