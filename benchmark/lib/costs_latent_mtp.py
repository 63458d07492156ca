"""Bytes and FLOPs a SELF-DRAFTING ROUND of the dense latent decoder
(``pangu_ultra_moe``: multi-head latent attention read over EVERY live
position, sandwich norms, a dense layer, ungrouped routed experts beside
a shared expert, one multi-token-prediction module with a latent leaf of
its own) NEEDS, from the configuration's sizes and the round's own
counts: the numerators of ``dense_latent_roofline.serve``,
``spec_round_roofline.serve``, ``decode_step_roofline.offline`` and
``moe_experts_roofline.serve`` in the ``openpangu_ultra_moe_718b`` cell,
and the pool's bytes the family holds the program's gauges to.  What the
algorithm requires, not what the program happens to move: a byte read
twice counts once (a position's row is read ONCE a round for both fresh
rows and all 128 heads), an expert no row chose is not read at all, a
position past a row's own is neither read nor multiplied — and nothing
here knows a kernel's tiles, a key block or a rung.
"""
from __future__ import annotations

from benchmark.lib.costs_latent_sparse import whole_tiles


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "vocab_size",
        "num_nextn_predict_layers")}
    s["n_routed_experts_all"] = int(cfg.get("n_routed_experts_all",
                                            cfg["n_routed_experts"]))
    s["blocks"] = s["num_hidden_layers"] + s["num_nextn_predict_layers"]
    s["sparse_blocks"] = s["blocks"] - s["first_k_dense_replace"]
    s["latent_lanes"] = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return s


def expert_parameters(cfg: dict) -> int:
    """Parameters of ONE expert (routed or shared): gate, up and down."""
    s = _sizes(cfg)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def attention_parameters(cfg: dict) -> int:
    """Parameters of ONE block's attention matrices: q_a, q_b, kv_a, the
    two up projections (kv_b) and o."""
    s = _sizes(cfg)
    d, h = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    return (d * s["q_lora_rank"] + s["q_lora_rank"] * h * qk
            + d * s["latent_lanes"]
            + h * s["kv_lora_rank"] * (s["qk_nope_head_dim"]
                                       + s["v_head_dim"])
            + h * s["v_head_dim"] * d)


def parameters(cfg: dict) -> dict:
    """Parameters by part over the whole cut, the module's block among
    the sparse ones; ``float32``: those of them stored in float32 (norms
    and routers), the rest are bf16."""
    s = _sizes(cfg)
    d, mtp = s["hidden_size"], s["num_nextn_predict_layers"]
    norms = (s["blocks"] * (4 * d + s["q_lora_rank"] + s["kv_lora_rank"])
             + d + mtp * 2 * d)
    routers = s["sparse_blocks"] * d * s["n_routed_experts_all"]
    return {
        "attention": s["blocks"] * attention_parameters(cfg),
        "dense_ffn": s["first_k_dense_replace"] * 3 * d
        * s["intermediate_size"],
        "routed_experts": s["sparse_blocks"] * s["n_routed_experts"]
        * expert_parameters(cfg),
        "shared_experts": s["sparse_blocks"] * s["n_shared_experts"]
        * expert_parameters(cfg),
        "module_projection": mtp * 2 * d * d,
        "embedding": s["vocab_size"] * d,
        "head": s["vocab_size"] * d,
        "norms": norms, "routers": routers, "float32": norms + routers,
    }


def weight_bytes_as_stored(cfg: dict) -> int:
    """Bytes of every weight as stored (bf16 matrices, float32 norms and
    routers)."""
    p = parameters(cfg)
    return 2 * (sum(p.values()) - 2 * p["float32"]) + 4 * p["float32"]


def weight_bytes_outside_routed_experts(cfg: dict) -> int:
    """Bytes, as stored, of every weight a round reads whoever is routed
    where: all but the routed experts and the embedding (of which only
    the rows looked up are read, counted 0)."""
    p = parameters(cfg)
    return weight_bytes_as_stored(cfg) - 2 * (p["routed_experts"]
                                              + p["embedding"])


def cache_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes ONE leaf HOLDS of one cached position: the latent row (c and
    the rotated lanes) in whole 128-lane tiles as the chip stores a row
    (576 -> 640).  What a read NEEDS is the bare width
    (:func:`dense_read_min_bytes`)."""
    return whole_tiles(_sizes(cfg)["latent_lanes"]) * bytes_per_value


def cache_bytes_per_slot(cfg: dict, rung: int,
                         bytes_per_value: int = 2) -> int:
    """Every layer's leaf and the module's, at length rung ``rung``."""
    return (cache_bytes_per_position(cfg, bytes_per_value)
            * _sizes(cfg)["blocks"] * int(rung))


def dense_read_min_bytes(cfg: dict, positions: float, rows: float,
                         bytes_per_value: int = 2) -> float:
    """The least HBM traffic of ONE round's appends and dense reads over
    every leaf: each live position's latent row ONCE a round and leaf,
    whatever the number of fresh rows and heads that read it
    (``positions``: live positions of the slots that advanced, at their
    LAST fresh row, summed over the leaves), plus the row each computed
    row appends to each leaf."""
    s = _sizes(cfg)
    return ((float(positions) + float(rows) * s["blocks"])
            * s["latent_lanes"] * bytes_per_value)


def dense_read_flops(cfg: dict, row_positions: float) -> float:
    """Absorbed: every head of every fresh row scores each position it
    may read over the latent lanes and sums its ``kv_lora_rank`` lanes
    back (``row_positions``: positions read, summed over the fresh ROWS
    computed and the leaves — the program's counter)."""
    s = _sizes(cfg)
    return 2.0 * float(row_positions) * s["num_attention_heads"] * (
        s["latent_lanes"] + s["kv_lora_rank"])


def experts_min_bytes(cfg: dict, experts_touched: float,
                      rows: float) -> float:
    """The least HBM traffic of the ROUTED experts' products of ONE
    round over all sparse blocks (the module's among them): the matrices
    of the held experts that got a row, once each (``experts_touched``
    summed over the blocks), plus the rows' (row, choice) pairs that
    fall to a held expert, in bf16 in and float32 out."""
    s = _sizes(cfg)
    pairs = (float(rows) * s["num_experts_per_tok"] * s["n_routed_experts"]
             / s["n_routed_experts_all"])
    return (float(experts_touched) * expert_parameters(cfg) * 2
            + s["sparse_blocks"] * pairs * s["hidden_size"] * (2 + 4))


def round_min_bytes(cfg: dict, positions: float, rows: float,
                    experts_touched: float) -> float:
    """The least HBM traffic of ONE self-drafting round: the held weights
    of the layers, the module and the head slice once (the routed
    experts by what was touched), and :func:`dense_read_min_bytes`."""
    return (weight_bytes_outside_routed_experts(cfg)
            + float(experts_touched) * expert_parameters(cfg) * 2
            + dense_read_min_bytes(cfg, positions, rows))
