"""Bytes a SELF-DRAFTING ROUND of the ``exaone_moe`` decoder NEEDS
(``k_exaone_236b_a23b``: window and global attention, a dense layer,
routed experts beside a shared expert, one multi-token-prediction module
with leaves of its own), from the configuration's sizes and the round's
own counts: the numerators of ``spec_round_roofline.serve``,
``decode_step_roofline.offline``, ``moe_experts_roofline.serve`` and
``mixed_attention_roofline.serve`` in that cell, and the pool's bytes the
family holds the program's gauges to.  What the algorithm requires, not
what the program happens to move: a byte read twice counts once, an
expert no row chose is not read at all, a K/V row the mask hides is not
read — and nothing here knows a kernel's tiles.
"""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_experts", "num_experts_all", "num_experts_per_tok",
        "num_shared_experts", "sliding_window", "vocab_size",
        "num_nextn_predict_layers")}
    s["window_layers"] = sum(k == "sliding_attention"
                             for k in cfg["layer_types"])
    s["global_layers"] = s["num_hidden_layers"] - s["window_layers"]
    s["dense_layers"] = sum(k == "dense" for k in cfg["mlp_layer_types"])
    s["sparse_layers"] = s["num_hidden_layers"] - s["dense_layers"]
    return s


def expert_parameters(cfg: dict) -> int:
    """Parameters of ONE expert (routed or shared): gate, up and down."""
    s = _sizes(cfg)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def parameters(cfg: dict) -> dict:
    """Parameters by part over the whole cut, the module's block among
    the sparse layers; ``float32``: those of them stored in float32
    (norms, routers, selection biases), the rest are bf16."""
    s = _sizes(cfg)
    d, dh = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"] * dh, s["num_key_value_heads"] * dh
    blocks = s["num_hidden_layers"] + s["num_nextn_predict_layers"]
    sparse = s["sparse_layers"] + s["num_nextn_predict_layers"]
    norms = (blocks * (2 * dh + 2 * d) + d
             + s["num_nextn_predict_layers"] * 2 * d)
    routers = sparse * (d * s["num_experts_all"] + s["num_experts_all"])
    return {
        "attention": blocks * (2 * d * nq + 2 * d * nkv),
        "dense_ffn": s["dense_layers"] * 3 * d * s["intermediate_size"],
        "routed_experts": sparse * s["num_experts"] * expert_parameters(cfg),
        "shared_experts": sparse * s["num_shared_experts"]
        * expert_parameters(cfg),
        "module_projection": s["num_nextn_predict_layers"] * 2 * d * d,
        "embedding": s["vocab_size"] * d,
        "head": s["vocab_size"] * d,
        "norms": norms, "routers": routers, "float32": norms + routers,
    }


def weights_bytes(cfg: dict) -> int:
    """Bytes of every weight as stored (bf16 matrices, float32 norms,
    routers and biases)."""
    p = parameters(cfg)
    total = sum(v for k, v in p.items() if k != "float32")
    return 2 * total + 2 * p["float32"]


def weight_bytes_outside_routed_experts(cfg: dict) -> int:
    """Bytes, as stored, of every weight a round reads whoever is routed
    where: all but the routed experts and the embedding (of which only
    the rows looked up are read, counted 0)."""
    p = parameters(cfg)
    return weights_bytes(cfg) - 2 * (p["routed_experts"] + p["embedding"])


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V one cached position holds in ONE layer."""
    s = _sizes(cfg)
    return 2 * s["num_key_value_heads"] * s["head_dim"] * bytes_per_value


def kv_bytes_per_slot(cfg: dict, rung: int, bytes_per_value: int = 2,
                      one_length: bool = False) -> int:
    """K/V bytes of one slot at length rung ``rung``: a global layer and
    the module hold the rung, a window layer ``min(rung, window)``
    positions (``one_length``: the rung too)."""
    s = _sizes(cfg)
    ring = rung if one_length else min(int(rung), s["sliding_window"])
    whole = s["global_layers"] + s["num_nextn_predict_layers"]
    return kv_bytes_per_position(cfg, bytes_per_value) * (
        whole * int(rung) + s["window_layers"] * ring)


def experts_min_bytes(cfg: dict, experts_touched: float,
                      rows: float) -> float:
    """The least HBM traffic of the ROUTED experts' products of ONE
    round over all sparse blocks (the module's among them): the matrices
    of the held experts that got a row, once each (``experts_touched``
    summed over the blocks), plus the rows' (row, choice) pairs that
    fall to a held expert, in bf16 in and float32 out."""
    s = _sizes(cfg)
    sparse = s["sparse_layers"] + s["num_nextn_predict_layers"]
    pairs = (float(rows) * s["num_experts_per_tok"] * s["num_experts"]
             / s["num_experts_all"])
    return (float(experts_touched) * expert_parameters(cfg) * 2
            + sparse * pairs * s["hidden_size"] * (2 + 4))


def attention_min_bytes(cfg: dict, whole_positions: float,
                        window_positions: float, rows: float,
                        kv_bytes: int = 2) -> float:
    """The least HBM traffic of ONE round's appends-and-reads over every
    leaf: the K/V of every position a query may read, ONCE a round
    (``whole_positions``: live positions summed over the global layers
    and the module; ``window_positions``: the lesser of live and window
    summed over the window layers), plus one new position written per
    row computed and block."""
    s = _sizes(cfg)
    blocks = s["num_hidden_layers"] + s["num_nextn_predict_layers"]
    return kv_bytes_per_position(cfg, kv_bytes) * (
        float(whole_positions) + float(window_positions)
        + float(rows) * blocks)


def round_min_bytes(cfg: dict, whole_positions: float,
                    window_positions: float, rows: float,
                    experts_touched: float, kv_bytes: int = 2) -> float:
    """The least HBM traffic of ONE self-drafting round: the held weights
    of the layers, the module and the head slice once (the routed
    experts by what was touched), and :func:`attention_min_bytes`."""
    return (weight_bytes_outside_routed_experts(cfg)
            + float(experts_touched) * expert_parameters(cfg) * 2
            + attention_min_bytes(cfg, whole_positions, window_positions,
                                  rows, kv_bytes))
