"""Bytes a pooled decode step of the lightning + block-sparse decoder
(``minicpm_sala``) NEEDS, from the configuration's sizes alone: the
numerators of ``decode_step_roofline.offline``,
``linear_state_roofline.serve`` and ``sparse_attention_roofline.serve``
in the ``minicpm_sala`` cells.  What the algorithm requires, not what
the program happens to move: a byte read twice counts once, and a sparse
layer is charged the positions the selection rule names, not the rung.
"""
from __future__ import annotations

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _sparse(cfg: dict) -> dict:
    return cfg.get("sparse_config") or cfg["assumed"]["sparse_config"]


def layer_parameters(cfg: dict, kind: str) -> dict:
    """Parameters of ONE layer of ``kind``, by part."""
    d = int(cfg["hidden_size"])
    if kind == LIGHTNING:
        width = int(cfg["lightning_nh"]) * int(cfg["lightning_head_dim"])
        kv, dh = width, int(cfg["lightning_head_dim"])
    else:
        width = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
        kv = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
        dh = int(cfg["head_dim"])
    return {"mixer": (3 * d * width + 2 * d * kv + 2 * dh
                      + (width if kind == LIGHTNING else 0)),
            "mlp": 3 * d * int(cfg["intermediate_size"]),
            "norms": 2 * d}


def kinds(cfg: dict) -> list:
    return list(cfg["mixer_types"])


def weight_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """Bytes of the weights one decode step must read, as stored: every
    layer, the final norm and the output head once; of the embedding
    only the rows looked up (counted 0: 64 rows of 8 KB)."""
    d = int(cfg["hidden_size"])
    layers = sum(sum(layer_parameters(cfg, k).values()) for k in kinds(cfg))
    return (layers + d * int(cfg["vocab_size"]) + d) * bytes_per_weight


def lightning_state_bytes_per_slot(cfg: dict, bytes_per_value: int = 4) -> int:
    """Bytes of the ``[heads, d, d]`` state of one slot over all
    lightning layers."""
    h, dh = int(cfg["lightning_nh"]), int(cfg["lightning_head_dim"])
    return kinds(cfg).count(LIGHTNING) * h * dh * dh * bytes_per_value


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V one cached position holds in ONE sparse layer."""
    return (2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
            * bytes_per_value)


def sequence_bytes_per_slot(cfg: dict, rung: int,
                            bytes_per_value: int = 2) -> int:
    """K, V and compressed-key rows of one slot at length ``rung`` over
    all sparse layers."""
    per = kv_bytes_per_position(cfg, bytes_per_value)
    stride = int(_sparse(cfg)["kernel_stride"])
    return kinds(cfg).count(SPARSE) * (rung * per + rung // stride * per // 2)


def linear_state_min_bytes(cfg: dict, rows_stepped: float,
                           state_bytes: int = 4) -> float:
    """The least HBM traffic of the lightning state updates of ONE step
    over all lightning layers: each stepped row's state read once and
    written once, plus q, k, v in and o out in float32."""
    h, dh = int(cfg["lightning_nh"]), int(cfg["lightning_head_dim"])
    io = kinds(cfg).count(LIGHTNING) * 4 * h * dh * 4
    return float(rows_stepped) * (
        2 * lightning_state_bytes_per_slot(cfg, state_bytes) + io)


def sparse_min_bytes(cfg: dict, positions_read: float, rows_stepped: float,
                     positions_live: float, bytes_per_value: int = 2) -> float:
    """The least HBM traffic of the select-and-attend of ONE step:
    ``positions_read`` K/V positions (summed over rows AND sparse
    layers: what the rule names, the program's own counter), the
    complete compressed-key rows of the live positions (one per
    ``kernel_stride``, half a K/V position's bytes each), and per
    stepped row and layer one new K/V position written, its last
    ``kernel_size`` K rows re-read every ``kernel_stride`` steps for the
    new compressed key, and q in and the context out in float32."""
    sp = _sparse(cfg)
    per = kv_bytes_per_position(cfg, bytes_per_value)
    n_sparse = kinds(cfg).count(SPARSE)
    q_io = 2 * int(cfg["num_attention_heads"]) * int(cfg["head_dim"]) * 4
    per_row = (per + int(sp["kernel_size"]) * per / 2.0
               / int(sp["kernel_stride"]) + q_io)
    return (float(positions_read) * per
            + float(positions_live) / int(sp["kernel_stride"]) * per / 2.0
            + float(rows_stepped) * n_sparse * per_row)


def step_min_bytes(cfg: dict, rows_stepped: float, positions_read: float,
                   positions_live: float) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights as
    stored, every stepped row's lightning state read and written, and
    the sparse layers' select-and-attend.  Bandwidth-bound: at 64 rows
    the step's 2 * params * rows FLOPs are 1.8 ms of the bf16 peak
    against 9 ms+ for the bytes."""
    return (weight_bytes(cfg) + linear_state_min_bytes(cfg, rows_stepped)
            + sparse_min_bytes(cfg, positions_read, rows_stepped,
                               positions_live))
