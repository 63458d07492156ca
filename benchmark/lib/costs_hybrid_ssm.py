"""Bytes a pooled decode step of the hybrid SSM + attention decoder
(``falcon_h1``) NEEDS, from the configuration's sizes alone: the
numerators of ``decode_step_roofline.offline`` and
``ssm_update_roofline.serve`` in the ``falcon_h1_34b`` cells.  What the
algorithm requires, not what the program happens to move: a byte read
twice counts once.
"""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    d = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "mamba_d_conv", "vocab_size")}
    d["d_xbc"] = d["mamba_d_ssm"] + 2 * d["mamba_n_groups"] * d["mamba_d_state"]
    return d


def layer_parameters(cfg: dict) -> dict:
    """Parameters of ONE block, by part."""
    s = _sizes(cfg)
    d, dh = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"] * dh, s["num_key_value_heads"] * dh
    heads = s["mamba_n_heads"]
    return {
        "attention": d * nq + 2 * d * nkv + nq * d,
        "mixer": (d * (s["mamba_d_ssm"] + s["d_xbc"] + heads)
                  + s["mamba_d_conv"] * s["d_xbc"] + s["d_xbc"]
                  + 3 * heads + s["mamba_d_ssm"] + s["mamba_d_ssm"] * d),
        "mlp": 3 * d * s["intermediate_size"],
        "norms": 2 * d,
    }


def weight_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """Bytes of the weights one decode step must read, as stored: every
    block, the final norm and the output head once; of the embedding
    only the rows looked up (counted 0: 80 rows of 10 KB)."""
    s = _sizes(cfg)
    block = sum(layer_parameters(cfg).values())
    head = s["hidden_size"] * s["vocab_size"] + s["hidden_size"]
    return (s["num_hidden_layers"] * block + head) * bytes_per_weight


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V one cached position holds across all layers."""
    s = _sizes(cfg)
    return (2 * s["num_hidden_layers"] * s["num_key_value_heads"]
            * s["head_dim"] * bytes_per_value)


def ssm_state_bytes_per_slot(cfg: dict, bytes_per_value: int = 4) -> int:
    """Bytes of the SSM state ``[heads, d_head, d_state]`` of one slot
    across all layers."""
    s = _sizes(cfg)
    return (s["num_hidden_layers"] * s["mamba_n_heads"] * s["mamba_d_head"]
            * s["mamba_d_state"] * bytes_per_value)


def recurrent_state_bytes_per_slot(cfg: dict, ssm_bytes: int = 4,
                                   conv_bytes: int = 4) -> int:
    """SSM state plus the conv window (the last ``d_conv - 1`` pre-conv
    rows) of one slot across all layers."""
    s = _sizes(cfg)
    return (ssm_state_bytes_per_slot(cfg, ssm_bytes)
            + s["num_hidden_layers"] * (s["mamba_d_conv"] - 1)
            * s["d_xbc"] * conv_bytes)


def ssm_update_min_bytes(cfg: dict, rows_stepped: float,
                         ssm_bytes: int = 4) -> float:
    """The least HBM traffic of the state update of ONE step over all
    layers: each stepped row's state read once and written once, plus
    the update's inputs (x, B, C, dt, the decay) and its output y, in
    float32."""
    s = _sizes(cfg)
    io = 4 * (2 * s["mamba_d_ssm"] + 2 * s["mamba_n_groups"]
              * s["mamba_d_state"] + 2 * s["mamba_n_heads"])
    return float(rows_stepped) * (
        2 * ssm_state_bytes_per_slot(cfg, ssm_bytes)
        + s["num_hidden_layers"] * io)


def step_min_bytes(cfg: dict, live_positions: float, rows_stepped: float,
                   weight_bytes_each: int = 2, kv_bytes: int = 2,
                   ssm_bytes: int = 4, conv_bytes: int = 4) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights as
    stored, the recurrent state of every row that stepped read and
    written, the K/V of every live position read once, one new K/V
    position written per row that stepped.  Bandwidth-bound: at 80 rows
    the step's 2 * params * rows FLOPs are 3 ms of the bf16 peak against
    14 ms+ for the bytes."""
    per_pos = kv_bytes_per_position(cfg, kv_bytes)
    return (weight_bytes(cfg, weight_bytes_each)
            + 2.0 * recurrent_state_bytes_per_slot(cfg, ssm_bytes, conv_bytes)
            * float(rows_stepped)
            + per_pos * float(live_positions)
            + per_pos * float(rows_stepped))
