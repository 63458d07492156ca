"""Bytes a pooled decode step of the windowed routed decoder
(``smallthinker``: grouped-query attention over a sliding window or over
the whole context, a mixture of routed experts after each) NEEDS, from
the configuration's sizes and the step's own counts: the numerators of
``decode_step_roofline.offline``, ``moe_experts_roofline.serve`` and
``mixed_attention_roofline.serve`` in the ``smallthinker_21b_a3b`` cell,
and the pool's bytes the family holds the program's gauges to.  What the
algorithm requires, not what the program happens to move: a byte read
twice counts once, an expert no row chose is not read at all, a K/V row
the mask hides is not read — and nothing here knows a kernel's tiles.
"""
from __future__ import annotations

GLOBAL, WINDOW = 0, 1


def _sizes(cfg: dict) -> dict:
    s = {k: int(cfg[k]) for k in (
        "hidden_size", "moe_ffn_hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_num_primary_experts", "moe_num_active_primary_experts",
        "sliding_window_size", "vocab_size")}
    s["kinds"] = [int(x) for x in cfg["sliding_window_layout"]]
    s["window_layers"] = s["kinds"].count(WINDOW)
    s["global_layers"] = s["kinds"].count(GLOBAL)
    return s


def expert_parameters(cfg: dict) -> int:
    """Parameters of ONE expert: gate, up and down."""
    s = _sizes(cfg)
    return 3 * s["hidden_size"] * s["moe_ffn_hidden_size"]


def parameters(cfg: dict) -> dict:
    """Parameters by part, over the whole cut (the head is untied:
    embedding and head each counted)."""
    s = _sizes(cfg)
    d, dh, n = s["hidden_size"], s["head_dim"], s["num_hidden_layers"]
    nq, nkv = s["num_attention_heads"] * dh, s["num_key_value_heads"] * dh
    return {
        "attention": n * (2 * d * nq + 2 * d * nkv),
        "norms": n * 2 * d + d,
        "routers": n * d * s["moe_num_primary_experts"],
        "experts": n * s["moe_num_primary_experts"] * expert_parameters(cfg),
        "embedding": s["vocab_size"] * d,
        "head": s["vocab_size"] * d,
    }


def float32_parameters(cfg: dict) -> int:
    """Those of them stored in float32 (norms and routers); the rest are
    bf16."""
    p = parameters(cfg)
    return p["norms"] + p["routers"]


def weight_bytes_outside_experts(cfg: dict) -> int:
    """Bytes, as stored, of every weight a step reads whoever is routed
    where: all but the experts and the embedding (of which only the rows
    looked up are read, counted 0)."""
    p = parameters(cfg)
    return (2 * (sum(p.values()) - p["experts"] - p["embedding"])
            + 2 * float32_parameters(cfg))


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V one cached position holds in ONE layer."""
    s = _sizes(cfg)
    return 2 * s["num_key_value_heads"] * s["head_dim"] * bytes_per_value


def kv_bytes_per_slot(cfg: dict, rung: int, bytes_per_value: int = 2,
                      one_length: bool = False) -> int:
    """K/V bytes of one slot at length rung ``rung``: a global layer
    holds the rung, a window layer ``min(rung, window)`` positions
    (``one_length``: the rung too, what a pool with one length for every
    layer would hold)."""
    s = _sizes(cfg)
    ring = rung if one_length else min(int(rung), s["sliding_window_size"])
    return kv_bytes_per_position(cfg, bytes_per_value) * (
        s["global_layers"] * int(rung) + s["window_layers"] * ring)


def experts_min_bytes(cfg: dict, experts_touched: float,
                      rows_stepped: float) -> float:
    """The least HBM traffic of the experts' products of ONE step over
    all layers: the matrices of the experts that got a row, once each
    (``experts_touched`` summed over the layers), plus the (row, choice)
    pairs' inputs in bf16 and outputs in float32."""
    s = _sizes(cfg)
    pairs = float(rows_stepped) * s["moe_num_active_primary_experts"]
    return (float(experts_touched) * expert_parameters(cfg) * 2
            + s["num_hidden_layers"] * pairs * s["hidden_size"] * (2 + 4))


def attention_min_bytes(cfg: dict, global_positions: float,
                        window_positions: float, rows_stepped: float,
                        kv_bytes: int = 2) -> float:
    """The least HBM traffic of ONE step's appends-and-reads over both
    kinds of leaf: the K/V of every position a query may read, once
    (``global_positions``: live positions summed over the global layers;
    ``window_positions``: the lesser of live and window, summed over the
    window layers — both per step, from the program's two counters),
    plus one new position written per row and layer."""
    s = _sizes(cfg)
    per = kv_bytes_per_position(cfg, kv_bytes)
    return per * (float(global_positions) + float(window_positions)
                  + float(rows_stepped) * s["num_hidden_layers"])


def step_min_bytes(cfg: dict, global_positions: float,
                   window_positions: float, rows_stepped: float,
                   experts_touched: float, kv_bytes: int = 2) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights
    outside the experts as stored, the matrices of the experts touched,
    and :func:`attention_min_bytes`.  Bandwidth-bound: at 40 rows an
    expert sees under 4 rows."""
    return (weight_bytes_outside_experts(cfg)
            + float(experts_touched) * expert_parameters(cfg) * 2
            + attention_min_bytes(cfg, global_positions, window_positions,
                                  rows_stepped, kv_bytes))
