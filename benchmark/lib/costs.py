"""Operations and bytes a step NEEDS, computed from shapes alone.

These are the numerators of ``mfu.train`` and ``decode_step_roofline``:
what the algorithm requires, not what the program happens to execute.
Recomputed operations and bytes moved twice do not count.
"""
from __future__ import annotations


def bert_param_roles(cfg: dict) -> dict:
    """BERT-base parameter counts split by how often each multiplies
    (the role split of ``bench_bert.py``, from the config's sizes)."""
    d, di, layers = (int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers"))
    v = int(cfg["vocab_size"])
    # per encoder layer: q, k, v, out (weights + biases), two FFN
    # matrices with biases, two LayerNorms
    enc = layers * (4 * (d * d + d) + (d * di + di) + (di * d + d) + 4 * d)
    # MLM transform (dense + LayerNorm); the vocabulary projection is
    # the tied word embedding, counted apart
    mlm = d * d + d + 2 * d
    # pooler + NSP classifier
    head = (d * d + d) + (2 * d + 2)
    return {"encoder": enc, "mlm_transform": mlm, "vocab_projection": d * v,
            "heads": head}


def bert_train_flops_per_step(cfg: dict, batch: int, seq_len: int,
                              masks_per_seq: int) -> float:
    """Forward + backward FLOPs of one BERT pretraining step: 6 per
    parameter per token that meets it, plus the attention products
    (scores and context, forward and backward: 12 * L * B * S^2 * D).
    Embedding tables are gathers and count 0."""
    r = bert_param_roles(cfg)
    d, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    tokens = batch * seq_len
    masked = batch * masks_per_seq
    return (6.0 * r["encoder"] * tokens
            + 6.0 * (r["mlm_transform"] + r["vocab_projection"]) * masked
            + 6.0 * r["heads"] * batch
            + 12.0 * layers * batch * seq_len * seq_len * d)


def lm_weight_bytes(cfg: dict, bytes_per_weight: int = 4) -> int:
    """Bytes of the weights one decode step must read: every block and
    the output head once; of the two embedding tables only the rows
    looked up (counted as 0 — 320 rows of 3 KB)."""
    d, di, layers = (int(cfg[k]) for k in ("n_embd", "n_inner", "n_layer"))
    v = int(cfg["vocab_size"])
    block = 4 * (d * d + d) + (d * di + di) + (di * d + d) + 4 * d
    head = d * v + v
    return (layers * block + head) * bytes_per_weight


def lm_kv_bytes_per_position(cfg: dict, bytes_per_value: int = 4) -> int:
    """Bytes of K and V one cached position holds across all layers."""
    return 2 * int(cfg["n_layer"]) * int(cfg["n_embd"]) * bytes_per_value


def decode_step_min_bytes(cfg: dict, live_positions: float,
                          rows_stepped: float, weight_bytes: int = 4,
                          kv_bytes: int = 4) -> float:
    """The least HBM traffic of ONE pooled decode step: the weights as
    stored, the K/V of every live position read once, and one new K/V
    position written per row that stepped.  Bandwidth-bound: at 320 rows
    the step's 2 * params * rows FLOPs are 0.5 ms of the bf16 peak
    against 0.7 ms+ for the bytes."""
    per_pos = lm_kv_bytes_per_position(cfg, kv_bytes)
    return (lm_weight_bytes(cfg, weight_bytes)
            + per_pos * float(live_positions)
            + per_pos * float(rows_stepped))
