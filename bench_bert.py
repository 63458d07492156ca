"""Benchmark: BERT-base pretraining step (MLM+NSP) on one TPU chip.

Prints ONE JSON line like bench.py (metric bert_base_pretrain_*).

MFU accounting (corrected round 3 — the naive 6*N*D rule overcounts
~18% here): parameters are split by role, because not every parameter
matmuls every token:

* encoder params (QKVO, FFN, LNs)          -> 6 * P_enc * B*S
* MLM transform + its LN (masked only)     -> 6 * P_mlm * B*M
* tied vocab projection (masked only)      -> 6 * D*V * B*M
* pooler + NSP head ([CLS] only)           -> 6 * P_head * B
* embedding tables: gathers, no matmul     -> 0
* attention scores/context (fwd+bwd)       -> 12 * L * B * S^2 * D

against the chip's published bf16 peak (paddle_tpu/device_peaks.py).
"""
import json
import os
import time

import numpy as np

# bs128 / S=128 / chunk=640 with fresh per-step batches is the regime
# BENCH_r05.json's headline (59.0% MFU, 76.96 ms/step) was recorded in.
BATCH = int(os.environ.get("BENCH_BERT_BATCH", "128"))
SEQ = int(os.environ.get("BENCH_BERT_SEQ", "128"))
MASKS = max(1, int(SEQ * 0.15))
STEPS = int(os.environ.get("BENCH_STEPS", "640"))
CHUNK = int(os.environ.get("BENCH_CHUNK", "640"))


def run(batch=BATCH, seq=SEQ, steps=STEPS, chunk=CHUNK):
    """Run the benchmark; returns the result dict (no printing)."""
    import paddle_tpu as fluid
    from paddle_tpu import device_peaks, framework, models

    place = fluid.TPUPlace(0)  # a chip bench: no chip, no run
    use_amp = os.environ.get("BENCH_AMP", "1") == "1"
    masks = max(1, int(seq * 0.15))

    V, D, L, H, DI, S = 30522, 768, 12, 12, 3072, seq
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 42
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [S], dtype="int64")
        sent = fluid.layers.data("sent", [S], dtype="int64")
        mask = fluid.layers.data("mask", [S])
        mpos = fluid.layers.data("mpos", [1], dtype="int64")
        mlab = fluid.layers.data("mlab", [1], dtype="int64")
        nlab = fluid.layers.data("nlab", [1], dtype="int64")
        # no dropout: each layer's attention is ONE fused_attention op,
        # whose lowering (Pallas kernel pair / XLA ops) the op picks from
        # the backend and the shapes (paddle_tpu/fused_attention.py)
        total, mlm_loss, nsp_acc = models.bert_pretrain(
            src, sent, mask, mpos, mlab, nlab,
            vocab_size=V, d_model=D, n_layer=L, n_head=H, d_inner=DI,
            seq_len=S, dropout_rate=0.0,
        )
        opt = fluid.optimizer.AdamOptimizer(1e-4)
        if use_amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(total)

    # ---- split the parameter count by role (see module docstring)
    n_params = n_embed = n_mlm = n_head = 0
    for p in prog.all_parameters():
        n = 1
        for s in p.shape:
            n *= max(1, int(s))
        n_params += n
        if p.name.endswith(("_word_emb", "_pos_emb", "_sent_emb", "_mlm_out_b")):
            n_embed += n
        elif "_mlm_" in p.name:
            n_mlm += n
        elif "_pool" in p.name or "_nsp" in p.name:
            n_head += n
    n_enc = n_params - n_embed - n_mlm - n_head

    # CHUNK *distinct* batches, stacked on a leading axis and consumed one
    # per fori_loop iteration (Executor per_step_feed: a same-batch chunk
    # is a different HBM/infeed regime).  BENCH_FRESH=0
    # restores the old same-batch regime for A/B comparison.
    import bench_common

    fresh = bench_common.fresh_enabled()
    n_b = chunk if fresh else 1
    rng = np.random.RandomState(0)
    srcv = rng.randint(0, V, (n_b, batch, S)).astype(np.int32)
    sentv = rng.randint(0, 2, (n_b, batch, S)).astype(np.int32)
    maskv = np.ones((n_b, batch, S), np.float32)
    # flattened positions into [N*S]
    mposv = (
        np.arange(batch)[None, :, None] * S
        + rng.randint(0, S, (n_b, batch, masks))
    ).reshape(n_b, -1, 1).astype(np.int32)
    mlabv = rng.randint(0, V, (n_b, batch * masks, 1)).astype(np.int32)
    nlabv = rng.randint(0, 2, (n_b, batch, 1)).astype(np.int32)

    scope = fluid.Scope()
    exe = fluid.Executor(place)
    dev = exe._device()
    with fluid.scope_guard(scope):
        exe.run(startup)
        stacked = {
            "src": srcv, "sent": sentv, "mask": maskv,
            "mpos": mposv, "mlab": mlabv, "nlab": nlabv,
        }
        feed, feed1, run_kw = bench_common.stage_feeds(
            stacked, fresh, chunk, dev)
        # warmup: 2 single-step runs settle the state avals, then one
        # chunked (steps=CHUNK fori_loop) call compiles the timed module
        for _ in range(2):
            (l,) = exe.run(prog, feed=feed1, fetch_list=[total], return_numpy=False)
            np.asarray(l)
        (l,) = exe.run(prog, feed=feed, fetch_list=[total], **run_kw)
        np.asarray(l)
        done = 0
        t0 = time.perf_counter()
        while done < steps:
            (l,) = exe.run(prog, feed=feed, fetch_list=[total], **run_kw)
            done += chunk
            lv = np.asarray(l)
        dt = time.perf_counter() - t0

    step_time = dt / done
    tokens = batch * S
    flops = (
        6.0 * n_enc * tokens
        + 6.0 * (n_mlm + D * V) * batch * masks
        + 6.0 * n_head * batch
        + 12.0 * L * batch * S * S * D
    )
    mfu = (flops / step_time) / device_peaks.peak_flops(dev)
    return {
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens / step_time, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.50, 4),
        "step_time_ms": round(step_time * 1e3, 2),
        "mfu": round(mfu, 4),
        "batch": batch,
        "seq_len": S,
        "n_params": n_params,
        "n_embed_params": n_embed,
        "per_step_feed": fresh,
        "chunk": chunk,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "loss": float(lv),
    }


def main():
    print(json.dumps(run()))


if __name__ == "__main__":
    main()
