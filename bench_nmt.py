"""Benchmark: Transformer-NMT training step (BASELINE config 4 —
variable-length seq2seq, the LoDTensor-equivalent padded+mask encoding).

Variable lengths are the bucketed-padding story: each batch row carries a
real length; src_mask feeds the encoder/cross attention bias and the
reported tokens/sec counts REAL (unpadded) tokens, while MFU charges the
padded work the chip actually executes (honest accounting both ways).

Role-split MFU like bench_bert.py: embedding gathers 0; per-token matmul
params x 6 x padded tokens; attention 12*L*B*S^2*D for encoder self,
decoder self (causal), and cross attention.
"""
import os
import time

import numpy as np

# bs128 / 64+64 / chunk=80 is the regime BENCH_r05.json's nmt block
# (34.5% MFU, 44.63 ms/step) was recorded in.
BATCH = int(os.environ.get("BENCH_NMT_BATCH", "128"))
SRC_LEN = int(os.environ.get("BENCH_NMT_SRC", "64"))
TGT_LEN = int(os.environ.get("BENCH_NMT_TGT", "64"))
STEPS = int(os.environ.get("BENCH_NMT_STEPS", "160"))
CHUNK = int(os.environ.get("BENCH_NMT_CHUNK", "80"))


def run(batch=BATCH, src_len=SRC_LEN, tgt_len=TGT_LEN, steps=STEPS, chunk=CHUNK):
    import paddle_tpu as fluid
    from paddle_tpu import device_peaks, framework, models

    place = fluid.TPUPlace(0)  # a chip bench: no chip, no run
    use_amp = os.environ.get("BENCH_AMP", "1") == "1"

    V, D, L, H, DI = 32000, 512, 6, 8, 2048
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 42
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [src_len], dtype="int64")
        tgt = fluid.layers.data("tgt", [tgt_len], dtype="int64")
        lbl = fluid.layers.data("lbl", [tgt_len, 1], dtype="int64")
        smask = fluid.layers.data("smask", [src_len])
        avg_loss, _ = models.seq2seq.transformer_nmt(
            src, tgt, lbl, src_mask=smask, src_vocab=V, tgt_vocab=V,
            d_model=D, n_layer=L, n_head=H, d_inner=DI,
            src_len=src_len, tgt_len=tgt_len, dropout_rate=0.0,
        )
        opt = fluid.optimizer.AdamOptimizer(1e-4)
        if use_amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(avg_loss)

    # role split: embeddings gather; head matmuls tgt tokens; encoder
    # blocks matmul src tokens; decoder blocks matmul tgt tokens — EXCEPT
    # the cross-attention K/V projections, which consume the encoder
    # output (src tokens)
    n_enc = n_dec = n_head_p = n_cross_kv = 0
    for p in prog.all_parameters():
        n = int(np.prod([max(1, int(s)) for s in p.shape]))
        if "_emb" in p.name:
            continue
        if "_head" in p.name:
            n_head_p += n
        elif "_cross_k" in p.name or "_cross_v" in p.name:
            n_cross_kv += n
        elif "_enc_" in p.name or "_src" in p.name:
            n_enc += n
        else:
            n_dec += n

    # chunk distinct batches per jitted call (per_step_feed);
    # BENCH_FRESH=0 restores the same-batch regime
    import bench_common

    fresh = bench_common.fresh_enabled()
    n_b = chunk if fresh else 1
    rng = np.random.RandomState(0)
    srcv = rng.randint(0, V, (n_b, batch, src_len)).astype(np.int32)
    tgtv = rng.randint(0, V, (n_b, batch, tgt_len)).astype(np.int32)
    lblv = rng.randint(0, V, (n_b, batch, tgt_len, 1)).astype(np.int32)
    # variable lengths: uniform in [src_len//2, src_len]
    src_lens = rng.randint(src_len // 2, src_len + 1, (n_b, batch))
    smaskv = (np.arange(src_len)[None, None, :]
              < src_lens[:, :, None]).astype(np.float32)

    scope = fluid.Scope()
    exe = fluid.Executor(place)
    dev = exe._device()
    with fluid.scope_guard(scope):
        exe.run(startup)
        stacked = {"src": srcv, "tgt": tgtv, "lbl": lblv, "smask": smaskv}
        feed, feed1, run_kw = bench_common.stage_feeds(
            stacked, fresh, chunk, dev)
        for _ in range(2):
            (l,) = exe.run(prog, feed=feed1, fetch_list=[avg_loss], return_numpy=False)
            np.asarray(l)
        (l,) = exe.run(prog, feed=feed, fetch_list=[avg_loss], **run_kw)
        np.asarray(l)
        done = 0
        t0 = time.perf_counter()
        while done < steps:
            (l,) = exe.run(prog, feed=feed, fetch_list=[avg_loss], **run_kw)
            done += chunk
            lv = np.asarray(l)
        dt = time.perf_counter() - t0

    step_time = dt / done
    src_tok, tgt_tok = batch * src_len, batch * tgt_len
    real_tokens = int(src_lens.sum() / n_b) + tgt_tok  # per-step mean
    flops = (
        6.0 * (n_enc + n_cross_kv) * src_tok
        + 6.0 * (n_dec + n_head_p) * tgt_tok
        + 12.0 * L * batch * src_len * src_len * D      # encoder self
        + 12.0 * L * batch * tgt_len * tgt_len * D      # decoder self
        + 12.0 * L * batch * tgt_len * src_len * D      # cross
    )
    mfu = (flops / step_time) / device_peaks.peak_flops(dev)
    return {
        "metric": "transformer_nmt_tokens_per_sec_per_chip",
        "value": round(real_tokens / step_time, 1),
        "unit": "tokens/sec",
        "step_time_ms": round(step_time * 1e3, 2),
        "mfu": round(mfu, 4),
        "batch": batch,
        "src_len": src_len,
        "tgt_len": tgt_len,
        "per_step_feed": fresh,
        "chunk": chunk,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "loss": float(lv),
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run()))
