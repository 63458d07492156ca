"""Family ``bert_pretrain``: BERT pretraining (masked LM + next
sentence, Adam under bf16 AMP) built by ``models.bert_pretrain`` and run
through ``Executor(TPUPlace(0)).run(steps=, per_step_feed=True)``, the
way ``bench_bert.py`` and ``chip_smoke.train_leg`` do.

From the program: the model builder, the optimizer decoration, the
Executor and ``jit_cache_stats()``.  The batches, the clock, the FLOP count and the
comparison with the reference are the benchmark's own.
"""
from __future__ import annotations

import math
import os
import time

import numpy as np

from benchmark.lib import costs, harness, traffic


def build(cfg, seq_len):
    """(train program, startup, forward-only test clone, loss var)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, models

    dropout = float(cfg["hidden_dropout_prob"])
    if dropout != float(cfg["attention_probs_dropout_prob"]):
        raise ValueError("models.bert_pretrain takes ONE dropout rate: the "
                         "config's two dropout keys must agree")
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = int(
        cfg["assumed"]["program_seed"])
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [seq_len], dtype="int64")
        sent = fluid.layers.data("sent", [seq_len], dtype="int64")
        mask = fluid.layers.data("mask", [seq_len])
        mpos = fluid.layers.data("mpos", [1], dtype="int64")
        mlab = fluid.layers.data("mlab", [1], dtype="int64")
        nlab = fluid.layers.data("nlab", [1], dtype="int64")
        total, _, _ = models.bert_pretrain(
            src, sent, mask, mpos, mlab, nlab,
            vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_layer=cfg["num_hidden_layers"],
            n_head=cfg["num_attention_heads"],
            d_inner=cfg["intermediate_size"], seq_len=seq_len,
            max_pos=cfg["max_position_embeddings"],
            dropout_rate=dropout)
        test_prog = prog.clone(for_test=True)  # before the optimizer ops
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(1e-4))
        opt.minimize(total)
    return prog, startup, test_prog, total


def check_tensors(test_prog):
    """Names of the forward's tensors that the reference also returns,
    read off the test clone's ops: the logits of the two cross-entropy
    ops (masked LM first, next sentence second) and the encoder output,
    which is what the [CLS] ``slice`` reads."""
    ops = test_prog.global_block().ops
    xent = [op for op in ops if op.type == "softmax_with_cross_entropy"]
    cls = [op for op in ops if op.type == "slice"]
    if len(xent) != 2 or len(cls) != 1:
        raise RuntimeError("bert_pretrain's forward no longer has two "
                           "cross-entropy ops and one slice: %d, %d"
                           % (len(xent), len(cls)))
    return {"encoder_out": cls[0].input("Input")[0],
            "mlm_logits": xent[0].input("Logits")[0],
            "nsp_logits": xent[1].input("Logits")[0]}


def check_against_reference(ctx, exe, scope, test_prog, total, mix):
    """The program's forward on a few seeded, padded sequences, from the
    scope's own weights, against the reference's: tensor by tensor, each
    held to the bounds in the config's ``check.tensors``.  (ok, details)."""
    import jax

    cfg = ctx.cfg
    chk = cfg["check"]
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_" + cfg["name"])
    small = dict(mix, steps_per_chunk=1, batch=int(chk["sequences"]),
                 min_len_share=float(chk["min_len_share"]))
    one = {k: v[0] for k, v in traffic.train_batches(
        small, ctx.seed + 1, int(cfg["vocab_size"])).items()}
    feed = {k: jax.device_put(v, ctx.device) for k, v in one.items()}
    names = check_tensors(test_prog)
    block = test_prog.global_block()
    held = sorted(chk["tensors"])  # the tensors the config bounds
    fetched = exe.run(test_prog, feed=feed, return_numpy=False,
                      fetch_list=[total] + [block.var(names[k])
                                            for k in held])
    got_loss = float(np.asarray(fetched[0]))
    got = {k: np.asarray(v) for k, v in zip(held, fetched[1:])}
    weights = {p.name: scope.get(p.name)
               for p in test_prog.all_parameters()}
    layers, heads = (int(cfg[k]) for k in ("num_hidden_layers",
                                           "num_attention_heads"))
    eps = float(cfg["layer_norm_eps"])
    want, want_loss = jax.jit(lambda w, b: (
        ref.forward(w, b, layers, heads, eps),
        ref.loss(w, b, layers, heads, eps)))(weights, feed)
    want_loss = float(np.asarray(want_loss))
    ok = math.isfinite(got_loss) and math.isfinite(want_loss)
    info = {"sequences": small["batch"], "program_loss": got_loss,
            "reference_loss": want_loss,
            "real_tokens": int(one["mask"].sum())}
    for name in held:
        bounds = chk["tensors"][name]
        have = got[name].reshape(np.shape(want[name]))
        found = dict(zip(("rel_rms", "worst_gap_share"),
                         ref.gaps(have, want[name])))
        for key, allowed in sorted(bounds.items()):
            info["%s.%s" % (name, key)] = found[key]
            info["%s.%s_allowed" % (name, key)] = allowed
            ok = ok and math.isfinite(found[key]) and found[key] <= allowed
    return ok, info


def run(ctx):
    import jax

    import paddle_tpu as fluid

    cfg, mix = ctx.cfg, ctx.mix
    if mix["kind"] != "train_chunks":
        raise ValueError("family bert_pretrain cannot drive a %r mix"
                         % mix["kind"])
    seq, batch, chunk = (int(mix[k]) for k in (
        "seq_len", "batch", "steps_per_chunk"))
    with ctx.phase("build"):
        prog, startup, test_prog, total = build(cfg, seq)
        place = fluid.CPUPlace() if ctx.rehearse else fluid.TPUPlace(0)
        exe = fluid.Executor(place)
        scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with ctx.phase("weights"):
            exe.run(startup)
        with ctx.phase("inputs"):
            stacked = traffic.train_batches(mix, ctx.seed,
                                            int(cfg["vocab_size"]))
            # the chunk's 32 distinct batches are staged on the device
            # once (``bench_common.stage_feeds``' fresh regime): each
            # step still reads its own batch, and no host thread runs
            # beside the dispatching one
            feed = {k: jax.device_put(v, ctx.device)
                    for k, v in stacked.items()}
            feed1 = {k: v[0] for k, v in feed.items()}

        def run_chunk():
            with ctx.annotate("bench/chunk_dispatch"):
                (l,) = exe.run(prog, feed=feed, fetch_list=[total],
                               return_numpy=False, steps=chunk,
                               per_step_feed=True)
            with ctx.annotate("bench/loss_d2h"):
                return float(np.asarray(l))

        with ctx.phase("compile_or_cache_load"):
            # two single steps settle the state's types, then one
            # chunk builds the module the window runs
            for _ in range(2):
                (l,) = exe.run(prog, feed=feed1, fetch_list=[total],
                               return_numpy=False)
                np.asarray(l)
            losses = [run_chunk()]
            if ctx.tracer.enabled:
                # trace two whole dispatches and the gap between them
                t_chunk = time.perf_counter()
                losses.append(run_chunk())
                ctx.tracer.tail_s = 1.9 * (time.perf_counter() - t_chunk)
        misses0 = exe.jit_cache_stats()["misses"]
        w0 = ctx.open_window()
        w1 = w0 + ctx.seconds
        ends = []
        while time.perf_counter() < w1:
            ctx.tracer.maybe_start(w1)
            losses.append(run_chunk())
            ends.append(time.perf_counter())
        t1 = ends[-1]
        ctx.close_window(t1)
        new_misses = exe.jit_cache_stats()["misses"] - misses0
        ref_ok, ref_info = check_against_reference(
            ctx, exe, scope, test_prog, total, mix)

    steps = len(ends) * chunk
    elapsed = t1 - w0
    checks = {
        "reference": ref_ok,
        "finite_loss": bool(np.isfinite(losses).all()),
        "no_window_compiles": (ctx.window["compiles"]["compiles"] == 0
                               and new_misses == 0),
    }
    flops = costs.bert_train_flops_per_step(
        cfg, batch, seq, int(mix["masks_per_seq"]))
    ctx.say("training", chunks_in_window=len(ends), steps_in_window=steps,
            window_s=elapsed, first_loss=losses[0], final_loss=losses[-1],
            tokens_per_step=batch * seq, jit_misses_in_window=new_misses,
            mfu_host_window=(None if ctx.peaks is None else flops * steps
                             / elapsed / ctx.peaks["bf16_flops_per_s"]),
            chunk_s=[round(b - a, 4) for a, b in
                     zip([w0] + ends[:-1], ends)])
    ctx.say("reference_check", **ref_info)
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": steps * batch * seq / elapsed},
        "counters": {"window_s": elapsed, "steps": steps,
                     "steps_per_dispatch": chunk,
                     "flops_per_step": flops,
                     "attention_score_shape": [batch, int(
                         cfg["num_attention_heads"]), seq, seq]},
    }
