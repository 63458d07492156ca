"""Family ``pooled_sparse_linear_lm``: a decoder of lightning
linear-attention layers beside block-sparse attention layers
(``model_type: minicpm_sala``) served through
``decoding.make_sparse_linear_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` (continuous batching over ONE slot pool
that holds K/V rows, compressed-key rows and recurrent lightning state
side by side, a chunked prefill, and a prefix cache of device snapshots),
under the ``closed_loop_shared_docs`` traffic kind.

From the program this file takes the system under test and nothing
else: the parameter names and shapes (``sparse_linear_lm.param_shapes``),
the step builder, the server, its monitor series
(``serving_decode_{tokens,prefill_tokens,ticks,prefill_chunks,
sparse_positions_read,sparse_positions_live}_total``,
``serving_prefix_cache_{hits,misses}_total``,
``serving_prefix_snapshots_total``, ``serving_{kv_cache,
recurrent_state}_bytes``) and, for the device trace, the shapes only the
two new layers' tensors have.  Lengths, the corpus, stamps, the bytes a
step needs (``lib/costs_sparse_linear``) and the comparison that decides
``correct`` (the configuration's reference beside its file) are the
benchmark's own; the window's loop is ``lib/pooled_window``.

Before the callers start, ONE pilot request per document goes through
the server's normal path: each misses the prefix cache, is prefilled in
chunks and leaves its snapshot; all of that is ``setup_s``.  ``correct``
then holds, besides the reference comparison: every request admitted in
the window was a prefix hit (a miss would put a 20k-token prefill into
the window), every sampled request sat in a slot another request had
left, and every branch of every layer is at least
``check.min_branch_share`` of the residual it is added to.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmark.lib import costs_sparse_linear as costs
from benchmark.lib import harness, loadgen, pooled_window, traffic
from benchmark.lib.traffic_shared_docs import SharedDocsSource

_drain = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_decode_lm.py"),
    "pooled_decode_lm")._drain


def builder():
    """The program's step builder and parts module, or a clean exit
    where the program has none (a commit before PR 31)."""
    from paddle_tpu import decoding
    try:
        from paddle_tpu import sparse_linear_lm
        return decoding.make_sparse_linear_lm_pooled_step_fn, sparse_linear_lm
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve a "
                         "minicpm_sala decoder (%s)" % exc)


def make_weights(cfg, device, parts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed: matrices normal(0, initializer_range)
    in bf16 as they are served, norm vectors 1 in float32 — but the
    sparse layers' ``q_norm`` at ``assumed.sparse_q_norm_weight``."""
    import jax
    import jax.numpy as jnp

    shapes = parts.param_shapes(cfg)
    names = sorted(shapes)
    a = cfg["assumed"]
    std, q_w = float(a["initializer_range"]), float(a["sparse_q_norm_weight"])
    sparse = {"lm_l%d_q_norm" % i for i, kind in enumerate(cfg["mixer_types"])
              if kind == parts.SPARSE}

    def make(key):
        out = {}
        for i, n in enumerate(names):
            if len(shapes[n]) == 1:
                out[n] = jnp.full(shapes[n], q_w if n in sparse else 1.0,
                                  jnp.float32)
            else:
                out[n] = (jax.random.normal(
                    jax.random.fold_in(key, i), shapes[n], jnp.bfloat16)
                    * std).astype(jnp.bfloat16)
        return out

    with jax.default_device(device):
        state = jax.jit(make)(jax.random.PRNGKey(int(a["weight_seed"])))
    jax.block_until_ready(state)
    return state


def check_against_reference(ctx, state, kept, slots=None):
    """Snapshot admission + decode through the pool against the
    reference's full forward of the WHOLE prompt (no cache, no snapshot),
    on the sample of served requests that kept their tokens: ``kept`` is
    ``[(prompt ids, generated ids, requests sent before it)]``.
    Returns (ok, details)."""
    import jax
    import jax.numpy as jnp

    cfg, chk = ctx.cfg, ctx.cfg["check"]
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_" + cfg["name"])
    share_max = float(chk["logit_gap_share"])
    mean_max = float(chk["mean_logit_gap_share"])
    if not kept:
        return False, {"why": "no finished request kept its tokens"}
    s_ref, rows = int(chk["reference_len"]), int(chk["head_rows"])
    kinds = list(cfg["mixer_types"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    rcfg["sparse_config"] = cfg["assumed"]["sparse_config"]
    embed = jax.jit(lambda w, t: ref.embed(w, t, rcfg))
    # one program per KIND of layer: each layer's weights go in under
    # layer 0's names
    block = {kind: jax.jit(lambda w, h, kind=kind: ref.block(
        w, 0, kind, h, rcfg, mlp_blocks=int(chk["mlp_blocks"]),
        query_block=int(chk["query_block"]))) for kind in set(kinds)}
    stats = jax.jit(lambda w, h, t: ref.head_stats(
        w, h, t, rcfg, int(chk["vocab_blocks"])))
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(len(kinds))]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, hits, finite = [], 0, True
    shares = np.zeros((len(kinds), 2))
    for prompt, got, _ in kept:
        toks = np.zeros((s_ref,), np.int32)
        toks[:len(prompt)] = prompt
        toks[len(prompt):len(prompt) + len(got)] = got
        h = embed(ends, jax.device_put(toks, ctx.device))
        for i, kind in enumerate(kinds):
            h, sh = block[kind](by_layer[i], h)
            shares[i] += np.asarray(sh) / len(kept)
        # position s predicts the token at s + 1: the rows that
        # predicted the served tokens, padded to a fixed count
        at = np.minimum(len(prompt) - 1 + np.arange(rows), s_ref - 2)
        hi, lo, arg, val = (np.asarray(x) for x in stats(
            ends, h[jnp.asarray(at)], jnp.asarray(toks[at + 1])))
        n = len(got)
        gap = (hi[:n] - val[:n]) / (hi[:n] - lo[:n])
        finite = finite and bool(np.isfinite(gap).all())
        gaps.append(gap)
        hits += int((arg[:n] == got).sum())
        del h
    reused = sum(1 for _, _, before in kept
                 if slots is not None and before >= slots)
    branch_min = float(shares.min())
    gaps = np.concatenate(gaps)
    worst, mean, n_tok = float(gaps.max()), float(gaps.mean()), len(gaps)
    ok = (finite and worst <= share_max and mean <= mean_max
          and reused == len(kept)
          and branch_min >= float(chk["min_branch_share"]))
    return ok, {"requests": len(kept), "tokens": n_tok,
                "prompt_lens": [len(p) for p, _, _ in kept],
                "argmax_agreement": "%d/%d" % (hits, n_tok),
                "mean_logit_gap_share": mean,
                "mean_logit_gap_share_allowed": mean_max,
                "p99_logit_gap_share": float(np.percentile(gaps, 99)),
                "worst_logit_gap_share": worst,
                "logit_gap_share_allowed": share_max,
                "in_reused_slots": reused,
                "branch_share_of_residual": {
                    "layers_x_[mixer,mlp]": np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def make_server(cfg, state, build):
    """The cell's ``DecodeServer``: what ``run`` measures and what the
    harmed-variant test serves through."""
    from paddle_tpu.serving.decode import DecodeServer

    sv = cfg["serving"]
    step_fn, make_cache, _ = build(
        state, cfg, kv_dtype=sv["kv_dtype"],
        state_dtype=cfg["assumed"]["lightning_state_dtype"],
        prefill_tokens=int(sv["prefill_tokens"]))
    return DecodeServer(
        step_fn, make_cache, eos_id=int(cfg["vocab_size"]),
        max_seq_len=sv["max_seq_len"], max_slots=sv["slot_ladder"][-1],
        slot_ladder=tuple(sv["slot_ladder"]),
        len_ladder=tuple(sv["len_ladder"]),
        steps_per_tick=sv["steps_per_tick"],
        queue_capacity=sv["queue_capacity"],
        target_queue_wait_ms=sv["target_queue_wait_ms"],
        prefix_cache=int(sv["prefix_cache_bytes"]),
        kv_dtype=sv["kv_dtype"], name="bench-" + cfg["name"])


def run_pilots(srv, source, mix, timeout_s=900.0):
    """One request per document through the normal path: each misses,
    is prefilled in chunks and leaves its snapshot.  Returns the seconds
    it took."""
    t0 = time.perf_counter()
    pilots = [srv.submit(
        {"tokens": np.concatenate(
            [doc, source.question(int(mix["pilot_question_tokens"]))])},
        max_new_tokens=int(mix["pilot_output_tokens"]))
        for doc in source.documents]
    for p in pilots:
        p.result(timeout_s)
    return time.perf_counter() - t0


def run(ctx):
    build, parts = builder()
    from paddle_tpu import monitor

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    if mix["kind"] != "closed_loop_shared_docs":
        raise ValueError("family pooled_sparse_linear_lm cannot drive a "
                         "%r mix" % mix["kind"])
    vocab = int(cfg["vocab_size"])
    slots = int(sv["slot_ladder"][-1])
    with ctx.phase("weights"):
        state = make_weights(cfg, ctx.device, parts)
    with ctx.phase("build"):
        srv = make_server(cfg, state, build)
    load = None
    try:
        with ctx.phase("compile_or_cache_load"):
            warm_compiles = srv.warmup()

        def counters_now():
            out = {k: monitor.counter_value("serving_decode_%s_total" % k)
                   for k in ("tokens", "prefill_tokens", "ticks",
                             "prefill_chunks", "sparse_positions_read",
                             "sparse_positions_live", "admitted")}
            for k in ("hits", "misses"):
                out["prefix_" + k] = monitor.counter_value(
                    "serving_prefix_cache_%s_total" % k)
            out["snapshots"] = monitor.counter_value(
                "serving_prefix_snapshots_total")
            out["kv_bytes"] = monitor.counter_value("serving_kv_cache_bytes")
            out["recurrent_bytes"] = monitor.counter_value(
                "serving_recurrent_state_bytes")
            return out

        load = loadgen.LoadRun(
            submit=lambda p, n: srv.submit({"tokens": p}, max_new_tokens=n),
            drain=_drain,
            produced=lambda: monitor.counter_value(
                "serving_decode_tokens_total"),
            annotate=ctx.annotate)
        chk = cfg["check"]
        source = SharedDocsSource(mix, ctx.seed, vocab)
        with ctx.phase("document_prefill"):
            # brings the pool's state to the device, prefills every
            # document once and leaves its snapshot
            pilot_s = run_pilots(srv, source, mix)
            after_pilots = counters_now()
        with ctx.phase("ramp"):
            t_ramp = time.perf_counter()
            prompts = source.prompts  # grows as the clients draw
            load.start_closed_loop(source, int(mix["clients"]),
                                   chk["sample_requests"],
                                   chk["sample_max_total"])
            time.sleep(max(0.0, t_ramp + float(mix["ramp_s"])
                           - time.perf_counter()))
        c0, c1, w0, t1 = pooled_window.measure(ctx, counters_now)
        metrics = srv.metrics()
        load.stop()
        ctx.close_window(t1)
    finally:
        if load is not None:
            load.halt()
        # a stopped server drops its pool and its snapshots: the
        # reference needs the room
        srv.stop(drain=False, timeout=60.0)

    s = loadgen.summarize(load.records, load.token_events, w0, t1,
                          mix.get("limits"))
    # idx counts the load's requests; the pilots went before them all
    kept = [(prompts[r.idx], np.concatenate(r.tokens).astype(np.int32),
             r.idx + len(source.documents))
            for r in load.records
            if r.keep and r.status == "done" and r.n_tok == r.output_len]
    with ctx.annotate("bench/reference_check"):
        ref_ok, ref_info = check_against_reference(ctx, state, kept, slots)
    stamps = loadgen.stamp_faults(load.records, load.sweeps, w0, t1)
    delta = {k: c1[k] - c0[k] for k in c0}
    ticks = delta["ticks"]
    steps = ticks * sv["steps_per_tick"]
    n_docs = len(source.documents)
    doc_tokens = int(sum(len(d) for d in source.documents))
    checks = {
        "reference": ref_ok,
        "no_window_compiles": ctx.window["compiles"]["compiles"] == 0,
        "no_server_recompiles": metrics["recompiles"] == 0,
        "served_something": s["tokens_delivered"] > 0 and s["attempted"] > 0,
        "no_failed_requests": s["failed"] == 0,
        "stamps_in_time": stamps["ok"],
        # every document left exactly one snapshot, in set-up
        "one_snapshot_a_document": after_pilots["snapshots"] == n_docs
        and c1["snapshots"] == n_docs,
        # ... and every admission of the window was seated over one
        "every_window_admission_a_prefix_hit": delta["prefix_misses"] == 0
        and delta["prefix_hits"] == delta["admitted"] > 0
        and delta["prefill_chunks"] == 0,
        # the program's gauges against the benchmark's own arithmetic
        "pool_bytes_as_computed": c1["recurrent_bytes"]
        == costs.lightning_state_bytes_per_slot(cfg) * slots
        and c1["kv_bytes"] == costs.sequence_bytes_per_slot(
            cfg, int(sv["len_ladder"][-1])) * slots,
    }
    ctx.say("requests", sent_total=len(load.records),
            sent_in_window=s["sent_in_window"], ended_in_window=s["attempted"],
            failed_in_window=s["failed"],
            in_flight_at_close=s["in_flight_at_close"],
            refused_total=sum(r.status == "refused" for r in load.records),
            first_failures=[r.tokens for r in load.records
                            if r.status in ("failed", "refused")][:3],
            prompt_len_done=traffic.length_summary(s["prompt_len_done"]),
            output_len_done=traffic.length_summary(s["output_len_done"]),
            stamps=dict(stamps, sweep_delay_s=load.delay_s),
            tick_ms=s["window_s"] * 1e3 / ticks if ticks else None,
            warmup_compiles=int(warm_compiles),
            document_prefill=dict(
                seconds=pilot_s, documents=n_docs, tokens=doc_tokens,
                chunks=after_pilots["prefill_chunks"],
                tokens_per_s=doc_tokens / pilot_s if pilot_s else None),
            window_counters=delta,
            kv_cache_bytes=c1["kv_bytes"],
            recurrent_state_bytes=c1["recurrent_bytes"],
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    per_step = lambda v: v / steps if steps else 0.0
    rows = per_step(delta["tokens"] + delta["prefill_tokens"])
    read = per_step(delta["sparse_positions_read"])
    live = per_step(delta["sparse_positions_live"])
    d = parts.dims(cfg)
    rung = int(sv["len_ladder"][-1])
    counters = {
        "window_s": s["window_s"],
        "steps": steps, "ticks": ticks,
        "steps_per_dispatch": sv["steps_per_tick"],
        "generated_tokens": delta["tokens"],
        "prefill_tokens": delta["prefill_tokens"],
        "rows_stepped_per_step": rows,
        "in_flight_at_close": s["in_flight_at_close"],
        "queue_depth_at_close": metrics["queue_depth"],
        "kv_cache_bytes": c1["kv_bytes"],
        "recurrent_state_bytes": c1["recurrent_bytes"],
        "sparse_positions_read": delta["sparse_positions_read"],
        "sparse_positions_live": delta["sparse_positions_live"],
        "linear_state_scopes": [parts.LINEAR_STATE_SCOPE],
        "linear_state_shapes": [
            [slots, d.l_heads, d.l_head_dim, d.l_head_dim]],
        "sparse_scopes": [parts.SPARSE_SELECT_SCOPE,
                          parts.SPARSE_ATTEND_SCOPE],
        # the K/V leaf, the compressed-key leaf, and what the select and
        # the attend make of them: gathered blocks, scores, relevances
        "sparse_shapes": [
            [slots, rung, d.d_kv], [slots, rung // d.kernel_stride, d.d_kv],
            [slots, rung // d.block_size, d.block_size, d.n_kv_head,
             d.head_dim],
            [slots, d.n_kv_head, d.n_sel, d.block_size, d.head_dim],
            [slots, d.n_kv_head, d.n_head // d.n_kv_head, d.n_sel,
             d.block_size],
            [slots, d.n_kv_head, d.n_head // d.n_kv_head,
             rung // d.kernel_stride],
            [slots, d.n_kv_head, rung // d.block_size]],
        "linear_state_min_bytes": costs.linear_state_min_bytes(cfg, rows),
        "sparse_min_bytes": costs.sparse_min_bytes(cfg, read, rows, live),
        "step_min_bytes": costs.step_min_bytes(cfg, rows, read, live),
    }
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
