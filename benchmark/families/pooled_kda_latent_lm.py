"""Family ``pooled_kda_latent_lm``: a decoder of Kimi Delta Attention
layers (a gated delta rule whose decay is a factor a key CHANNEL: a
recurrent state and a conv window a slot) beside multi-head LATENT
attention read DENSELY and position-free (one compressed row a
position), a leading dense FFN and routed experts beside a shared expert
(``model_type: kimi_linear``), served through
``decoding.make_kda_latent_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` with a prefix cache of device snapshots
(continuous batching over ONE slot pool whose layers hold different
leaves; a chunked prefill that runs the delta rule in its CHUNKWISE form;
a prefix kept as a slot's whole row: the delta state, the conv window and
the latent rows together), a share of the experts held, under the
``closed_loop_shared_docs`` traffic kind.

From the program this file takes the system under test and nothing
else: the parameter names and shapes (``kda_latent_lm.param_shapes``),
the shape the state leaf is declared in, the step builder, the server,
its monitor series (``serving_decode_{tokens,prefill_tokens,ticks,
prefill_chunks,admitted,state_resets,kv_positions_read,kv_positions_live,
index_positions_scored,latent_positions_selected,expert_assignments,
experts_touched,expert_peak_load,expert_layer_steps}_total``,
``serving_spec_rounds_total``, ``serving_prefix_cache_{hits,misses}
_total``, ``serving_prefix_snapshots_total``, ``serving_{kv_cache,
recurrent_state}_bytes``, ``delta_update_{lowered,decay}_total``), the
dense read's host mirror of what its lowering touches
(``decode_attention.dense_latent_positions_read``), the scopes' names
and, in the device trace, the kernels' names and the shapes only one kind
of layer's tensors have.  Lengths, the corpus, stamps, the bytes and
FLOPs a step needs (``lib/costs_kda_latent``) and the comparison that
decides ``correct`` (the configuration's reference beside its file) are
the benchmark's own; the window's loop is ``lib/pooled_window``; the
pilots, the weights' maker and the check's shape follow
``pooled_latent_sparse_lm`` and ``pooled_kda_routed_lm``, whose helpers
this file imports.

Before the callers start, ONE pilot request per document goes through
the server's normal path: each misses the prefix cache, is prefilled in
chunks (a K layer by the chunkwise form, an M layer expanded) and leaves
its snapshot; all of that is ``setup_s``, and the server's prefill-token
counter over that phase's seconds LESS the pool's birth (the server makes
its pool at the first admission, inside the phase) is
``doc_prefill_tokens_per_s.setup``.
``correct`` then holds, besides the reference comparison — the served
tokens of a sample of requests against the reference's full forward of
the WHOLE prompt (document + question + answer; the rule a scan over
positions, attention expanded; float32 at "highest" on the operands the
configuration states, ``check.matmul_inputs``; a token's gap under TWO
bounds, mean and worst) —: every request admitted in the window was a
prefix hit and took no prefill chunk, every tick of the window was a
plain chunk (no round ran), every sampled request sat in a slot another
request had left over a context past ``check.min_context``, every branch
of every block is at least ``check.min_branch_share`` of the residual it
is added to, the pool's bytes are what the benchmark's own arithmetic
gives, the program's expert counters add up, the latent positions read
are the live positions of every row stepped in every M layer, and on a
TPU every delta update of a step took the kernel with a decay a channel.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmark.lib import costs_kda_latent as costs
from benchmark.lib import harness, loadgen, pooled_window, traffic
from benchmark.lib.traffic_shared_docs import SharedDocsSource

# what the families this one composes already have, taken from them
_sparse = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_latent_sparse_lm.py"),
    "pooled_latent_sparse_lm")
_kda = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_kda_routed_lm.py"),
    "pooled_kda_routed_lm")
_drain, run_pilots = _sparse._drain, _sparse.run_pilots
EXPERT_COUNTERS, held_of = _sparse.EXPERT_COUNTERS, _sparse.held_of
# one jitted block a kind of layer, built once a configuration: a layer's
# kind is (mixer, dense FFN or not) in this configuration's reference
_reference_programs = _kda._reference_programs


def builder():
    """The program's step builder, the dense read's host mirror and the
    parts modules (the schema's, the delta rule's), or a clean exit where
    the program has none (a commit before PR 63)."""
    try:
        from paddle_tpu import decoding, delta_hybrid_lm, kda_latent_lm
        from paddle_tpu.decode_attention import dense_latent_positions_read
        return ((decoding.make_kda_latent_lm_pooled_step_fn,
                 dense_latent_positions_read),
                (kda_latent_lm, delta_hybrid_lm))
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve a "
                         "kimi_linear decoder (%s)" % exc)


def make_weights(cfg, device, parts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed (``assumed.weights`` says why each
    scale): matrices in bf16 as they are served; norms, the conv kernel,
    ``A_log``, ``dt_bias``, the router and its bias in float32."""
    import jax
    import jax.numpy as jnp

    shapes = parts[0].param_shapes(cfg, held=held_of(cfg))
    names = sorted(shapes)
    a = cfg["assumed"]
    std, emb_std = float(a["initializer_range"]), float(a["embedding_std"])
    low_std, q_std = float(a["low_rank_out_std"]), float(a["attn_q_std"])
    router_std, bias = float(a["router_std"]), float(a["expert_bias_range"])
    a_lo, a_hi = (float(x) for x in a["a_log_range"])
    dt_lo, dt_hi = (float(x) for x in a["dt_range"])
    f32 = jnp.float32

    def make(key):
        out = {}
        for i, n in enumerate(names):
            k, shp = jax.random.fold_in(key, i), shapes[n]
            if n.endswith("norm"):
                out[n] = jnp.ones(shp, f32)
            elif n.endswith("lin_A_log"):
                out[n] = jnp.log(jax.random.uniform(k, shp, f32, a_lo, a_hi))
            elif n.endswith("lin_dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, shp, f32, np.log(dt_lo), np.log(dt_hi)))
                out[n] = dt + jnp.log(-jnp.expm1(-dt))
            elif n.endswith("lin_conv_w"):
                lim = 1.0 / np.sqrt(shp[0])
                out[n] = jax.random.uniform(k, shp, f32, -lim, lim)
            elif n.endswith("expert_bias"):
                out[n] = jax.random.uniform(k, shp, f32, -bias, bias)
            elif n.endswith("router"):
                out[n] = jax.random.normal(k, shp, f32) * router_std
            else:
                sd = (emb_std if n.endswith("_emb") else low_std
                      if n.endswith(("lin_fb", "lin_gb")) else q_std
                      if n.endswith("attn_q") else std)
                out[n] = (jax.random.normal(k, shp, jnp.bfloat16)
                          * sd).astype(jnp.bfloat16)
        return out

    with jax.default_device(device):
        state = jax.jit(make)(jax.random.PRNGKey(int(a["weight_seed"])))
    jax.block_until_ready(state)
    return state


def check_against_reference(ctx, state, kept, slots=None):
    """Chunk prefill + snapshot admission + decode through the pool
    against the reference's full forward of the WHOLE prompt and answer
    (the rule a scan, attention expanded, no cache, no snapshot), on the
    sample of served requests that kept their tokens: ``kept`` is
    ``[(prompt ids, generated ids, requests sent before it)]``.  Returns
    (ok, details)."""
    import jax
    import jax.numpy as jnp

    cfg, chk = ctx.cfg, ctx.cfg["check"]
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_" + cfg["name"])
    mean_max = float(chk["mean_gap_share"])
    worst_max = float(chk["worst_gap_share"])
    if not kept:
        return False, {"why": "no finished request kept its tokens"}
    s_ref = int(chk["reference_len"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items()
            if not isinstance(v, dict) or k == "linear_attn_config"}
    # the operands the configuration STATES are rounded are rounded in
    # the reference too; its arithmetic stays float32 at "highest"
    rcfg["matmul_inputs"] = chk.get("matmul_inputs")
    held = held_of(cfg)
    embed, blocks, stats = _reference_programs(ref, rcfg, chk, held)
    pairs = ref.kinds_of(rcfg)
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(len(pairs))]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, hits, finite = [], 0, True
    shares = np.zeros((len(pairs), 2))
    for prompt, got, _ in kept:
        n, p = len(got), len(prompt)
        toks = np.zeros((s_ref,), np.int32)
        toks[:p] = prompt
        toks[p:p + n] = got
        h = embed(ends, jax.device_put(toks, ctx.device))
        for i, pair in enumerate(pairs):
            h, sh = blocks[pair](by_layer[i], h)
            shares[i] += np.asarray(sh) / len(kept)
        # position s predicts the token at s + 1
        at = p - 1 + np.arange(n)
        hi, lo, arg, val = (np.asarray(x) for x in stats(
            ends, h[jnp.asarray(at)], jnp.asarray(got)))
        gap = (hi - val) / (hi - lo)
        finite = finite and bool(np.isfinite(gap).all())
        gaps.append(gap)
        hits += int((arg == got).sum())
        del h
    reused = sum(1 for k in kept if slots is not None and k[2] >= slots)
    past = sum(1 for k in kept if len(k[0]) > int(chk["min_context"]))
    branch_min = float(shares.min())
    gaps = np.concatenate(gaps)
    mean, worst = float(gaps.mean()), float(gaps.max())
    ok = (finite and mean <= mean_max and worst <= worst_max
          and reused == len(kept) and past == len(kept)
          and branch_min >= float(chk["min_branch_share"]))
    return ok, {"requests": len(kept), "tokens": int(gaps.size),
                "prompt_lens": [len(k[0]) for k in kept],
                "argmax_agreement": "%d/%d" % (hits, gaps.size),
                # a random-weight decoder that falls into a loop of a few
                # tokens shows nothing: said, not judged
                "distinct_tokens_per_answer": [
                    "%d/%d" % (len(set(k[1].tolist())), len(k[1]))
                    for k in kept],
                "mean_logit_gap_share": mean,
                "mean_gap_share_allowed": mean_max,
                "worst_logit_gap_share": worst,
                "worst_gap_share_allowed": worst_max,
                "gap_share_quantiles": {
                    q: float(np.quantile(gaps, float(q)))
                    for q in ("0.5", "0.9", "0.99")},
                "in_reused_slots": reused, "contexts_past_minimum": past,
                "branch_share_of_residual": {
                    "layers_x_[mixer,ffn]": np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def expert_counts_add_up(cfg, delta) -> bool:
    """``pooled_latent_sparse_lm``'s rule over the HELD experts (this
    configuration counts them under ``num_experts``)."""
    return _sparse.expert_counts_add_up(
        {"n_routed_experts": cfg["num_experts"]}, delta)


def make_server(cfg, state, build):
    """The cell's ``DecodeServer``: what ``run`` measures and what the
    harmed-variant test serves through (``pooled_latent_sparse_lm``'s: a
    chunked prefill and a prefix cache over the step builder)."""
    return _sparse.make_server(cfg, state, build[0])


def run(ctx):
    build, parts = builder()
    from paddle_tpu import grouped_matmul, monitor

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    if mix["kind"] != "closed_loop_shared_docs":
        raise ValueError("family pooled_kda_latent_lm cannot drive a %r mix"
                         % mix["kind"])
    vocab = int(cfg["vocab_size"])
    slots = int(sv["slot_ladder"][-1])
    rung = int(sv["len_ladder"][-1])
    chk = cfg["check"]
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
                             "prefill_chunks", "state_resets",
                             "kv_positions_read", "kv_positions_live",
                             "index_positions_scored",
                             "latent_positions_selected",
                             "admitted") + EXPERT_COUNTERS}
            out["spec_rounds"] = monitor.counter_value(
                "serving_spec_rounds_total")
            for k in ("hits", "misses"):
                out["prefix_" + k] = monitor.counter_value(
                    "serving_prefix_cache_%s_total" % k)
            out["snapshots"] = monitor.counter_value(
                "serving_prefix_snapshots_total")
            out["kv_bytes"] = monitor.counter_value("serving_kv_cache_bytes")
            out["recurrent_bytes"] = monitor.counter_value(
                "serving_recurrent_state_bytes")
            return out

        # the pool's birth (alloc + place): ``setup_pool_state_s``'s series
        pool_state_s = lambda: monitor.counter_value(
            "serving_pool_state_seconds_total")

        load = loadgen.LoadRun(
            submit=lambda p, n: srv.submit({"tokens": p}, max_new_tokens=n),
            drain=_drain,
            produced=lambda: monitor.counter_value(
                "serving_decode_tokens_total"),
            annotate=ctx.annotate)
        source = SharedDocsSource(mix, ctx.seed, vocab)
        before_pilots, unborn_s = counters_now(), pool_state_s()
        with ctx.phase("document_prefill"):
            # brings the pool's state to the device (the server allocates
            # it at its first admission), prefills every document once
            # (the delta rule in its chunkwise form) and leaves its
            # snapshot
            pilot_s = run_pilots(srv, source, mix, timeout_s=1800.0)
            after_pilots = counters_now()
        # the phase less the pool's birth, which ends when the first turn
        # (one chunk of 512 of the ~180,000 tokens) has delivered
        pool_born_s = pool_state_s() - unborn_s
        prefill_s = pilot_s - pool_born_s
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
    n_docs = len(source.documents)
    # idx counts the load's requests; the pilots went before them all
    kept = [(prompts[r.idx], np.concatenate(r.tokens).astype(np.int32),
             r.idx + n_docs)
            for r in load.records
            if r.keep and r.status == "done" and r.n_tok == r.output_len]
    with ctx.annotate("bench/reference_check"):
        ref_ok, ref_info = check_against_reference(ctx, state, kept, slots)
    stamps = loadgen.stamp_faults(load.records, load.sweeps, w0, t1)
    delta = {k: c1[k] - c0[k] for k in c0}
    ticks = delta["ticks"]
    steps = ticks * sv["steps_per_tick"]
    d = parts[0].dims(cfg)
    dh = parts[1]
    m_layers = sum(kind == parts[0].LATENT for kind in d.kinds)
    doc_tokens = int(sum(len(x) for x in source.documents))
    lowered = {path: monitor.counter_value("delta_update_lowered_total",
                                           path=path)
               for path in ("kernel", "xla", "chunk")}
    decay = {kind: monitor.counter_value("delta_update_decay_total",
                                         decay=kind)
             for kind in ("head", "channel")}
    on_tpu = ctx.device.platform == "tpu"
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
        # no round ran: every tick of the window a plain chunk
        "every_tick_plain": delta["spec_rounds"] == 0 and ticks > 0,
        # the program's gauges against the benchmark's own arithmetic
        "pool_bytes_as_computed": c1["recurrent_bytes"]
        == costs.recurrent_state_bytes_per_slot(cfg) * slots
        + costs.expert_stats_bytes(cfg)
        and c1["kv_bytes"]
        == costs.latent_bytes_per_position(cfg) * rung * slots,
        "expert_counts_add_up": expert_counts_add_up(cfg, delta),
        # a DENSE read: every row stepped read every live position, in
        # every M layer's leaf
        "latent_positions_read_are_the_live_ones":
        delta["latent_positions_selected"]
        == delta["index_positions_scored"]
        == delta["kv_positions_live"] * m_layers > 0,
        # a decay a channel; the prefill took the chunk form and, on the
        # chip, every step the kernel: a change cannot win the cell by
        # serving another rule or the slower form unseen
        "delta_rule_as_declared": decay["channel"] > 0 and not decay["head"]
        and lowered["chunk"] > 0
        and (not on_tpu or (lowered["kernel"] > 0 and not lowered["xla"])),
    }
    layer_steps = delta["expert_layer_steps"]
    n_sparse = len(d.expert_layers)
    n_held = held_of(cfg)[1] - held_of(cfg)[0]
    # per step, summed over the sparse layers
    touched = (delta["experts_touched"] / layer_steps * n_sparse
               if layer_steps else 0.0)
    prefill_counted = (after_pilots["prefill_tokens"]
                       - before_pilots["prefill_tokens"])
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
                seconds=pilot_s, pool_state_seconds=pool_born_s,
                documents=n_docs, tokens=doc_tokens,
                prefill_tokens_counted=prefill_counted,
                chunks=after_pilots["prefill_chunks"],
                tokens_per_s=(prefill_counted / prefill_s
                              if prefill_s > 0 else None)),
            window_counters=delta,
            kv_cache_bytes=c1["kv_bytes"],
            recurrent_state_bytes=c1["recurrent_bytes"],
            pool_bytes_computed=costs.pool_bytes(cfg, slots, rung),
            weight_bytes_computed=costs.weight_bytes_as_stored(cfg),
            delta_update_lowered=lowered, delta_update_decay=decay,
            experts_touched_per_layer_step=(
                delta["experts_touched"] / layer_steps if layer_steps
                else None),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    per_step = lambda v: v / steps if steps else 0.0
    rows = per_step(delta["tokens"] + delta["prefill_tokens"])
    # positions the rows of a step read, summed over rows and M layers
    # (ONE fresh row a slot: what is read is what must leave HBM)
    positions = per_step(delta["latent_positions_selected"])
    # what the read's lowering touched: the program's own mirror at the
    # server's counter (whole key blocks), in every M layer
    touched_positions = per_step(delta["kv_positions_read"]) * m_layers
    pairs = -(-slots * d.top_k // grouped_matmul.ROW_TILE) \
        * grouped_matmul.ROW_TILE
    lanes = costs.whole_tiles(d.d_latent)
    # positions a turn of the read's walk holds, by the lowering in force
    key_block = int(build[1](
        np.zeros(1, np.int64), rung, lanes=d.d_latent,
        dtype={"bf16": "bfloat16", "fp32": "float32"}[sv["kv_dtype"]],
        n_head=d.n_head, d_value=d.d_c)[0])
    counters = {
        "window_s": s["window_s"],
        "steps": steps, "ticks": ticks,
        "steps_per_dispatch": sv["steps_per_tick"],
        "generated_tokens": delta["tokens"],
        "prefill_tokens": delta["prefill_tokens"],
        "rows_stepped_per_step": rows,
        "in_flight_at_close": s["in_flight_at_close"],
        "queue_depth_at_close": metrics["queue_depth"],
        "state_resets": delta["state_resets"],
        "kv_cache_bytes": c1["kv_bytes"],
        "recurrent_state_bytes": c1["recurrent_bytes"],
        "index_positions_scored": delta["index_positions_scored"],
        "latent_positions_selected": delta["latent_positions_selected"],
        "delta_update_lowered": lowered, "delta_update_decay": decay,
        # what the chunk form did in set-up: the server's own counter
        # over the phase's seconds AFTER the pool's birth
        # (doc_prefill_tokens_per_s.setup)
        "doc_prefill_tokens": prefill_counted,
        "doc_prefill_seconds": max(prefill_s, 0.0),
        # the state leaf as the program declares it, the conv window
        # before and after the fresh row joins it
        "delta_state_scopes": [dh.DELTA_UPDATE_SCOPE],
        "delta_state_shapes": [[slots] + list(d.state_shape)],
        "short_conv_scopes": [dh.SHORT_CONV_SCOPE],
        "short_conv_shapes": [[slots, d.conv_len - 1, d.d_qkv],
                              [slots, d.conv_len, d.d_qkv]],
        # the two low-rank pairs' matrices and their intermediate, the
        # step gate's matrix: what only the making of the gates bears
        "channel_gate_scopes": [dh.CHANNEL_GATES_SCOPE],
        "channel_gate_shapes": [[d.d_model, d.d_rank], [d.d_rank, d.d_key],
                                [slots, d.d_rank], [d.d_model, d.lin_heads]],
        # the held experts: what the counters' groups are over
        "num_experts": n_held,
        "experts_touched_per_step": touched,
        "expert_kernel_names": [grouped_matmul.KERNEL_NAME],
        "expert_shapes": [[n_held, d.d_model, 2 * d.d_expert],
                          [n_held, d.d_expert, d.d_model],
                          [pairs, 2 * d.d_expert], [pairs, d.d_expert]],
        # needles of two or more dims only: a one-number needle such as
        # the sorted pairs' [768] or the held experts' [16] would ride
        # instructions that are not the routing's (PERF.md section 7,
        # After PR 55 (2), After PR 57 (2))
        "route_shapes": [[slots, d.n_expert], [slots, d.top_k],
                         [slots, d.top_k, d.d_model],
                         [slots * d.top_k, n_held], [pairs, d.d_model]],
        "shared_expert_scopes": [],
        "shared_expert_shapes": [
            [d.d_model, 2 * d.n_shared * d.d_expert],
            [slots, 2 * d.n_shared * d.d_expert],
            [slots, d.n_shared * d.d_expert]],
        # the dense read's own: a key block of a slot's leaf (whole, and
        # its value lanes), the heads' scores over it, their running
        # context and their padded queries; and the leaf itself (the
        # append)
        "dense_latent_shapes": [
            [slots, key_block, lanes], [slots, key_block, d.d_c],
            [slots, d.n_head, key_block], [slots, d.n_head, d.d_c],
            [slots, d.n_head, lanes]],
        "latent_append_shapes": [[slots, rung, lanes]],
        "dense_latent_flops": costs.dense_read_flops(cfg, positions),
        "dense_latent_min_bytes": costs.dense_read_min_bytes(
            cfg, positions, rows),
        "dense_latent_positions_live": positions,
        "dense_latent_positions_touched": touched_positions,
        "delta_state_min_bytes": costs.delta_update_min_bytes(cfg, rows),
        "experts_min_bytes": costs.experts_min_bytes(cfg, touched, rows),
        "experts_flops": costs.experts_flops(cfg, rows),
        "step_min_bytes": costs.step_min_bytes(cfg, positions, rows,
                                               touched),
    }
    # what ``latent_attention_time_share.serve`` looks for: the read and
    # the append together (its reader needs only this list)
    counters["latent_attend_shapes"] = (counters["dense_latent_shapes"]
                                        + counters["latent_append_shapes"])
    counters.update({c: delta[c] for c in EXPERT_COUNTERS})
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
