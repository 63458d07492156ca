"""Family ``pooled_latent_sparse_lm``: a decoder of multi-head LATENT
attention read through a learned top-k selection (a lightning indexer),
a dense layer and group-limited routed experts beside a shared expert
(``model_type: deepseek_v32``), served through
``decoding.make_latent_sparse_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` (continuous batching over ONE slot pool
of latent leaves: one compressed row and one index key a position; a
chunked prefill; a prefix cache of device snapshots), under the
``closed_loop_shared_docs`` traffic kind.

From the program this file takes the system under test and nothing
else: the parameter names and shapes
(``latent_sparse_lm.param_shapes``), the step builder, the server, its
monitor series (``serving_decode_{tokens,prefill_tokens,ticks,
prefill_chunks,admitted,kv_positions_live,index_positions_scored,
latent_positions_selected,expert_assignments,experts_touched,
expert_peak_load,expert_layer_steps}_total``,
``serving_prefix_cache_{hits,misses}_total``,
``serving_prefix_snapshots_total``, ``serving_kv_cache_bytes``) and, in
the device trace, the grouped product's kernel name.  For the indexer's
own check it also calls the program's three indexer functions
(``latent_sparse_lm.index_inputs`` / ``index_scores`` /
``select_positions``) on the reference's layer inputs.  Lengths, the
corpus, stamps, the bytes a step needs (``lib/costs_latent_sparse``) and
the comparison that decides ``correct`` (the configuration's reference
beside its file) are the benchmark's own; the window's loop is
``lib/pooled_window``.

Before the callers start, ONE pilot request per document goes through
the server's normal path: each misses the prefix cache, is prefilled in
chunks and leaves its snapshot; all of that is ``setup_s``.  ``correct``
then holds, besides the reference comparison (the served tokens of a
sample of requests against the reference's full forward of the WHOLE
prompt, expanded, with its own float32 indexer and top-k, computed at
"highest" on the operands the configuration states,
``check.matmul_inputs``; a token's gap under TWO bounds, mean and worst;
and the indexer's two bounds): every request admitted in the window was
a prefix hit, every sampled request sat in a slot another request had
left, every branch of every block is at least ``check.min_branch_share``
of the residual it is added to, the pool's bytes are what the
benchmark's own arithmetic gives, and the program's expert and
selection counters add up.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark.lib import costs_latent_sparse as costs
from benchmark.lib import harness, loadgen, pooled_window, traffic
from benchmark.lib.traffic_shared_docs import SharedDocsSource

_drain = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_decode_lm.py"),
    "pooled_decode_lm")._drain
run_pilots = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_sparse_linear_lm.py"),
    "pooled_sparse_linear_lm").run_pilots

_routed = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_routed_conv_lm.py"),
    "pooled_routed_conv_lm")
EXPERT_COUNTERS = _routed.EXPERT_COUNTERS


def builder():
    """The program's step builder and parts module, or a clean exit
    where the program has none (a commit before PR 54)."""
    from paddle_tpu import decoding
    try:
        from paddle_tpu import latent_sparse_lm
        return (decoding.make_latent_sparse_lm_pooled_step_fn,
                latent_sparse_lm)
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve a "
                         "deepseek_v32 decoder (%s)" % exc)


def held_of(cfg):
    return tuple(int(x) for x in cfg["experts_held"])


def _std_of(name: str, a: dict) -> float:
    """The initialiser scale of matrix ``name`` (``assumed``)."""
    if name.endswith("_emb"):
        return float(a["embedding_std"])
    if name.endswith("attn_q_b"):
        return float(a["attn_q_b_std"])
    if name.endswith(("ffn_gate", "ffn_up")):
        return float(a["ffn_in_std"])
    if name.endswith(("experts_w13", "shared_w13")):
        return float(a["expert_in_std"])
    return float(a["initializer_range"])


def make_weights(cfg, device, parts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed (``assumed.weights``): matrices normal in
    bf16 as they are served, at the scales ``assumed`` names; RMSNorm and
    LayerNorm weights 1, the LayerNorm's bias, the router and the
    selection bias in float32."""
    import jax
    import jax.numpy as jnp

    shapes = parts.param_shapes(cfg, held=held_of(cfg))
    names = sorted(shapes)
    a = cfg["assumed"]
    f32 = jnp.float32

    def make(key):
        out = {}
        for i, n in enumerate(names):
            k, shp = jax.random.fold_in(key, i), shapes[n]
            if n.endswith("_norm"):
                out[n] = jnp.ones(shp, f32)
            elif n.endswith("_norm_bias"):
                out[n] = jax.random.normal(k, shp, f32) * float(
                    a["index_norm_bias_std"])
            elif n.endswith("router"):
                out[n] = jax.random.normal(k, shp, f32) * float(
                    a["router_std"])
            elif n.endswith("expert_bias"):
                r = float(a["expert_bias_range"])
                out[n] = jax.random.uniform(k, shp, f32, -r, r)
            else:
                out[n] = (jax.random.normal(k, shp, jnp.bfloat16)
                          * _std_of(n, a)).astype(jnp.bfloat16)
        return out

    with jax.default_device(device):
        state = jax.jit(make)(jax.random.PRNGKey(int(a["weight_seed"])))
    jax.block_until_ready(state)
    return state


_PROGRAMS = {}
PROGRAM_KEY = "program:"    # prefix of the program's own piece in there


def _reference_programs(ref, parts, rcfg, chk, cfg):
    """The reference's jitted pieces and the program's indexer, built
    once for one configuration (a test serves many variants against the
    same reference)."""
    import jax
    import jax.numpy as jnp

    key = json.dumps([rcfg, chk], sort_keys=True, default=str)
    if key in _PROGRAMS and PROGRAM_KEY + key in _PROGRAMS:
        return _PROGRAMS[key] + (_PROGRAMS[PROGRAM_KEY + key],)
    held = held_of(cfg)
    embed = jax.jit(lambda w, t: ref.embed(w, t, rcfg))
    # one program a kind of layer (dense or sparse): each layer's weights
    # go in under layer 0's names
    blocks = {dense: jax.jit(lambda w, h, rows, dense=dense: ref.block(
        w, "lm_l0_", h, rcfg, dense, held,
        query_block=int(chk["query_block"]), index_rows=rows))
        for dense in (True, False)}
    stats = jax.jit(lambda w, h, t: ref.head_stats(
        w, h, t, rcfg, int(chk["vocab_blocks"])))
    d = parts.dims(cfg)

    def program_index(w, h, rows):
        """The PROGRAM's index scores and selection for the query rows
        ``rows`` of a sequence whose layer input is ``h`` [S, D]: its own
        norm, projections, rounding to the stored dtype, scoring and
        top-k."""
        p, pos = "lm_l0_", jnp.arange(h.shape[0])
        x = parts.rms_norm(h, w[p + "input_norm"], d.eps)
        cq = parts.latent_inputs(x, w, p, pos, d)[0]
        # every position's key; the queries of the sampled rows alone
        ki = parts.index_inputs(x, cq, w, p, pos, d)[1]
        qi, _, wi = parts.index_inputs(x[rows], cq[rows], w, p, rows, d)
        keys = ki.astype({"bf16": jnp.bfloat16, "fp32": jnp.float32}[
            cfg["serving"]["kv_dtype"]])
        scores = parts.index_scores(qi, wi, keys)
        sel, valid = parts.select_positions(scores, rows, d.index_topk)
        return scores, sel, valid

    # the program's piece apart: a test that harms the program's indexer
    # drops it alone and keeps the reference's compiled pieces
    _PROGRAMS.setdefault(key, (embed, blocks, stats))
    _PROGRAMS[PROGRAM_KEY + key] = jax.jit(program_index)
    return _PROGRAMS[key] + (_PROGRAMS[PROGRAM_KEY + key],)


def _index_agreement(i_ref, i_got, sel, valid, at, top_k):
    """The indexer's two readings for one layer of one request: the
    largest |program's I - reference's I| over the live positions, and
    how far below the reference's ``top_k``-th largest score the worst
    position the program selected lies — both as shares of the spread
    (standard deviation) of the reference's live scores."""
    i_ref, i_got = np.asarray(i_ref, np.float64), np.asarray(i_got,
                                                             np.float64)
    sel, valid = np.asarray(sel), np.asarray(valid)
    worst_diff = worst_rank = 0.0
    for r, t in enumerate(at):
        live = i_ref[r, :t + 1]
        spread = float(live.std()) or 1.0
        worst_diff = max(worst_diff, float(
            np.abs(i_got[r, :t + 1] - live).max()) / spread)
        k = min(top_k, t + 1)
        least = np.partition(live, len(live) - k)[len(live) - k]
        chosen = sel[r][valid[r]]
        if len(chosen) != k or (chosen > t).any():
            return float("inf"), float("inf")
        worst_rank = max(worst_rank, float(
            (least - live[chosen].min())) / spread)
    return worst_diff, worst_rank


def check_against_reference(ctx, state, kept, slots=None):
    """Snapshot admission + decode through the pool against the
    reference's full forward of the WHOLE prompt (expanded, its own
    float32 indexer and top-k, no cache, no snapshot), on the sample of
    served requests that kept their tokens: ``kept`` is ``[(prompt ids,
    generated ids, requests sent before it)]``.  Returns (ok, details)."""
    import jax
    import jax.numpy as jnp

    _, parts = builder()
    cfg, chk = ctx.cfg, ctx.cfg["check"]
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_" + cfg["name"])
    mean_max = float(chk["mean_gap_share"])
    worst_max = float(chk["worst_gap_share"])
    if not kept:
        return False, {"why": "no finished request kept its tokens"}
    s_ref, rows = int(chk["reference_len"]), int(chk["head_rows"])
    n_index = int(chk["index_rows"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items()
            if not isinstance(v, dict) or k == "rope_scaling"}
    # the operands the configuration STATES are rounded are rounded in
    # the reference too; its arithmetic stays float32 at "highest"
    rcfg["matmul_inputs"] = chk.get("matmul_inputs")
    embed, blocks, stats, program_index = _reference_programs(
        ref, parts, rcfg, chk, cfg)
    layers = int(cfg["num_hidden_layers"])
    n_dense = int(cfg["first_k_dense_replace"])
    top_k = int(cfg["index_topk"])
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(layers)]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, hits, finite = [], 0, True
    shares = np.zeros((layers, 2))
    index_diff = index_rank = 0.0
    for prompt, got, _ in kept:
        toks = np.zeros((s_ref,), np.int32)
        toks[:len(prompt)] = prompt
        toks[len(prompt):len(prompt) + len(got)] = got
        # the rows that predicted the first served tokens: the indexer's
        # sample
        at = np.minimum(len(prompt) - 1 + np.arange(n_index), s_ref - 2)
        h = embed(ends, jax.device_put(toks, ctx.device))
        for i in range(layers):
            i_got, sel, valid = program_index(by_layer[i], h, jnp.asarray(at))
            h, sh, i_ref = blocks[i < n_dense](by_layer[i], h,
                                               jnp.asarray(at))
            shares[i] += np.asarray(sh) / len(kept)
            diff, rank = _index_agreement(i_ref, i_got, sel, valid, at, top_k)
            index_diff, index_rank = max(index_diff, diff), max(index_rank,
                                                                rank)
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
    mean, worst = float(gaps.mean()), float(gaps.max())
    ok = (finite and mean <= mean_max and worst <= worst_max
          and index_diff <= float(chk["index_score_tolerance"])
          and index_rank <= float(chk["index_rank_margin"])
          and reused == len(kept)
          and branch_min >= float(chk["min_branch_share"]))
    return ok, {"requests": len(kept), "tokens": int(gaps.size),
                "prompt_lens": [len(p) for p, _, _ in kept],
                "argmax_agreement": "%d/%d" % (hits, gaps.size),
                # a random-weight decoder that falls into a loop of a
                # few tokens routes its rows alike: said, not judged
                "distinct_tokens_per_answer": [
                    "%d/%d" % (len(set(got.tolist())), len(got))
                    for _, got, _ in kept],
                "mean_logit_gap_share": mean,
                "mean_gap_share_allowed": mean_max,
                "worst_logit_gap_share": worst,
                "worst_gap_share_allowed": worst_max,
                "gap_share_quantiles": {
                    q: float(np.quantile(gaps, float(q)))
                    for q in ("0.5", "0.9", "0.99")},
                "index_score_worst_difference": index_diff,
                "index_score_tolerance": float(chk["index_score_tolerance"]),
                "index_selected_worst_below_kth": index_rank,
                "index_rank_margin": float(chk["index_rank_margin"]),
                "in_reused_slots": reused,
                "branch_share_of_residual": {
                    "layers_x_[attention,ffn]": np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def expert_counts_add_up(cfg, delta) -> bool:
    """What must hold of the four expert counters' deltas whatever the
    routing, where only ``n_routed_experts`` of the routed-over experts
    are held (as ``pooled_mtp_routed_lm.expert_counts_add_up``): a
    layer-step touches at most all of them (and may touch none: 8 of 256
    are held), the largest group is at least the mean group and no more
    than all the pairs."""
    n = int(cfg["n_routed_experts"])
    pairs, touched, peak, ls = (delta[c] for c in EXPERT_COUNTERS)
    if not ls:
        return False
    return bool(0 < touched <= n * ls and touched <= pairs
                and peak * n >= pairs and peak * touched >= pairs
                and peak <= pairs)


def make_server(cfg, state, build):
    """The cell's ``DecodeServer``: what ``run`` measures and what the
    harmed-variant test serves through."""
    from paddle_tpu.serving.decode import DecodeServer

    sv = cfg["serving"]
    step_fn, make_cache, _ = build(
        state, cfg, kv_dtype=sv["kv_dtype"], held=held_of(cfg),
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


def run(ctx):
    build, parts = builder()
    from paddle_tpu import grouped_matmul, monitor

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    if mix["kind"] != "closed_loop_shared_docs":
        raise ValueError("family pooled_latent_sparse_lm cannot drive a "
                         "%r mix" % mix["kind"])
    vocab = int(cfg["vocab_size"])
    slots = int(sv["slot_ladder"][-1])
    rung = int(sv["len_ladder"][-1])
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
                             "prefill_chunks", "kv_positions_live",
                             "index_positions_scored",
                             "latent_positions_selected",
                             "admitted") + EXPERT_COUNTERS}
            for k in ("hits", "misses"):
                out["prefix_" + k] = monitor.counter_value(
                    "serving_prefix_cache_%s_total" % k)
            out["snapshots"] = monitor.counter_value(
                "serving_prefix_snapshots_total")
            out["kv_bytes"] = monitor.counter_value("serving_kv_cache_bytes")
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
            pilot_s = run_pilots(srv, source, mix, timeout_s=1800.0)
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
    d = parts.dims(cfg)
    n_layers = d.n_layer
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
        # the program's gauge against the benchmark's own arithmetic
        "pool_bytes_as_computed": c1["kv_bytes"]
        == costs.cache_bytes_per_slot(cfg, rung) * slots,
        "expert_counts_add_up": expert_counts_add_up(cfg, delta),
        # every layer scored every live position and read no more than
        # it scored, nor more than top-k a row
        "selection_counts_add_up":
        delta["index_positions_scored"]
        == delta["kv_positions_live"] * n_layers
        and 0 < delta["latent_positions_selected"]
        <= delta["index_positions_scored"],
    }
    layer_steps = delta["expert_layer_steps"]
    n_sparse = len(d.expert_layers)
    # per step, summed over the layers
    touched = (delta["experts_touched"] / layer_steps * n_sparse
               if layer_steps else 0.0)
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
            experts_touched_per_layer_step=(
                delta["experts_touched"] / layer_steps if layer_steps
                else None),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    per_step = lambda v: v / steps if steps else 0.0
    rows = per_step(delta["tokens"] + delta["prefill_tokens"])
    scored = per_step(delta["index_positions_scored"])
    selected = per_step(delta["latent_positions_selected"])
    n_held = held_of(cfg)[1] - held_of(cfg)[0]
    pairs = -(-slots * d.top_k // grouped_matmul.ROW_TILE) \
        * grouped_matmul.ROW_TILE
    k_sel = min(d.index_topk, rung)
    lanes = costs.whole_tiles      # a leaf's row: whole 128-lane tiles
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
        "index_positions_scored": delta["index_positions_scored"],
        "latent_positions_selected": delta["latent_positions_selected"],
        # the held experts: what the counters' groups are over
        "num_experts": n_held,
        "experts_touched_per_step": touched,
        "expert_kernel_names": [grouped_matmul.KERNEL_NAME],
        "expert_shapes": [[n_held, d.d_model, 2 * d.d_expert],
                          [n_held, d.d_expert, d.d_model],
                          [pairs, 2 * d.d_expert], [pairs, d.d_expert]],
        "route_shapes": [[slots, d.n_expert], [slots, d.top_k],
                         [slots * d.top_k], [pairs], [pairs, d.d_model],
                         [slots, d.top_k, d.d_model],
                         [slots * d.top_k, n_held], [n_held],
                         [slots, d.n_group, d.n_expert // d.n_group],
                         [slots, d.n_group]],
        "shared_expert_shapes": [
            [d.d_model, 2 * d.n_shared * d.d_expert],
            [slots, 2 * d.n_shared * d.d_expert],
            [slots, d.n_shared * d.d_expert]],
        # the index-key leaf and the per-head products over the rung
        "index_score_shapes": [[slots, rung, lanes(d.d_index)],
                               [slots, d.n_index_head, rung]],
        # the latent leaf, the gathered rows, the heads' scores over them
        "latent_attend_shapes": [[slots, rung, lanes(d.d_latent)],
                                 [slots, k_sel, lanes(d.d_latent)],
                                 [slots, k_sel, d.d_c],
                                 [slots, d.n_head, k_sel]],
        # the rung's scores (masked, sorted) and the lists that come out
        "index_select_shapes": [[slots, rung], [slots, k_sel]],
        "experts_min_bytes": costs.experts_min_bytes(cfg, touched, rows),
        "index_score_min_bytes": costs.index_score_min_bytes(cfg, scored),
        "selected_read_min_bytes": costs.selected_read_min_bytes(
            cfg, selected, rows),
        "latent_attention_min_bytes": costs.latent_attention_min_bytes(
            cfg, scored, selected, rows),
        "index_score_flops": costs.index_score_flops(cfg, scored),
        "selected_read_flops": costs.selected_read_flops(cfg, selected),
        "step_min_bytes": costs.step_min_bytes(cfg, scored, selected, rows,
                                               touched),
    }
    counters.update({k: delta[k] for k in EXPERT_COUNTERS})
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
