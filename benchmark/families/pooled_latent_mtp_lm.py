"""Family ``pooled_latent_mtp_lm``: a decoder of multi-head LATENT
attention read DENSELY (every live position, no indexer), every branch
between two norms, a dense layer and ungrouped routed experts beside a
shared expert, and ONE multi-token-prediction module that drafts for its
own model (``model_type: pangu_ultra_moe``), served through
``decoding.make_latent_mtp_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` with ``serving.speculative.
make_self_draft`` attached AND a prefix cache of device snapshots
(continuous batching over ONE slot pool of latent leaves, one compressed
row a position in every layer and in the module; a chunked prefill that
feeds the module's leaf; every request ``speculative=True``: one
self-drafting round a tick, seated over an installed snapshot), under
the ``closed_loop_shared_docs`` traffic kind.

From the program this file takes the system under test and nothing
else: the parameter names and shapes (``latent_mtp_lm.param_shapes``),
the step builder, the self-draft attachment, the server, its monitor
series (``serving_decode_{tokens,prefill_tokens,ticks,prefill_chunks,
admitted,kv_positions_live,index_positions_scored,
latent_positions_selected,expert_assignments,experts_touched,
expert_peak_load,expert_layer_steps}_total``,
``serving_spec_{tokens_proposed,tokens_accepted,rounds,row_rounds}
_total``, ``serving_prefix_cache_{hits,misses}_total``,
``serving_prefix_snapshots_total``, ``serving_kv_cache_bytes``), a
request's kept proposals (``DecodeRequest.draft_tokens``), the dense
read's host mirror of what it touches
(``decode_attention.dense_latent_positions_touched``) and, in the device
trace, the grouped product's kernel name.  Lengths, the corpus, stamps,
the bytes and FLOPs a round needs (``lib/costs_latent_mtp``) and the
comparison that decides ``correct`` (the configuration's reference beside
its file) are the benchmark's own; the window's loop is
``lib/pooled_window``.

Before the callers start, ONE pilot request per document goes through
the server's normal path: each misses the prefix cache, is prefilled in
chunks (the module's leaf too) and leaves its snapshot; all of that is
``setup_s``.  ``correct`` then holds, besides the reference comparison —
the served tokens of a sample of requests against the reference's full
forward of the WHOLE prompt (document + question + answer, expanded,
float32 at "highest" on the operands the configuration states,
``check.matmul_inputs``; a token's gap under TWO bounds, mean and worst)
AND the module's proposals at the same positions against the
reference's module logits, the same way —: every request admitted in the
window was a prefix hit and took no prefill chunk, every tick of the
window was a self-drafting round and every generated token came out of
one, every sampled request sat in a slot another request had left over
a context past ``check.min_context``, every branch of every block is at
least ``check.min_branch_share`` of the residual it is added to, the
pool's bytes are what the benchmark's own arithmetic gives, the
program's expert counters add up, and the latent positions read are the
live positions of every row computed in every leaf.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark.lib import costs_latent_mtp as costs
from benchmark.lib import harness, loadgen, pooled_window, traffic
from benchmark.lib.traffic_shared_docs import SharedDocsSource

# what the two families this one composes already have, taken from them
_sparse = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_latent_sparse_lm.py"),
    "pooled_latent_sparse_lm")
_mtp = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_mtp_routed_lm.py"),
    "pooled_mtp_routed_lm")
_drain, run_pilots = _sparse._drain, _sparse.run_pilots
EXPERT_COUNTERS, held_of = _sparse.EXPERT_COUNTERS, _sparse.held_of
# over ``n_routed_experts`` HELD of a wider router, as that family's
expert_counts_add_up = _sparse.expert_counts_add_up
SPEC_COUNTERS, _gaps = _mtp.SPEC_COUNTERS, _mtp._gaps
K = 2                       # rows a slot a round computes


def builder():
    """The program's step builder, its parts module, the self-draft
    attachment and the dense read's host mirror, or a clean exit where
    the program has none (a commit before PR 60)."""
    try:
        from paddle_tpu import decoding, latent_mtp_lm
        from paddle_tpu.decode_attention import (
            dense_latent_positions_touched)
        from paddle_tpu.serving.speculative import make_self_draft
        return ((decoding.make_latent_mtp_lm_pooled_step_fn,
                 make_self_draft, dense_latent_positions_touched),
                latent_mtp_lm)
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve a "
                         "pangu_ultra_moe decoder with its module (%s)"
                         % exc)


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
    if name.endswith("mtp_eh"):
        return float(a["mtp_eh_std"])
    return float(a["initializer_range"])


def make_weights(cfg, device, parts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed (``assumed.weights``): matrices normal in
    bf16 as they are served, at the scales ``assumed`` names; the norms
    that close a branch at their ``assumed`` weights, every other norm 1,
    the router normal; norms and routers float32."""
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
            if n.endswith("post_attn_norm"):
                out[n] = jnp.full(shp, float(a["post_attn_norm_weight"]), f32)
            elif n.endswith("post_mlp_norm"):
                out[n] = jnp.full(shp, float(a["post_mlp_norm_weight"]), f32)
            elif n.endswith("_norm"):
                out[n] = jnp.ones(shp, f32)
            elif n.endswith("router"):
                out[n] = jax.random.normal(k, shp, f32) * float(
                    a["router_std"])
            else:
                out[n] = (jax.random.normal(k, shp, jnp.bfloat16)
                          * _std_of(n, a)).astype(jnp.bfloat16)
        return out

    with jax.default_device(device):
        state = jax.jit(make)(jax.random.PRNGKey(int(a["weight_seed"])))
    jax.block_until_ready(state)
    return state


_PROGRAMS = {}


def _reference_programs(ref, rcfg, chk, held):
    """The reference's jitted pieces, built once for one configuration
    (a test serves many variants against the same reference)."""
    import jax

    key = json.dumps([rcfg, chk, held], sort_keys=True, default=str)
    if key not in _PROGRAMS:
        qb = int(chk["query_block"])
        embed = jax.jit(lambda w, t: ref.embed(w, t, rcfg))
        # one program a kind of layer (dense or sparse): each layer's
        # weights go in under layer 0's names
        blocks = {dense: jax.jit(lambda w, h, dense=dense: ref.block(
            w, "lm_l0_", h, rcfg, dense, held, query_block=qb))
            for dense in (True, False)}
        module = jax.jit(lambda w, h, t: ref.module_input(
            w, h, ref.embed(w, jax.numpy.concatenate([t[1:], t[:1]]), rcfg),
            rcfg))
        stats = jax.jit(lambda w, h, t: ref.head_stats(
            w, h, t, rcfg, int(chk["vocab_blocks"])))
        _PROGRAMS[key] = embed, blocks, module, stats
    return _PROGRAMS[key]


def check_against_reference(ctx, state, kept, slots=None):
    """Snapshot admission + self-drafting rounds through the pool
    against the reference's full forward of the WHOLE prompt and answer
    (expanded, no cache, no snapshot, no round), on the sample of served
    requests that kept their tokens AND their module's proposals:
    ``kept`` is ``[(prompt ids, generated ids, requests sent before it,
    proposals)]``, ``proposals[j]`` the module's proposal for the position
    of ``generated[j]``.  Returns (ok, details)."""
    import jax
    import jax.numpy as jnp

    cfg, chk = ctx.cfg, ctx.cfg["check"]
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_" + cfg["name"])
    if not kept:
        return False, {"why": "no finished request kept its tokens"}
    if any(k[3] is None for k in kept):
        return False, {"why": "a sampled request kept no proposals"}
    s_ref = int(chk["reference_len"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    # the operands the configuration STATES are rounded are rounded in
    # the reference too; its arithmetic stays float32 at "highest"
    rcfg["matmul_inputs"] = chk.get("matmul_inputs")
    held = held_of(cfg)
    embed, blocks, module, stats = _reference_programs(ref, rcfg, chk, held)
    layers = int(cfg["num_hidden_layers"])
    n_dense = int(cfg["first_k_dense_replace"])
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(layers)]
    mtp_block = {k.replace("lm_mtp_", "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_mtp_")}
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, dgaps, hits, dhits, finite = [], [], 0, 0, True
    shares = np.zeros((layers + 1, 2))
    for prompt, got, _, drafts in kept:
        n, p = len(got), len(prompt)
        toks = np.zeros((s_ref,), np.int32)
        toks[:p] = prompt
        toks[p:p + n] = got
        dev = jax.device_put(toks, ctx.device)
        h = embed(ends, dev)
        for i in range(layers):
            h, sh = blocks[i < n_dense](by_layer[i], h)
            shares[i] += np.asarray(sh) / len(kept)
        # position s predicts the token at s + 1 ...
        at = p - 1 + np.arange(n)
        gap, arg, fin = _gaps(stats, ends, h[jnp.asarray(at)], got)
        gaps.append(gap)
        hits += int((arg == got).sum())
        # ... and the module's row s the token at s + 2
        u, sh = blocks[False](mtp_block, module(ends, h, dev))
        shares[layers] += np.asarray(sh) / len(kept)
        dgap, darg, dfin = _gaps(stats, ends, u[jnp.asarray(at - 1)],
                                 np.asarray(drafts, np.int32))
        dgaps.append(dgap)
        dhits += int((darg == drafts).sum())
        finite = finite and fin and dfin
        del h, u
    reused = sum(1 for k in kept if slots is not None and k[2] >= slots)
    past = sum(1 for k in kept if len(k[0]) > int(chk["min_context"]))
    branch_min = float(shares.min())
    gaps, dgaps = np.concatenate(gaps), np.concatenate(dgaps)
    mean, worst = float(gaps.mean()), float(gaps.max())
    dmean, dworst = float(dgaps.mean()), float(dgaps.max())
    ok = (finite and mean <= float(chk["mean_gap_share"])
          and worst <= float(chk["worst_gap_share"])
          and dmean <= float(chk["draft_mean_gap_share"])
          and dworst <= float(chk["draft_worst_gap_share"])
          and reused == len(kept) and past == len(kept)
          and branch_min >= float(chk["min_branch_share"]))
    quantiles = lambda g: {q: float(np.quantile(g, float(q)))
                           for q in ("0.5", "0.9", "0.99")}
    return ok, {"requests": len(kept), "tokens": int(gaps.size),
                "prompt_lens": [len(k[0]) for k in kept],
                "argmax_agreement": "%d/%d" % (hits, gaps.size),
                "draft_argmax_agreement": "%d/%d" % (dhits, dgaps.size),
                # how often the (random) module proposed what was served
                "drafts_equal_served": "%d/%d" % (
                    sum(int((np.asarray(k[3]) == k[1]).sum()) for k in kept),
                    gaps.size),
                # a random-weight decoder that falls into a loop of a few
                # tokens shows nothing: said, not judged
                "distinct_tokens_per_answer": [
                    "%d/%d" % (len(set(k[1].tolist())), len(k[1]))
                    for k in kept],
                "mean_logit_gap_share": mean,
                "mean_gap_share_allowed": float(chk["mean_gap_share"]),
                "worst_logit_gap_share": worst,
                "worst_gap_share_allowed": float(chk["worst_gap_share"]),
                "gap_share_quantiles": quantiles(gaps),
                "draft_mean_gap_share": dmean,
                "draft_mean_gap_share_allowed": float(
                    chk["draft_mean_gap_share"]),
                "draft_worst_gap_share": dworst,
                "draft_worst_gap_share_allowed": float(
                    chk["draft_worst_gap_share"]),
                "draft_gap_share_quantiles": quantiles(dgaps),
                "in_reused_slots": reused, "contexts_past_minimum": past,
                "branch_share_of_residual": {
                    "layers_then_module_x_[attention,ffn]":
                    np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def make_server(cfg, state, build):
    """The cell's ``DecodeServer``: what ``run`` measures and what the
    harmed-variant test serves through."""
    from paddle_tpu.serving.decode import DecodeServer

    sv = cfg["serving"]
    make_step, make_self_draft = build[:2]
    step_fn, make_cache, _ = make_step(
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
        speculative=make_self_draft(make_cache),
        kv_dtype=sv["kv_dtype"], name="bench-" + cfg["name"])


def run(ctx):
    build, parts = builder()
    from paddle_tpu import grouped_matmul, monitor

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    if mix["kind"] != "closed_loop_shared_docs":
        raise ValueError("family pooled_latent_mtp_lm cannot drive a %r mix"
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
                             "prefill_chunks", "kv_positions_live",
                             "index_positions_scored",
                             "latent_positions_selected",
                             "admitted") + EXPERT_COUNTERS}
            for k in SPEC_COUNTERS:
                out["spec_" + k] = monitor.counter_value(
                    "serving_spec_%s_total" % k)
            for k in ("hits", "misses"):
                out["prefix_" + k] = monitor.counter_value(
                    "serving_prefix_cache_%s_total" % k)
            out["snapshots"] = monitor.counter_value(
                "serving_prefix_snapshots_total")
            out["kv_bytes"] = monitor.counter_value("serving_kv_cache_bytes")
            return out

        sent = {"k": 0, "kept": 0}
        clients = int(mix["clients"])

        def submit(prompt, n):
            # the proposals of the requests the load keeps for the check
            # (its own rule, lib/loadgen.start_closed_loop) and no other
            keep = (sent["k"] >= clients
                    and sent["kept"] < int(chk["sample_requests"])
                    and len(prompt) + n <= int(chk["sample_max_total"]))
            sent["k"] += 1
            sent["kept"] += int(keep)
            return srv.submit({"tokens": prompt}, max_new_tokens=n,
                              speculative=True, keep_drafts=keep)

        load = loadgen.LoadRun(
            submit=submit, drain=_drain,
            produced=lambda: monitor.counter_value(
                "serving_decode_tokens_total"),
            annotate=ctx.annotate)
        source = SharedDocsSource(mix, ctx.seed, vocab)
        with ctx.phase("document_prefill"):
            # brings the pool's state to the device, prefills every
            # document once (the module's leaf too) and leaves its
            # snapshot; a pilot is a plain request: the rounds are the
            # window's
            pilot_s = run_pilots(srv, source, mix, timeout_s=1800.0)
            after_pilots = counters_now()
        with ctx.phase("ramp"):
            t_ramp = time.perf_counter()
            prompts = source.prompts  # grows as the clients draw
            load.start_closed_loop(source, clients, chk["sample_requests"],
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
             r.idx + n_docs, r.handle.draft_tokens)
            for r in load.records
            if r.keep and r.status == "done" and r.n_tok == r.output_len]
    with ctx.annotate("bench/reference_check"):
        ref_ok, ref_info = check_against_reference(ctx, state, kept, slots)
    stamps = loadgen.stamp_faults(load.records, load.sweeps, w0, t1)
    delta = {k: c1[k] - c0[k] for k in c0}
    ticks, rounds = delta["ticks"], delta["spec_rounds"]
    d = parts.dims(cfg)
    n_leaves = d.n_layer + d.n_mtp
    doc_tokens = int(sum(len(x) for x in source.documents))
    # tokens no round proposed for: a teacher-forced second row emits the
    # first token of a request whose question ends on it: at most one a
    # request that began to generate in the window
    unproposed = (delta["tokens"] - delta["spec_tokens_proposed"]
                  - delta["spec_tokens_accepted"])
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
        # every tick a self-drafting round, every token out of one: a
        # change cannot win the cell by switching the module off
        "every_row_step_in_a_round": rounds == ticks > 0
        and 0 <= unproposed <= delta["admitted"] + slots,
        # the program's gauge against the benchmark's own arithmetic
        "pool_bytes_as_computed": c1["kv_bytes"]
        == costs.cache_bytes_per_slot(cfg, rung) * slots,
        "expert_counts_add_up": expert_counts_add_up(cfg, delta),
        # a DENSE read: every row computed read every live position, in
        # every layer's leaf and the module's
        "latent_positions_read_are_the_live_ones":
        delta["latent_positions_selected"]
        == delta["index_positions_scored"] > 0
        and delta["latent_positions_selected"] % n_leaves == 0
        and delta["latent_positions_selected"]
        >= delta["kv_positions_live"] * n_leaves,
    }
    layer_steps = delta["expert_layer_steps"]
    n_sparse = len(d.expert_layers) + d.n_mtp
    # per round, summed over the sparse blocks (the module's among them)
    touched = (delta["experts_touched"] / layer_steps * n_sparse
               if layer_steps else 0.0)
    proposed = delta["spec_tokens_proposed"]
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
            accept_rate=(delta["spec_tokens_accepted"] / proposed
                         if proposed else None),
            tokens_no_round_proposed_for=unproposed,
            kv_cache_bytes=c1["kv_bytes"],
            experts_touched_per_layer_step=(
                delta["experts_touched"] / layer_steps if layer_steps
                else None),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    per_round = lambda v: v / rounds if rounds else 0.0
    rows = per_round(delta["spec_row_rounds"]) * K
    # positions the rows of a round read, summed over rows and leaves
    # (the program's counter counts per ROW computed) ...
    row_positions = per_round(delta["latent_positions_selected"])
    # ... and the positions that have to leave HBM for them: both rows of
    # a slot read ONE set of positions, the longer row's (one more than
    # the shorter's: halve the sum and add half a position a row)
    positions = row_positions / K + rows / K * n_leaves * (K - 1) / 2.0
    # what the read's form touched: whole key blocks up to the pool's
    # longest live context, for every slot, in every leaf (the longest
    # context of the window's traffic: the longest document + question
    # + answer, at most the rung)
    longest = min(rung, max(len(x) for x in source.documents)
                  + int(mix["question"]["max"]) + int(mix["output"]["max"]))
    touched_positions = build[2](longest, rung) * slots * n_leaves
    n_rows = slots * K
    pairs = -(-n_rows * d.top_k // grouped_matmul.ROW_TILE) \
        * grouped_matmul.ROW_TILE
    n_held = held_of(cfg)[1] - held_of(cfg)[0]
    lanes = costs.whole_tiles(d.d_latent)
    key_block = build[2](1, rung)       # positions a turn of the walk
    round_bytes = costs.round_min_bytes(cfg, positions, rows, touched)
    counters = {
        "window_s": s["window_s"],
        "steps": rounds, "ticks": ticks, "steps_per_dispatch": 1,
        "generated_tokens": delta["tokens"],
        "prefill_tokens": delta["prefill_tokens"],
        "rows_stepped_per_step": rows,
        "in_flight_at_close": s["in_flight_at_close"],
        "queue_depth_at_close": metrics["queue_depth"],
        "kv_cache_bytes": c1["kv_bytes"],
        "index_positions_scored": delta["index_positions_scored"],
        "latent_positions_selected": delta["latent_positions_selected"],
        "spec_proposed": proposed,
        "spec_accepted": delta["spec_tokens_accepted"],
        "spec_rounds": rounds, "spec_row_rounds": delta["spec_row_rounds"],
        # the held experts: what the counters' groups are over
        "num_experts": n_held,
        "experts_touched_per_step": touched,
        "expert_kernel_names": [grouped_matmul.KERNEL_NAME],
        "expert_shapes": [[n_held, d.d_model, 2 * d.d_expert],
                          [n_held, d.d_expert, d.d_model],
                          [pairs, 2 * d.d_expert], [pairs, d.d_expert]],
        # NOT the sorted pairs' [n_rows * top_k]: at 64 rows x 8 that is
        # [512], the dense read's key block (its iota rides every product
        # of the walk: the first traced run booked the read as routing)
        "route_shapes": [[n_rows, d.n_expert], [n_rows, d.top_k],
                         [n_rows, d.top_k, d.d_model],
                         [n_rows * d.top_k, n_held], [n_held]],
        # the shared expert's own: its gate-and-up matrix and every
        # row's gate-and-up and activation
        "shared_expert_shapes": [
            [d.d_model, 2 * d.n_shared * d.d_expert],
            [n_rows, 2 * d.n_shared * d.d_expert],
            [n_rows, d.n_shared * d.d_expert]],
        # the dense read's own: a key block of every slot's leaf (whole,
        # and its value lanes), the two rows' heads' scores over it,
        # their running context and their padded queries; and the leaf
        # itself (the K-row append)
        "dense_latent_shapes": [
            [slots, key_block, lanes], [slots, key_block, d.d_c],
            [slots, K * d.n_head, key_block], [slots, K * d.n_head, d.d_c],
            [slots, K * d.n_head, lanes]],
        "latent_append_shapes": [[slots, rung, lanes]],
        "dense_latent_flops": costs.dense_read_flops(cfg, row_positions),
        "dense_latent_min_bytes": costs.dense_read_min_bytes(
            cfg, positions, rows),
        "dense_latent_positions_live": positions,
        "dense_latent_positions_touched": touched_positions,
        "experts_min_bytes": costs.experts_min_bytes(cfg, touched, rows),
        "round_min_bytes": round_bytes,
        "step_min_bytes": round_bytes,
    }
    # what ``latent_attention_time_share.serve`` looks for: the read and
    # the append together (its reader needs only this list)
    counters["latent_attend_shapes"] = (counters["dense_latent_shapes"]
                                        + counters["latent_append_shapes"])
    counters.update({c: delta[c] for c in EXPERT_COUNTERS})
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
