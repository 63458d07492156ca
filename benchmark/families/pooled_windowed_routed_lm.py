"""Family ``pooled_windowed_routed_lm``: a decoder of sliding-window
attention layers beside position-free global attention layers, a mixture
of routed experts after each (``model_name: smallthinker_*``), served
through ``decoding.make_windowed_routed_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` (continuous batching over ONE slot pool
whose sequence leaves differ in LENGTH: ring leaves of the window beside
leaves of the whole rung; a chunked prefill; a prefix cache of device
snapshots), under the ``closed_loop_shared_docs`` traffic kind.

From the program this file takes the system under test and nothing
else: the parameter names and shapes
(``windowed_routed_lm.param_shapes``), the step builder, the server, its
monitor series (``serving_decode_{tokens,prefill_tokens,ticks,
prefill_chunks,admitted,window_positions_read,window_positions_live,
expert_assignments,experts_touched,expert_peak_load,expert_layer_steps}
_total``, ``serving_prefix_cache_{hits,misses}_total``,
``serving_prefix_snapshots_total``, ``serving_kv_cache_bytes``,
``serving_decode_kv_bytes_{held,one_length}``) and, in the device trace,
the grouped product's kernel name.  Lengths, the corpus, stamps, the
bytes a step needs (``lib/costs_windowed``) and the comparison that
decides ``correct`` (the configuration's reference beside its file) are
the benchmark's own; the window's loop is ``lib/pooled_window``.

Before the callers start, ONE pilot request per document goes through
the server's normal path: each misses the prefix cache, is prefilled in
chunks and leaves its snapshot; all of that is ``setup_s``.  ``correct``
then holds, besides the reference comparison (the served tokens of a
sample of requests against the reference's full forward of the WHOLE
prompt, computed in float32 at "highest" on the operands the
configuration states, ``check.matmul_inputs``; a token's gap under TWO
bounds, mean and worst): every request admitted in the window was a
prefix hit, every sampled request sat in a slot another request had
left, every branch of every block is at least ``check.min_branch_share``
of the residual it is added to, the pool's bytes are what the
benchmark's own arithmetic gives for two cache lengths, and the
program's expert counters add up.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark.lib import costs_windowed as costs
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
    where the program has none (a commit before PR 43)."""
    from paddle_tpu import decoding
    try:
        from paddle_tpu import windowed_routed_lm
        return (decoding.make_windowed_routed_lm_pooled_step_fn,
                windowed_routed_lm)
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve a "
                         "smallthinker decoder (%s)" % exc)


def make_weights(cfg, device, parts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed (``assumed.weights``): matrices
    normal(0, initializer_range) in bf16 as they are served (``W_q``,
    ``W_k`` at ``assumed.attn_qk_std``, the embedding at
    ``assumed.embedding_std``); norms 1 and the router
    (``assumed.router_std``) in float32."""
    import jax
    import jax.numpy as jnp

    shapes = parts.param_shapes(cfg)
    names = sorted(shapes)
    a = cfg["assumed"]
    std, qk_std = float(a["initializer_range"]), float(a["attn_qk_std"])
    emb_std, router_std = float(a["embedding_std"]), float(a["router_std"])
    f32 = jnp.float32

    def make(key):
        out = {}
        for i, n in enumerate(names):
            k, shp = jax.random.fold_in(key, i), shapes[n]
            if n.endswith("_norm"):
                out[n] = jnp.ones(shp, f32)
            elif n.endswith("router"):
                out[n] = jax.random.normal(k, shp, f32) * router_std
            else:
                sd = (qk_std if n.endswith(("attn_q", "attn_k"))
                      else emb_std if n.endswith("_emb") else std)
                out[n] = (jax.random.normal(k, shp, jnp.bfloat16)
                          * sd).astype(jnp.bfloat16)
        return out

    with jax.default_device(device):
        state = jax.jit(make)(jax.random.PRNGKey(int(a["weight_seed"])))
    jax.block_until_ready(state)
    return state


_PROGRAMS = {}


def _reference_programs(ref, rcfg, chk):
    """The reference's jitted pieces, built once for one configuration
    (a test serves many variants against the same reference)."""
    import jax

    key = json.dumps([rcfg, chk], sort_keys=True, default=str)
    if key not in _PROGRAMS:
        embed = jax.jit(lambda w, t: ref.embed(w, t, rcfg))
        # one program a (window?, rotated?) kind of layer: each layer's
        # weights go in under layer 0's names
        kinds = set(zip(rcfg["sliding_window_layout"], rcfg["rope_layout"]))
        blocks = {kind: jax.jit(lambda w, h, kind=kind: ref.block(
            w, 0, h, rcfg, int(kind[0]), rotated=bool(kind[1]),
            query_block=int(chk["query_block"]))) for kind in kinds}
        stats = jax.jit(lambda w, h, t: ref.head_stats(
            w, h, t, rcfg, int(chk["vocab_blocks"])))
        _PROGRAMS[key] = embed, blocks, stats
    return _PROGRAMS[key]


def check_against_reference(ctx, state, kept, slots=None):
    """Snapshot admission + decode through the pool against the
    reference's full forward of the WHOLE prompt (no cache, no ring, no
    snapshot), on the sample of served requests that kept their tokens:
    ``kept`` is ``[(prompt ids, generated ids, requests sent before
    it)]``.  Returns (ok, details)."""
    import jax
    import jax.numpy as jnp

    cfg, chk = ctx.cfg, ctx.cfg["check"]
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_" + cfg["name"])
    mean_max = float(chk["mean_gap_share"])
    worst_max = float(chk["worst_gap_share"])
    if not kept:
        return False, {"why": "no finished request kept its tokens"}
    s_ref, rows = int(chk["reference_len"]), int(chk["head_rows"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    # the operands the configuration STATES are rounded are rounded in
    # the reference too; its arithmetic stays float32 at "highest"
    rcfg["matmul_inputs"] = chk.get("matmul_inputs")
    embed, blocks, stats = _reference_programs(ref, rcfg, chk)
    layers = int(cfg["num_hidden_layers"])
    kinds = list(zip(rcfg["sliding_window_layout"], rcfg["rope_layout"]))
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(layers)]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, hits, finite = [], 0, True
    shares = np.zeros((layers, 2))
    for prompt, got, _ in kept:
        toks = np.zeros((1, s_ref), np.int32)
        toks[0, :len(prompt)] = prompt
        toks[0, len(prompt):len(prompt) + len(got)] = got
        h = embed(ends, jax.device_put(toks, ctx.device))
        for i in range(layers):
            h, sh = blocks[kinds[i]](by_layer[i], h)
            shares[i] += np.asarray(sh) / len(kept)
        # position s predicts the token at s + 1: the rows that
        # predicted the served tokens, padded to a fixed count
        at = np.minimum(len(prompt) - 1 + np.arange(rows), s_ref - 2)
        hi, lo, arg, val = (np.asarray(x) for x in stats(
            ends, h[0][jnp.asarray(at)], jnp.asarray(toks[0][at + 1])))
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
                "in_reused_slots": reused,
                "branch_share_of_residual": {
                    "layers_x_[attention,experts]":
                        np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def expert_counts_add_up(cfg, delta) -> bool:
    """What must hold of the four expert counters' deltas whatever the
    routing (``pooled_routed_conv_lm.expert_counts_add_up``, given this
    configuration's sizes under the key names it reads)."""
    return _routed.expert_counts_add_up(
        {"num_experts_per_tok": cfg["moe_num_active_primary_experts"],
         "num_experts": cfg["moe_num_primary_experts"]}, delta)


def make_server(cfg, state, build):
    """The cell's ``DecodeServer``: what ``run`` measures and what the
    harmed-variant test serves through."""
    from paddle_tpu.serving.decode import DecodeServer

    sv = cfg["serving"]
    step_fn, make_cache, _ = build(
        state, cfg, kv_dtype=sv["kv_dtype"],
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
        raise ValueError("family pooled_windowed_routed_lm cannot drive a "
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
                             "prefill_chunks", "window_positions_read",
                             "window_positions_live", "kv_positions_live",
                             "admitted") + EXPERT_COUNTERS}
            for k in ("hits", "misses"):
                out["prefix_" + k] = monitor.counter_value(
                    "serving_prefix_cache_%s_total" % k)
            out["snapshots"] = monitor.counter_value(
                "serving_prefix_snapshots_total")
            out["kv_bytes"] = monitor.counter_value("serving_kv_cache_bytes")
            for k in ("held", "one_length"):
                out["kv_bytes_" + k] = monitor.counter_value(
                    "serving_decode_kv_bytes_" + k)
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
        # the program's gauges against the benchmark's own arithmetic:
        # two cache lengths in one pool
        "pool_bytes_as_computed": c1["kv_bytes"] == c1["kv_bytes_held"]
        == costs.kv_bytes_per_slot(cfg, rung) * slots
        and c1["kv_bytes_one_length"]
        == costs.kv_bytes_per_slot(cfg, rung, one_length=True) * slots,
        "expert_counts_add_up": expert_counts_add_up(cfg, delta),
    }
    layer_steps = delta["expert_layer_steps"]
    n_layers = int(cfg["num_hidden_layers"])
    # per step, summed over the layers
    touched = (delta["experts_touched"] / layer_steps * n_layers
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
            kv_bytes_one_length=c1["kv_bytes_one_length"],
            experts_touched_per_layer_step=(
                delta["experts_touched"] / layer_steps if layer_steps
                else None),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    per_step = lambda v: v / steps if steps else 0.0
    rows = per_step(delta["tokens"] + delta["prefill_tokens"])
    d = parts.dims(cfg)
    n_global = n_layers - d.window_layers
    # positions a query may read, per step: live ones in the global
    # layers, the lesser of live and window in the window layers
    global_pos = per_step(delta["kv_positions_live"]) * n_global
    window_pos = per_step(delta["window_positions_read"])
    pairs = -(-slots * d.top_k // grouped_matmul.ROW_TILE) \
        * grouped_matmul.ROW_TILE
    ring = min(rung, d.window)
    rep = d.n_head // d.n_kv_head
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
        "kv_bytes_held": c1["kv_bytes_held"],
        "kv_bytes_one_length": c1["kv_bytes_one_length"],
        "window_positions_read": delta["window_positions_read"],
        "window_positions_live": delta["window_positions_live"],
        "num_experts": d.n_expert,
        "experts_touched_per_step": touched,
        "expert_kernel_names": [grouped_matmul.KERNEL_NAME],
        "expert_shapes": [[d.n_expert, d.d_model, 2 * d.d_expert],
                          [d.n_expert, d.d_expert, d.d_model],
                          [pairs, 2 * d.d_expert], [pairs, d.d_expert]],
        "route_shapes": [[slots, d.n_expert], [slots, d.top_k],
                         [slots * d.top_k], [pairs], [pairs, d.d_model],
                         [slots, d.top_k, d.d_model],
                         [slots * d.top_k, d.n_expert], [d.n_expert]],
        # a leaf of each length, its view by heads, and the scores over it
        "window_shapes": [[slots, ring, d.d_kv],
                          [slots, ring, d.n_kv_head, d.head_dim],
                          [slots, d.n_kv_head, rep, ring]],
        "global_shapes": [[slots, rung, d.d_kv],
                          [slots, rung, d.n_kv_head, d.head_dim],
                          [slots, d.n_kv_head, rep, rung]],
        "experts_min_bytes": costs.experts_min_bytes(cfg, touched, rows),
        "attention_min_bytes": costs.attention_min_bytes(
            cfg, global_pos, window_pos, rows),
        "step_min_bytes": costs.step_min_bytes(
            cfg, global_pos, window_pos, rows, touched),
    }
    counters.update({k: delta[k] for k in EXPERT_COUNTERS})
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
