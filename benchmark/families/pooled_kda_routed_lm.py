"""Family ``pooled_kda_routed_lm``: a decoder of Kimi Delta Attention
layers (a gated delta rule whose decay is a factor a key CHANNEL) beside
gated position-free GQA layers, routed experts beside a shared expert
after every mixer (``model_type: solar_open2``), served token by token
through ``decoding.make_kda_routed_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` (continuous batching over ONE slot pool
whose layers hold different leaves: a recurrent state and a conv window,
or K/V rows), a share of the experts held, under a closed-loop traffic
mix.

From the program this file takes the system under test and nothing
else: the parameter names and shapes
(``delta_hybrid_lm.kda_param_shapes``), the shape the state leaf is
declared in (``kda_dims(cfg).state_shape``), the step builder, the
server, its monitor series (``serving_decode_{tokens,prefill_tokens,
ticks,state_resets,kv_positions_read,kv_positions_live,
expert_assignments,experts_touched,expert_peak_load,expert_layer_steps}
_total``, ``serving_{kv_cache,recurrent_state}_bytes``,
``delta_update_{lowered,decay}_total``), the scopes' names and, in the
device trace, the kernels' names and the shapes only one kind of layer's
tensors have.  Lengths, arrivals, stamps, percentiles, the bytes a step
needs (``lib/costs_kda_routed``) and the comparison that decides
``correct`` (the configuration's reference beside its file) are the
benchmark's own; the window's loop is ``lib/pooled_window``; the weights'
maker and the check's shape follow ``pooled_delta_hybrid_lm`` and
``pooled_mtp_routed_lm``, whose helpers this file imports.

``correct`` holds the served tokens of a sample of requests to the
reference's full forward (float32 at "highest" on the operands the
configuration states, ``check.matmul_inputs``), logits not tokens.  A
served token's gap is how far its reference logit lies under the
position's maximum, as a share of the position's logit range, held under
TWO bounds: the mean over the sampled tokens (``check.mean_gap_share``,
tight: what tells a lower precision and a decay a head for a decay a
channel) and the worst token's (``check.worst_gap_share``, loose: gross
failure only).  Besides that: EVERY sampled request sat in a slot
another request had left (a closed loop samples only requests sent after
every client's first: a state that is not reset shows), every branch of
every block is at least ``check.min_branch_share`` of the residual it is
added to, the pool's bytes are what the benchmark's own arithmetic
gives, the program's expert counters add up, and on a TPU every delta
update traced took the kernel with a decay a channel.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark.lib import costs_kda_routed as costs
from benchmark.lib import harness, loadgen, pooled_window, traffic

_mtp = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_mtp_routed_lm.py"),
    "pooled_mtp_routed_lm")
EXPERT_COUNTERS = _mtp.EXPERT_COUNTERS
held_of = _mtp.held_of
_drain = _mtp._drain


def builder():
    """The program's step builder and parts module, or a clean exit
    where the program has none (a commit before PR 56)."""
    try:
        from paddle_tpu import decoding, delta_hybrid_lm
        return decoding.make_kda_routed_lm_pooled_step_fn, delta_hybrid_lm
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve a "
                         "solar_open2 decoder (%s)" % exc)


def make_weights(cfg, device, parts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed (``assumed.weights`` says why each
    scale): matrices in bf16 as they are served; norms, the conv kernel,
    ``A_log``, ``dt_bias``, the router and its bias in float32."""
    import jax
    import jax.numpy as jnp

    shapes = parts.kda_param_shapes(cfg, held=held_of(cfg))
    names = sorted(shapes)
    a = cfg["assumed"]
    std, emb_std = float(a["initializer_range"]), float(a["embedding_std"])
    low_std, router_std = float(a["low_rank_out_std"]), float(a["router_std"])
    bias = float(a["expert_bias_range"])
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
                      if n.endswith(("lin_fb", "lin_gb")) else std)
                out[n] = (jax.random.normal(k, shp, jnp.bfloat16)
                          * sd).astype(jnp.bfloat16)
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
        # one program for every layer of a kind: each layer's weights go
        # in under layer 0's names
        blocks = {kind: jax.jit(lambda w, h, kind=kind: ref.block(
            w, "lm_l0_", h, rcfg, kind, held, qb))
            for kind in set(ref.kinds_of(rcfg))}
        stats = jax.jit(lambda w, h, t: ref.head_stats(
            w, h, t, rcfg, int(chk["vocab_blocks"])))
        _PROGRAMS[key] = embed, blocks, stats
    return _PROGRAMS[key]


def check_against_reference(ctx, state, kept, slots=None):
    """Prompt and answer through the pool against the reference's full
    forward, on the sample of served requests that kept their tokens:
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
    s_ref, nb = int(chk["sample_max_total"]), int(chk["reference_batch"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items()
            if not isinstance(v, dict) or k == "linear_attn_config"}
    # the operands the configuration STATES are rounded are rounded in
    # the reference too; its arithmetic stays float32 at "highest"
    rcfg["matmul_inputs"] = chk.get("matmul_inputs")
    held = held_of(cfg)
    embed, blocks, stats = _reference_programs(ref, rcfg, chk, held)
    kinds = ref.kinds_of(rcfg)
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(len(kinds))]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, hits, finite = [], 0, True
    shares = np.zeros((len(kinds), 2))
    groups = [kept[i:i + nb] for i in range(0, len(kept), nb)]
    for group in groups:
        toks = np.zeros((nb, s_ref), np.int32)
        for i, (prompt, got, _) in enumerate(group):
            toks[i, :len(prompt)] = prompt
            toks[i, len(prompt):len(prompt) + len(got)] = got
        dev = jax.device_put(toks, ctx.device)
        h = embed(ends, dev)
        for i, kind in enumerate(kinds):
            h, sh = blocks[kind](by_layer[i], h)
            shares[i] += np.asarray(sh) / len(groups)
        # position s predicts the token at s + 1
        nxt = jnp.concatenate([dev[:, 1:], dev[:, :1]], axis=1)
        hi, lo, arg, at = (np.asarray(x) for x in stats(ends, h, nxt))
        for i, (prompt, got, _) in enumerate(group):
            sl = slice(len(prompt) - 1, len(prompt) - 1 + len(got))
            gap = (hi[i, sl] - at[i, sl]) / (hi[i, sl] - lo[i, sl])
            finite = finite and bool(np.isfinite(gap).all())
            gaps.append(gap)
            hits += int((arg[i, sl] == got).sum())
    gaps = np.concatenate(gaps)
    mean, worst = float(gaps.mean()), float(gaps.max())
    reused = sum(1 for _, _, before in kept
                 if slots is not None and before >= slots)
    branch_min = float(shares.min())
    ok = (finite and mean <= mean_max and worst <= worst_max
          and reused == len(kept)
          and branch_min >= float(chk["min_branch_share"]))
    return ok, {"requests": len(kept), "tokens": int(gaps.size),
                "prompt_lens": [len(k[0]) for k in kept],
                "argmax_agreement": "%d/%d" % (hits, gaps.size),
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
                "in_reused_slots": reused,
                "branch_share_of_residual": {
                    "layers_x_[mixer,experts]": np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def expert_counts_add_up(cfg, delta) -> bool:
    """``pooled_mtp_routed_lm``'s rule over the HELD experts (this
    configuration counts them under ``n_routed_experts``)."""
    return _mtp.expert_counts_add_up(
        {"num_experts": cfg["n_routed_experts"]}, delta)


def make_server(cfg, state, build):
    """The cell's ``DecodeServer``: what ``run`` measures and what the
    harmed-variant test serves through."""
    from paddle_tpu.serving.decode import DecodeServer

    sv = cfg["serving"]
    step_fn, make_cache = build(state, cfg, kv_dtype=sv["kv_dtype"],
                                held=held_of(cfg))
    return DecodeServer(
        step_fn, make_cache, eos_id=int(cfg["vocab_size"]),
        max_seq_len=sv["max_seq_len"], max_slots=sv["slot_ladder"][-1],
        slot_ladder=tuple(sv["slot_ladder"]),
        len_ladder=tuple(sv["len_ladder"]),
        steps_per_tick=sv["steps_per_tick"],
        queue_capacity=sv["queue_capacity"],
        target_queue_wait_ms=sv["target_queue_wait_ms"],
        kv_dtype=sv["kv_dtype"], name="bench-" + cfg["name"])


def run(ctx):
    build, parts = builder()
    from paddle_tpu import grouped_matmul, monitor

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    if mix["kind"] != "closed_loop":
        # the reused-slot argument above is a closed loop's
        raise ValueError("family pooled_kda_routed_lm cannot drive a %r mix"
                         % mix["kind"])
    vocab = int(cfg["vocab_size"])
    slots, rung = int(sv["slot_ladder"][-1]), int(sv["len_ladder"][-1])
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
                             "state_resets", "kv_positions_read",
                             "kv_positions_live") + EXPERT_COUNTERS}
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
        with ctx.phase("pool_fill"):
            # one pilot request brings the pool's state to the device
            # before the traffic starts and holds its slot into the ramp
            pilot = srv.submit({"tokens": np.zeros(1, np.int32)},
                               max_new_tokens=int(mix["pilot_tokens"]))
            next(pilot.stream())
        with ctx.phase("ramp"):
            t_ramp = time.perf_counter()
            sched = traffic.ClosedLoopSource(mix, ctx.seed, vocab)
            prompts = sched.prompts  # grows as the clients draw
            load.start_closed_loop(sched, int(mix["clients"]),
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
        # a stopped server drops its pool: the reference needs the room
        srv.stop(drain=False, timeout=60.0)

    s = loadgen.summarize(load.records, load.token_events, w0, t1,
                          mix.get("limits"))
    # idx counts the load's requests; the pilot went before them all
    kept = [(prompts[r.idx], np.concatenate(r.tokens).astype(np.int32),
             r.idx + 1)
            for r in load.records
            if r.keep and r.status == "done" and r.n_tok == r.output_len]
    with ctx.annotate("bench/reference_check"):
        ref_ok, ref_info = check_against_reference(ctx, state, kept, slots)
    stamps = loadgen.stamp_faults(load.records, load.sweeps, w0, t1)
    delta = {k: c1[k] - c0[k] for k in c0}
    ticks = delta["ticks"]
    steps = ticks * sv["steps_per_tick"]
    lowered = {path: monitor.counter_value("delta_update_lowered_total",
                                           path=path)
               for path in ("kernel", "xla")}
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
        # the program's gauges against the benchmark's own arithmetic
        "pool_bytes_as_computed": c1["recurrent_bytes"]
        == costs.recurrent_state_bytes_per_slot(cfg) * slots
        + costs.expert_stats_bytes(cfg)
        and c1["kv_bytes"] == costs.kv_bytes_per_position(cfg) * rung * slots,
        "expert_counts_add_up": expert_counts_add_up(cfg, delta),
        # a decay a channel, and on the chip the kernel: a change cannot
        # win the cell by serving another rule or the slower form unseen
        "delta_rule_as_declared": decay["channel"] > 0 and not decay["head"]
        and (not on_tpu or (lowered["kernel"] > 0 and not lowered["xla"])),
    }
    layer_steps = delta["expert_layer_steps"]
    d = parts.kda_dims(cfg)
    n_held = held_of(cfg)[1] - held_of(cfg)[0]
    # per step, summed over the layers
    touched = (delta["experts_touched"] / layer_steps * d.n_layer
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
            window_counters=delta,
            kv_cache_bytes=c1["kv_bytes"],
            recurrent_state_bytes=c1["recurrent_bytes"],
            pool_bytes_computed=costs.pool_bytes(cfg, slots, rung),
            weight_bytes_computed=costs.weight_bytes_held(cfg),
            delta_update_lowered=lowered, delta_update_decay=decay,
            experts_touched_per_layer_step=(
                delta["experts_touched"] / layer_steps if layer_steps
                else None),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    live = s["position_steps"] / steps if steps else 0.0
    rows = s["row_steps"] / steps if steps else 0.0
    pairs = -(-slots * d.top_k // grouped_matmul.ROW_TILE) \
        * grouped_matmul.ROW_TILE
    rep = d.n_head // d.n_kv_head
    counters = {
        "window_s": s["window_s"],
        "steps": steps, "ticks": ticks,
        "steps_per_dispatch": sv["steps_per_tick"],
        "generated_tokens": delta["tokens"],
        "prefill_tokens": delta["prefill_tokens"],
        "live_positions_per_step": live, "rows_stepped_per_step": rows,
        "in_flight_at_close": s["in_flight_at_close"],
        "queue_depth_at_close": metrics["queue_depth"],
        "state_resets": delta["state_resets"],
        "kv_cache_bytes": c1["kv_bytes"],
        "recurrent_state_bytes": c1["recurrent_bytes"],
        "full_positions_read": delta["kv_positions_read"],
        "full_positions_live": delta["kv_positions_live"],
        "delta_update_lowered": lowered, "delta_update_decay": decay,
        # the state leaf as the program declares it, the conv window
        # before and after the fresh row joins it
        "delta_state_scopes": [parts.DELTA_UPDATE_SCOPE],
        "delta_state_shapes": [[slots] + list(d.state_shape)],
        "short_conv_scopes": [parts.SHORT_CONV_SCOPE],
        "short_conv_shapes": [[slots, d.conv_len - 1, d.d_qkv],
                              [slots, d.conv_len, d.d_qkv]],
        # the two low-rank pairs' matrices and their intermediate, the
        # step gate's matrix: what only the making of the gates bears
        "channel_gate_scopes": [parts.CHANNEL_GATES_SCOPE],
        "channel_gate_shapes": [[d.d_model, d.d_rank], [d.d_rank, d.d_key],
                                [slots, d.d_rank], [d.d_model, d.lin_heads]],
        # the G layer's K/V leaf, its views by heads, the scores over it
        # and the gate's matrix
        "full_attention_scopes": [parts.FULL_ATTENTION_SCOPE],
        "full_attention_shapes": [
            [slots, rung, d.d_kv], [slots, rung, d.n_kv_head, d.head_dim],
            [slots, d.n_kv_head, d.head_dim, rung],
            [slots, d.n_kv_head, rep, rung],
            [slots, d.n_kv_head, rung], [slots, rung, d.n_kv_head]],
        # the held experts: what the counters' groups are over
        "num_experts": n_held,
        "experts_touched_per_step": touched,
        "expert_kernel_names": [grouped_matmul.KERNEL_NAME],
        "expert_shapes": [[n_held, d.d_model, 2 * d.d_expert],
                          [n_held, d.d_expert, d.d_model],
                          [pairs, 2 * d.d_expert], [pairs, d.d_expert]],
        "route_shapes": [[slots, d.n_expert], [slots, d.top_k],
                         [slots * d.top_k], [pairs, d.d_model],
                         [slots, d.top_k, d.d_model],
                         [slots * d.top_k, n_held], [n_held], [d.n_expert]],
        "shared_expert_scopes": [],
        "shared_expert_shapes": [
            [d.d_model, 2 * d.n_shared * d.d_expert],
            [slots, 2 * d.n_shared * d.d_expert],
            [slots, d.n_shared * d.d_expert]],
        "delta_state_min_bytes": costs.delta_update_min_bytes(cfg, rows),
        "experts_min_bytes": costs.experts_min_bytes(cfg, touched, rows),
        "experts_flops": costs.experts_flops(cfg, rows),
        "step_min_bytes": costs.step_min_bytes(cfg, live, rows, touched),
    }
    counters.update({c: delta[c] for c in EXPERT_COUNTERS})
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
