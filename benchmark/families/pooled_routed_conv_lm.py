"""Family ``pooled_routed_conv_lm``: a decoder of gated short
convolutions beside grouped-query attention with a mixture of routed
experts after most layers (``model_type: lfm2_moe``), served token by
token through ``decoding.make_routed_conv_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` (continuous batching over ONE slot pool
whose layers hold different leaves: K/V rows or a conv window), under a
closed-loop traffic mix.

From the program this file takes the system under test and nothing
else: the parameter names and shapes (``routed_experts.param_shapes``),
the step builder, the server, its monitor series (``serving_decode_
{tokens,prefill_tokens,ticks,state_resets,expert_assignments,
experts_touched,expert_peak_load,expert_layer_steps}_total``,
``serving_{kv_cache,recurrent_state}_bytes``) and, in the device trace,
the grouped product's kernel name.  Lengths, arrivals, stamps,
percentiles, the bytes a step needs (``lib/costs_moe``) and the
comparison that decides ``correct`` (the configuration's reference
beside its file) are the benchmark's own; the window's loop is
``pooled_hybrid_ssm_lm``'s, kept here in a copy because that file's
``run`` names its own builder.

``correct`` holds the served tokens of a sample of requests to the
reference's full forward — computed in float32 at "highest" on the
operands the configuration states (``check.matmul_inputs``; the
reference's docstring says why a mixture needs that) — logits not
tokens, under TWO bounds: the mean over the sampled tokens of each
token's logit gap share, tight, and the worst token's, loose.  Besides
that: at least ``check.min_reused_share`` of
the sample sat in a slot another request had left, every branch of
every block is at least ``check.min_branch_share`` of the residual it is
added to, and the program's expert counters add up (every live row of
every expert layer was given ``num_experts_per_tok`` experts, no layer
touched more experts than it has or fewer than its peak implies).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark.lib import costs_moe as costs
from benchmark.lib import harness, loadgen, traffic

_drain = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_decode_lm.py"),
    "pooled_decode_lm")._drain

EXPERT_COUNTERS = ("expert_assignments", "experts_touched",
                   "expert_peak_load", "expert_layer_steps")


def builder():
    """The program's step builder and parameter schema, or a clean exit
    where the program has none (a commit before PR 40)."""
    from paddle_tpu import decoding
    try:
        from paddle_tpu import routed_experts
        return decoding.make_routed_conv_lm_pooled_step_fn, routed_experts
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve an "
                         "lfm2_moe block (%s)" % exc)


def make_weights(cfg, device, routed_experts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed (``assumed.weights``): matrices in bf16
    as they are served; norms, the router, its bias and the conv kernel
    in float32."""
    import jax
    import jax.numpy as jnp

    shapes = routed_experts.param_shapes(cfg)
    names = sorted(shapes)
    a = cfg["assumed"]
    std, bias = float(a["initializer_range"]), float(a["expert_bias_range"])
    f32 = jnp.float32

    def make(key):
        out = {}
        for i, n in enumerate(names):
            k, shp = jax.random.fold_in(key, i), shapes[n]
            if n.endswith(("_norm", "_layernorm")):
                out[n] = jnp.ones(shp, f32)
            elif n.endswith("expert_bias"):
                out[n] = jax.random.uniform(k, shp, f32, -bias, bias)
            elif n.endswith("conv_w"):
                lim = 1.0 / np.sqrt(shp[0])
                out[n] = jax.random.uniform(k, shp, f32, -lim, lim)
            elif n.endswith("router"):
                out[n] = jax.random.normal(k, shp, f32) * std
            else:
                out[n] = (jax.random.normal(k, shp, jnp.bfloat16)
                          * std).astype(jnp.bfloat16)
        return out

    with jax.default_device(device):
        key = jax.random.PRNGKey(int(a["weight_seed"]))
        state = jax.jit(make)(key)
    jax.block_until_ready(state)
    return state


_PROGRAMS = {}


def _reference_programs(ref, rcfg, chk, kinds, layers, n_dense):
    """The reference's jitted pieces, built once for one configuration
    (a test serves many variants against the same reference)."""
    import jax

    key = json.dumps([rcfg, chk], sort_keys=True, default=str)
    if key not in _PROGRAMS:
        embed = jax.jit(lambda w, t: ref.embed(w, t, rcfg))
        # one program for every layer of a kind: each layer's weights go
        # in under layer 0's names
        blocks = {}
        for kind, dense in {(kinds[i], i < n_dense) for i in range(layers)}:
            blocks[kind, dense] = jax.jit(
                lambda w, h, kind=kind, dense=dense: ref.block(
                    w, 0, h, rcfg, kind, dense,
                    ffn_blocks=int(chk["ffn_blocks"]),
                    expert_blocks=int(chk["expert_blocks"])))
        stats = jax.jit(lambda w, h, t: ref.head_stats(
            w, h, t, rcfg, int(chk["vocab_blocks"])))
        _PROGRAMS[key] = embed, blocks, stats
    return _PROGRAMS[key]


def check_against_reference(ctx, state, kept, slots=None):
    """Prefill + decode through the pool against the reference's full
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
    layers, n_dense = int(cfg["num_hidden_layers"]), int(
        cfg["num_dense_layers"])
    kinds = list(cfg["layer_types"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items()
            if not isinstance(v, dict) or k == "rope_parameters"}
    # the operands the configuration STATES are rounded (its departures:
    # matmul inputs, stored K/V) are rounded in the reference too; its
    # arithmetic stays float32 at "highest" (the reference's docstring)
    rcfg["matmul_inputs"] = chk.get("matmul_inputs")
    embed, blocks, stats = _reference_programs(ref, rcfg, chk, kinds, layers,
                                               n_dense)
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(layers)]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, hits, finite = [], 0, True
    shares = np.zeros((layers, 2))
    groups = [kept[i:i + nb] for i in range(0, len(kept), nb)]
    for group in groups:
        toks = np.zeros((nb, s_ref), np.int32)
        for i, (prompt, got, _) in enumerate(group):
            toks[i, :len(prompt)] = prompt
            toks[i, len(prompt):len(prompt) + len(got)] = got
        dev = jax.device_put(toks, ctx.device)
        h = embed(ends, dev)
        for i in range(layers):
            h, sh = blocks[kinds[i], i < n_dense](by_layer[i], h)
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
          and reused >= float(chk["min_reused_share"]) * len(kept)
          and branch_min >= float(chk["min_branch_share"]))
    return ok, {"requests": len(kept), "tokens": int(gaps.size),
                "argmax_agreement": "%d/%d" % (hits, gaps.size),
                "mean_logit_gap_share": mean,
                "mean_gap_share_allowed": mean_max,
                "worst_logit_gap_share": worst,
                "worst_gap_share_allowed": worst_max,
                "gap_share_quantiles": {
                    q: float(np.quantile(gaps, float(q)))
                    for q in ("0.5", "0.9", "0.99")},
                "in_reused_slots": reused,
                "branch_share_of_residual": {
                    "layers_x_[operator,ffn_or_experts]":
                        np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def expert_counts_add_up(cfg, delta) -> bool:
    """What must hold of the four expert counters' deltas whatever the
    routing: every counted (row, choice) pair is one of ``top_k`` of a
    live row, a layer-step touches at least ``peak``-implied and at most
    ``num_experts`` experts, and the largest group is at least the mean
    group."""
    k, n = int(cfg["num_experts_per_tok"]), int(cfg["num_experts"])
    pairs, touched, peak, ls = (delta[c] for c in EXPERT_COUNTERS)
    if not ls:
        return False
    return bool(pairs % k == 0 and ls <= touched <= n * ls
                and touched <= pairs and peak * n >= pairs
                and peak * touched >= pairs)


def run(ctx):
    build, routed_experts = builder()
    from paddle_tpu import monitor
    from paddle_tpu.serving.decode import DecodeServer

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    vocab = int(cfg["vocab_size"])
    slots = int(sv["slot_ladder"][-1])
    with ctx.phase("weights"):
        state = make_weights(cfg, ctx.device, routed_experts)
    with ctx.phase("build"):
        step_fn, make_cache = build(state, cfg, kv_dtype=sv["kv_dtype"])
        srv = DecodeServer(
            step_fn, make_cache, eos_id=vocab,
            max_seq_len=sv["max_seq_len"], max_slots=slots,
            slot_ladder=tuple(sv["slot_ladder"]),
            len_ladder=tuple(sv["len_ladder"]),
            steps_per_tick=sv["steps_per_tick"],
            queue_capacity=sv["queue_capacity"],
            target_queue_wait_ms=sv["target_queue_wait_ms"],
            kv_dtype=sv["kv_dtype"], name="bench-" + cfg["name"])
    load = None
    try:
        with ctx.phase("compile_or_cache_load"):
            warm_compiles = srv.warmup()

        def counters_now():
            out = {k: monitor.counter_value("serving_decode_%s_total" % k)
                   for k in ("tokens", "prefill_tokens", "ticks",
                             "state_resets") + EXPERT_COUNTERS}
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
            if mix["kind"] != "closed_loop":
                # the reused-slot argument is a closed loop's
                raise ValueError("family pooled_routed_conv_lm cannot "
                                 "drive a %r mix" % mix["kind"])
            sched = traffic.ClosedLoopSource(mix, ctx.seed, vocab)
            prompts = sched.prompts  # grows as the clients draw
            load.start_closed_loop(sched, int(mix["clients"]),
                                   chk["sample_requests"],
                                   chk["sample_max_total"])
            time.sleep(max(0.0, t_ramp + float(mix["ramp_s"])
                           - time.perf_counter()))
        c0 = counters_now()
        w0 = ctx.open_window()
        w1 = w0 + ctx.seconds
        while True:
            left = w1 - time.perf_counter()
            if left <= 0:
                break
            ctx.tracer.maybe_start(w1)
            time.sleep(min(left, 0.25))
        c1 = counters_now()
        t1 = time.perf_counter()
        ctx.tracer.stop()  # before the traffic does
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
    per_slot = costs.recurrent_state_bytes_per_slot(cfg)
    checks = {
        "reference": ref_ok,
        "no_window_compiles": ctx.window["compiles"]["compiles"] == 0,
        "no_server_recompiles": metrics["recompiles"] == 0,
        "served_something": s["tokens_delivered"] > 0 and s["attempted"] > 0,
        "no_failed_requests": s["failed"] == 0,
        "stamps_in_time": stamps["ok"],
        # the program's gauge against the benchmark's own arithmetic
        "recurrent_bytes_as_computed": c1["recurrent_bytes"]
        == per_slot * slots + costs.expert_stats_bytes(cfg),
        "expert_counts_add_up": expert_counts_add_up(cfg, delta),
    }
    expert_layers = int(cfg["num_hidden_layers"]) - int(
        cfg["num_dense_layers"])
    layer_steps = delta["expert_layer_steps"]
    # per step, summed over the expert layers
    touched = (delta["experts_touched"] / layer_steps * expert_layers
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
            state_resets_in_window=delta["state_resets"],
            kv_cache_bytes=c1["kv_bytes"],
            recurrent_state_bytes=c1["recurrent_bytes"],
            experts_in_window={k: delta[k] for k in EXPERT_COUNTERS},
            experts_touched_per_layer_step=(
                delta["experts_touched"] / layer_steps if layer_steps
                else None),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    live = s["position_steps"] / steps if steps else 0.0
    rows = s["row_steps"] / steps if steps else 0.0
    d = routed_experts.dims(cfg)
    pairs = slots * d.top_k
    from paddle_tpu import grouped_matmul
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
        "num_experts": d.n_expert,
        "experts_touched_per_step": touched,
        "expert_kernel_names": [grouped_matmul.KERNEL_NAME],
        "expert_shapes": [[d.n_expert, d.d_model, 2 * d.d_expert],
                          [d.n_expert, d.d_expert, d.d_model],
                          [pairs, 2 * d.d_expert], [pairs, d.d_expert]],
        "route_shapes": [[slots, d.n_expert], [slots, d.top_k], [pairs],
                         [pairs, d.d_model], [slots, d.top_k, d.d_model],
                         [pairs, d.n_expert], [d.n_expert]],
        "experts_min_bytes": costs.experts_min_bytes(cfg, touched, rows),
        "step_min_bytes": costs.step_min_bytes(cfg, live, rows, touched),
    }
    counters.update({k: delta[k] for k in EXPERT_COUNTERS})
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
