"""Family ``pooled_hybrid_ssm_lm``: a hybrid SSM + attention decoder
(``model_type: falcon_h1``) served token by token through
``decoding.make_hybrid_ssm_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` (continuous batching over ONE slot pool
that holds K/V rows and recurrent SSM / conv state side by side), under
a closed-loop traffic mix.

From the program this file takes the system under test and nothing
else: the parameter names and shapes (``hybrid_ssm.param_shapes``), the
step builder, the server, five monitor series (``serving_decode_{tokens,
prefill_tokens,ticks,state_resets}_total``, ``serving_{kv_cache,
recurrent_state}_bytes``) and, in the device trace, the shape of the
state the ``ssm_state_update`` scope updates.  Lengths, arrivals,
stamps, percentiles, the bytes a step needs (``lib/costs_hybrid_ssm``)
and the comparison that decides ``correct`` (the configuration's
reference beside its file) are the benchmark's own; the window's loop
is ``pooled_decode_lm``'s, kept here in a copy because that file's
``run`` names its own builder.

``correct`` holds the served tokens of a sample of requests to the
reference's full forward, logits not tokens, and besides that: at least
``check.min_reused_share`` of the sample sat in a slot another request
had left (a closed loop samples only requests sent after every client's
first, and by then ``slots`` admissions have gone before, the pilot
among them: every slot has been held), and every branch of the block
(mixer, attention, MLP) is at least ``check.min_branch_share`` of the
residual it is added to, by the reference's own measure — a branch
smaller than that the comparison could not see.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmark.lib import costs_hybrid_ssm as costs
from benchmark.lib import harness, loadgen, traffic

_drain = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_decode_lm.py"),
    "pooled_decode_lm")._drain

VECTOR_INIT = {  # by the parameter name's ending; the rest are matrices
    "norm1": "ones", "norm2": "ones", "_final_norm": "ones",
    "ssm_norm": "ones", "ssm_D": "ones", "ssm_conv_b": "zeros",
    "ssm_A_log": "a_log", "ssm_dt_bias": "dt_bias", "ssm_conv_w": "conv_w"}


def builder():
    """The program's step builder and parameter schema, or a clean exit
    where the program has none (a commit before PR 27)."""
    from paddle_tpu import decoding
    try:
        from paddle_tpu import hybrid_ssm
        return decoding.make_hybrid_ssm_lm_pooled_step_fn, hybrid_ssm
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve a "
                         "falcon_h1 block (%s)" % exc)


def make_weights(cfg, device, hybrid_ssm):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed: matrices in bf16 as they are served,
    vectors and the conv kernel in float32 (``assumed.weights``)."""
    import jax
    import jax.numpy as jnp

    shapes = hybrid_ssm.param_shapes(cfg)
    names = sorted(shapes)
    a = cfg["assumed"]
    std, xbc_std = float(a["initializer_range"]), float(a["ssm_in_xbc_std"])
    d_ssm = int(cfg["mamba_d_ssm"])
    d_xbc = d_ssm + 2 * int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    f32 = jnp.float32

    def make(key):
        out = {}
        for i, n in enumerate(names):
            k, shp = jax.random.fold_in(key, i), shapes[n]
            kind = next((v for e, v in VECTOR_INIT.items() if n.endswith(e)),
                        "matrix")
            if kind == "ones":
                out[n] = jnp.ones(shp, f32)
            elif kind == "zeros":
                out[n] = jnp.zeros(shp, f32)
            elif kind == "a_log":
                out[n] = jnp.log(jax.random.uniform(k, shp, f32, 1.0, 16.0))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shp, f32, np.log(1e-3), np.log(1e-1)))
                out[n] = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "conv_w":
                lim = 1.0 / np.sqrt(shp[0])
                out[n] = jax.random.uniform(k, shp, f32, -lim, lim)
            else:
                w = jax.random.normal(k, shp, jnp.bfloat16)
                if n.endswith("ssm_in"):
                    col = np.arange(shp[1])
                    scale = np.where((col >= d_ssm) & (col < d_ssm + d_xbc),
                                     xbc_std, std).astype("float32")
                    out[n] = (w * scale).astype(jnp.bfloat16)
                else:
                    out[n] = (w * std).astype(jnp.bfloat16)
        return out

    with jax.default_device(device):
        key = jax.random.PRNGKey(int(a["weight_seed"]))
        state = jax.jit(make)(key)
    jax.block_until_ready(state)
    return state


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
    share_max = float(chk["logit_gap_share"])
    if not kept:
        return False, {"why": "no finished request kept its tokens"}
    s_ref, nb = int(chk["sample_max_total"]), int(chk["reference_batch"])
    layers = int(cfg["num_hidden_layers"])
    # the reference never sees the config's rehearse group or bytes
    rcfg = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    embed = jax.jit(lambda w, t: ref.embed(w, t, rcfg))
    # one program for every layer: each layer's weights go in under
    # layer 0's names
    block = jax.jit(lambda w, h: ref.block(
        w, 0, h, rcfg, mlp_blocks=int(chk["mlp_blocks"])))
    stats = jax.jit(lambda w, h, t: ref.head_stats(
        w, h, t, rcfg, int(chk["vocab_blocks"])))
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(layers)]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    worst, hits, n_tok, finite = 0.0, 0, 0, True
    shares = np.zeros((layers, 3))
    groups = [kept[i:i + nb] for i in range(0, len(kept), nb)]
    for group in groups:
        toks = np.zeros((nb, s_ref), np.int32)
        for i, (prompt, got, _) in enumerate(group):
            toks[i, :len(prompt)] = prompt
            toks[i, len(prompt):len(prompt) + len(got)] = got
        dev = jax.device_put(toks, ctx.device)
        h = embed(ends, dev)
        for i in range(layers):
            h, sh = block(by_layer[i], h)
            shares[i] += np.asarray(sh) / len(groups)
        # position s predicts the token at s + 1
        nxt = jnp.concatenate([dev[:, 1:], dev[:, :1]], axis=1)
        hi, lo, arg, at = (np.asarray(x) for x in stats(ends, h, nxt))
        for i, (prompt, got, _) in enumerate(group):
            sl = slice(len(prompt) - 1, len(prompt) - 1 + len(got))
            gap = (hi[i, sl] - at[i, sl]) / (hi[i, sl] - lo[i, sl])
            finite = finite and bool(np.isfinite(gap).all())
            worst = max(worst, float(gap.max()))
            hits += int((arg[i, sl] == got).sum())
            n_tok += len(got)
    reused = sum(1 for _, _, before in kept
                 if slots is not None and before >= slots)
    branch_min = float(shares.min())
    ok = (finite and worst <= share_max
          and reused >= float(chk["min_reused_share"]) * len(kept)
          and branch_min >= float(chk["min_branch_share"]))
    return ok, {"requests": len(kept), "tokens": n_tok,
                "argmax_agreement": "%d/%d" % (hits, n_tok),
                "worst_logit_gap_share": worst,
                "logit_gap_share_allowed": share_max,
                "in_reused_slots": reused,
                "branch_share_of_residual": {
                    "layers_x_[mixer,attention,mlp]":
                        np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def run(ctx):
    build, hybrid_ssm = builder()
    from paddle_tpu import monitor
    from paddle_tpu.serving.decode import DecodeServer

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    vocab = int(cfg["vocab_size"])
    slots = int(sv["slot_ladder"][-1])
    with ctx.phase("weights"):
        state = make_weights(cfg, ctx.device, hybrid_ssm)
    with ctx.phase("build"):
        step_fn, make_cache = build(
            state, cfg, kv_dtype=sv["kv_dtype"],
            ssm_state_dtype=cfg["assumed"]["ssm_state_dtype"])
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
                             "state_resets")}
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
                # the reused-slot argument above is a closed loop's
                raise ValueError("family pooled_hybrid_ssm_lm cannot drive "
                                 "a %r mix" % mix["kind"])
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
    ticks = c1["ticks"] - c0["ticks"]
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
        == per_slot * slots,
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
            state_resets_in_window=c1["state_resets"] - c0["state_resets"],
            kv_cache_bytes=c1["kv_bytes"],
            recurrent_state_bytes=c1["recurrent_bytes"],
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    live = s["position_steps"] / steps if steps else 0.0
    rows = s["row_steps"] / steps if steps else 0.0
    d = hybrid_ssm.dims(cfg)
    counters = {
        "window_s": s["window_s"],
        "steps": steps, "ticks": ticks,
        "steps_per_dispatch": sv["steps_per_tick"],
        "generated_tokens": c1["tokens"] - c0["tokens"],
        "prefill_tokens": c1["prefill_tokens"] - c0["prefill_tokens"],
        "live_positions_per_step": live, "rows_stepped_per_step": rows,
        "in_flight_at_close": s["in_flight_at_close"],
        "queue_depth_at_close": metrics["queue_depth"],
        "state_resets": c1["state_resets"] - c0["state_resets"],
        "kv_cache_bytes": c1["kv_bytes"],
        "recurrent_state_bytes": c1["recurrent_bytes"],
        "ssm_state_shape": [slots, d.ssm_heads, d.ssm_head_dim, d.d_state],
        "ssm_update_scope": hybrid_ssm.SSM_UPDATE_SCOPE,
        "ssm_update_min_bytes": costs.ssm_update_min_bytes(cfg, rows),
        "step_min_bytes": costs.step_min_bytes(cfg, live, rows),
    }
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
