"""Family ``pooled_decode_lm``: a decoder-only LM served token by token
through ``decoding.make_transformer_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` (continuous batching over a KV slot
pool), under an open-loop or closed-loop traffic mix.

From the program this file takes the system under test and nothing
else: the model's parameter names and shapes (``models.transformer.
transformer_lm``), the step builder, the server, and three monitor
counters.  Lengths, arrivals, stamps, percentiles, byte counts and the
comparison that decides ``correct`` are the benchmark's own
(``benchmark/lib``, the configuration's reference beside its file).
"""
from __future__ import annotations

import os
import queue
import time

import numpy as np

from benchmark.lib import costs, harness, loadgen, traffic


def _param_shapes(cfg):
    """Names and shapes of the served LM's parameters, read off the
    Program the model code builds (no run, nothing allocated)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, models

    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [8], dtype="int64")
        models.transformer.transformer_lm(
            src, None, vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
            n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_inner=cfg["assumed"]["n_inner"], seq_len=8,
            max_pos=cfg["n_positions"], dropout_rate=0.0, is_test=True)
    return {p.name: tuple(int(s) for s in p.shape)
            for p in prog.all_parameters()}


def make_weights(cfg, device):
    """Every parameter made on the device, in fp32 as it is served, by
    ONE jitted call from the configuration's weight seed."""
    import jax
    import jax.numpy as jnp

    shapes = _param_shapes(cfg)
    names = sorted(shapes)
    std = float(cfg["initializer_range"])

    def make(key):
        out = {}
        for i, n in enumerate(names):
            if n.endswith("_scale"):
                out[n] = jnp.ones(shapes[n], jnp.float32)
            elif n.endswith(("_b", "_bias")):
                out[n] = jnp.zeros(shapes[n], jnp.float32)
            else:  # matrices and embedding tables
                out[n] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shapes[n], jnp.float32)
        return out

    with jax.default_device(device):
        key = jax.random.PRNGKey(int(cfg["assumed"]["weight_seed"]))
        state = jax.jit(make)(key)
    jax.block_until_ready(state)
    return state


def _drain(req):
    """Every chunk delivered to ``req`` so far, without blocking.
    ``DecodeRequest`` offers only a blocking ``stream()``; the chunk
    queue behind it is read directly (PERF.md, Open questions: a public
    non-blocking poll)."""
    out = []
    while True:
        try:
            kind, val = req._chunks.get_nowait()
        except queue.Empty:
            return out
        out.append((kind, val if kind == "tokens" else req._exc))


def check_against_reference(ctx, state, kept):
    """Prefill + decode through the pool against the reference's full
    forward, on the sample of served requests that kept their tokens.
    Returns (ok, details)."""
    import jax

    cfg = ctx.cfg
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_" + cfg["name"])
    share_max = float(cfg["check"]["logit_gap_share"])
    if not kept:
        return False, {"why": "no finished request kept its tokens"}
    s_ref = int(cfg["check"]["sample_max_total"])
    toks = np.zeros((len(kept), s_ref), np.int32)
    outs = []
    for i, (prompt, rec) in enumerate(kept):
        got = np.concatenate(rec.tokens).astype(np.int32)
        outs.append(got)
        toks[i, :len(prompt)] = prompt
        toks[i, len(prompt):len(prompt) + len(got)] = got
    fwd = jax.jit(lambda w, t: ref.forward(
        w, t, int(cfg["n_layer"]), int(cfg["n_head"]),
        float(cfg["layer_norm_epsilon"])))
    logits = np.asarray(fwd(state, jax.device_put(toks, ctx.device)))
    worst, hits, n_tok = 0.0, 0, 0
    for i, ((prompt, _), got) in enumerate(zip(kept, outs)):
        for j, tok in enumerate(got):
            row = logits[i, len(prompt) + j - 1]  # predicts that position
            worst = max(worst, float(
                (row.max() - row[tok]) / (row.max() - row.min())))
            hits += int(row.argmax() == tok)
            n_tok += 1
    ok = bool(np.isfinite(logits).all()) and worst <= share_max
    return ok, {"requests": len(kept), "tokens": n_tok,
                "argmax_agreement": "%d/%d" % (hits, n_tok),
                "worst_logit_gap_share": worst,
                "logit_gap_share_allowed": share_max}


def run(ctx):
    from paddle_tpu import decoding, monitor
    from paddle_tpu.serving.decode import DecodeServer

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    vocab = int(cfg["vocab_size"])
    with ctx.phase("weights"):
        state = make_weights(cfg, ctx.device)
    with ctx.phase("build"):
        step_fn, make_cache = decoding.make_transformer_lm_pooled_step_fn(
            state, vocab, cfg["n_embd"], cfg["n_layer"], cfg["n_head"],
            cfg["assumed"]["n_inner"], kv_dtype=sv["kv_dtype"])
        srv = DecodeServer(
            step_fn, make_cache, eos_id=vocab,
            max_seq_len=sv["max_seq_len"], max_slots=sv["slot_ladder"][-1],
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
            return {k: monitor.counter_value("serving_decode_%s_total" % k)
                    for k in ("tokens", "prefill_tokens", "ticks")}

        load = loadgen.LoadRun(
            submit=lambda p, n: srv.submit({"tokens": p}, max_new_tokens=n),
            drain=_drain,
            produced=lambda: monitor.counter_value(
                "serving_decode_tokens_total"),
            annotate=ctx.annotate)
        chk = cfg["check"]
        with ctx.phase("pool_fill"):
            # The pool's state is born on the host and crosses to the
            # device with the first admitted request (12 GB here); an
            # idle server drops it again.  One pilot request pays that
            # before the traffic starts, and the ramp begins at the
            # pilot's first token, while it still holds its slot.
            pilot = srv.submit({"tokens": np.zeros(1, np.int32)},
                               max_new_tokens=int(mix["pilot_tokens"]))
            next(pilot.stream())
        with ctx.phase("ramp"):
            t_ramp = time.perf_counter()
            ramp_s = float(mix["ramp_s"])
            if mix["kind"] == "open_loop":
                sched = traffic.open_loop_schedule(
                    mix, ctx.seed, ctx.seconds + 2.0, vocab)
                prompts = sched["prompts"]
                load.start_open_loop(sched, t_ramp, chk["sample_requests"],
                                     chk["sample_max_total"])
            elif mix["kind"] == "closed_loop":
                sched = traffic.ClosedLoopSource(mix, ctx.seed, vocab)
                prompts = sched.prompts  # grows as the clients draw
                load.start_closed_loop(sched, int(mix["clients"]),
                                       chk["sample_requests"],
                                       chk["sample_max_total"])
            else:
                raise ValueError("family pooled_decode_lm cannot drive a "
                                 "%r mix" % mix["kind"])
            time.sleep(max(0.0, t_ramp + ramp_s - time.perf_counter()))
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
        srv.stop(drain=False, timeout=60.0)

    s = loadgen.summarize(load.records, load.token_events, w0, t1,
                          mix.get("limits"))
    kept = [(prompts[r.idx], r) for r in load.records
            if r.keep and r.status == "done" and r.n_tok == r.output_len]
    ref_ok, ref_info = check_against_reference(ctx, state, kept)
    stamps = loadgen.stamp_faults(load.records, load.sweeps, w0, t1)
    ticks = c1["ticks"] - c0["ticks"]
    steps = ticks * sv["steps_per_tick"]
    checks = {
        "reference": ref_ok,
        "no_window_compiles": ctx.window["compiles"]["compiles"] == 0,
        "no_server_recompiles": metrics["recompiles"] == 0,
        "served_something": len(s["tpot_ms"]) > 0 and s["tokens_delivered"] > 0,
        "no_failed_requests": s["failed"] == 0,
        "stamps_in_time": stamps["ok"],
    }
    ctx.say("requests", sent_total=len(load.records),
            sent_in_window=s["sent_in_window"], ended_in_window=s["attempted"],
            failed_in_window=s["failed"],
            in_flight_at_close=s["in_flight_at_close"],
            refused_total=sum(r.status == "refused" for r in load.records),
            first_failures=[r.tokens for r in load.records
                            if r.status in ("failed", "refused")][:3],
            realised_rate_per_s=s["sent_in_window"] / s["window_s"],
            prompt_len_done=traffic.length_summary(s["prompt_len_done"]),
            output_len_done=traffic.length_summary(s["output_len_done"]),
            ttft_samples=len(s["ttft_ms"]), tpot_samples=len(s["tpot_ms"]),
            attainment=s.get("attainment"),
            stamps=dict(stamps, sweep_delay_s=load.delay_s),
            tick_ms=s["window_s"] * 1e3 / ticks if ticks else None,
            warmup_compiles=int(warm_compiles),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    if s["ttft_ms"]:
        e2e["ttft_p95_ms"] = float(np.percentile(s["ttft_ms"], 95))
    if s["tpot_ms"]:
        e2e["tpot_p95_ms"] = float(np.percentile(s["tpot_ms"], 95))
    live = s["position_steps"] / steps if steps else 0.0
    rows = s["row_steps"] / steps if steps else 0.0
    counters = {
        "window_s": s["window_s"],
        "steps": steps, "ticks": ticks,
        "steps_per_dispatch": sv["steps_per_tick"],
        "generated_tokens": c1["tokens"] - c0["tokens"],
        "prefill_tokens": c1["prefill_tokens"] - c0["prefill_tokens"],
        "tpot_ms": s["tpot_ms"], "ttft_ms": s["ttft_ms"],
        "gen_late_ms": s["gen_late_ms"], "stall_ms": s["stall_ms"],
        "live_positions_per_step": live, "rows_stepped_per_step": rows,
        "attainment": s.get("attainment"),
        "in_flight_at_close": s["in_flight_at_close"],
        "queue_depth_at_close": metrics["queue_depth"],
        "step_min_bytes": costs.decode_step_min_bytes(
            dict(cfg, n_inner=cfg["assumed"]["n_inner"]), live, rows),
    }
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
