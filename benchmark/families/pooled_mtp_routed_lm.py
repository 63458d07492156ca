"""Family ``pooled_mtp_routed_lm``: a decoder of short sliding-window
attention layers beside position-free global ones, routed experts beside
a shared expert, and ONE multi-token-prediction module that drafts for
its own model (``model_type: exaone_moe``), served through
``decoding.make_mtp_routed_lm_pooled_step_fn`` ->
``serving.decode.DecodeServer`` with ``serving.speculative.
make_self_draft`` attached (continuous batching over ONE slot pool: ring
leaves of the window beside rung-long leaves of the global layer and of
the module; every request ``speculative=True``: one self-drafting round
a tick), under the ``closed_loop`` traffic kind.

From the program this file takes the system under test and nothing
else: the parameter names and shapes (``mtp_routed_lm.param_shapes``),
the step builder, the self-draft attachment, the server, its monitor
series (``serving_decode_{tokens,prefill_tokens,ticks,admitted,
kv_positions_live,window_positions_read,window_positions_live,
expert_assignments,experts_touched,expert_peak_load,expert_layer_steps}
_total``, ``serving_spec_{tokens_proposed,tokens_accepted,rounds,
row_rounds}_total``, ``serving_kv_cache_bytes``,
``serving_decode_kv_bytes_{held,one_length}``), a request's kept
proposals (``DecodeRequest.draft_tokens``) and, in the device trace, the
grouped product's kernel name.  Lengths, stamps, the bytes a round needs
(``lib/costs_mtp``) and the comparison that decides ``correct`` (the
configuration's reference beside its file) are the benchmark's own; the
window's loop is ``lib/pooled_window``.

``correct`` holds, besides the reference comparison — the served tokens
of a sample of requests in reused slots against the reference's full
forward (float32 at "highest" on the operands the configuration states,
``check.matmul_inputs``; a token's gap under TWO bounds, mean and worst)
AND the module's proposals at the same positions against the
reference's module logits, the same way —: every tick of the window was
a self-drafting round and every generated token came out of one, every
sampled context is past the window, every branch of every block is a
visible share of the residual it is added to, the pool's bytes are what
the benchmark's own arithmetic gives, and the program's expert counters
add up.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark.lib import costs_mtp as costs
from benchmark.lib import harness, loadgen, pooled_window, traffic

_routed = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_routed_conv_lm.py"),
    "pooled_routed_conv_lm")
EXPERT_COUNTERS = _routed.EXPERT_COUNTERS
_drain = harness.load_py(os.path.join(
    harness.BENCH, "families", "pooled_decode_lm.py"),
    "pooled_decode_lm")._drain

SPEC_COUNTERS = ("tokens_proposed", "tokens_accepted", "rounds", "row_rounds")


def builder():
    """The program's step builder, its parts module and the self-draft
    attachment, or a clean exit where the program has none (a commit
    before PR 47)."""
    try:
        from paddle_tpu import decoding, mtp_routed_lm
        from paddle_tpu.serving.speculative import make_self_draft
        return ((decoding.make_mtp_routed_lm_pooled_step_fn,
                 make_self_draft), mtp_routed_lm)
    except (ImportError, AttributeError) as exc:
        raise SystemExit("benchmark: this program cannot serve an "
                         "exaone_moe decoder with its module (%s)" % exc)


def held_of(cfg):
    return tuple(int(x) for x in cfg["experts_held"])


def make_weights(cfg, device, parts):
    """Every parameter made on the device by ONE jitted call from the
    configuration's weight seed (``assumed.weights``): matrices
    normal(0, initializer_range) in bf16 as they are served (the
    embedding at ``assumed.embedding_std``); norms 1 but the per-head q
    and k norms (``assumed.qk_norm_weight``), the router and its
    selection bias (uniform, not zero) in float32, the router at
    ``assumed.router_std``."""
    import jax
    import jax.numpy as jnp

    shapes = parts.param_shapes(cfg, held=held_of(cfg))
    names = sorted(shapes)
    a = cfg["assumed"]
    std, emb_std = float(a["initializer_range"]), float(a["embedding_std"])
    qk, bias = float(a["qk_norm_weight"]), float(a["expert_bias_range"])
    router_std = float(a["router_std"])
    f32 = jnp.float32

    def make(key):
        out = {}
        for i, n in enumerate(names):
            k, shp = jax.random.fold_in(key, i), shapes[n]
            if n.endswith(("q_norm", "k_norm")):
                out[n] = jnp.full(shp, qk, f32)
            elif n.endswith("_norm"):
                out[n] = jnp.ones(shp, f32)
            elif n.endswith("expert_bias"):
                out[n] = jax.random.uniform(k, shp, f32, -bias, bias)
            elif n.endswith("router"):
                out[n] = jax.random.normal(k, shp, f32) * router_std
            else:
                sd = emb_std if n.endswith("_emb") else std
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
        kinds = set(zip(ref.kinds_of(rcfg), rcfg["mlp_layer_types"]))
        # one program a (window?, dense?) kind of layer: each layer's
        # weights go in under layer 0's names
        blocks = {kind: jax.jit(lambda w, h, kind=kind: ref.block(
            w, "lm_l0_", h, rcfg, kind[0], kind[1] == "dense", held, qb))
            for kind in kinds}
        module = jax.jit(lambda w, h, t: ref.mtp_hidden(
            w, h, t, rcfg, held=held, query_block=qb))
        stats = jax.jit(lambda w, h, t: ref.head_stats(
            w, h, t, rcfg, int(chk["vocab_blocks"])))
        _PROGRAMS[key] = embed, blocks, module, stats
    return _PROGRAMS[key]


def _gaps(stats, ends, rows, targets):
    """``(gap [n], argmax [n], finite)`` of ``targets`` in the reference's
    logits at the hidden ``rows``."""
    import jax.numpy as jnp

    hi, lo, arg, val = (np.asarray(x) for x in stats(
        ends, rows, jnp.asarray(targets)))
    gap = (hi - val) / (hi - lo)
    return gap, arg, bool(np.isfinite(gap).all())


def check_against_reference(ctx, state, kept, slots=None):
    """Prompt and answer through the self-drafting rounds and the cache
    against the reference's full forward (no cache, no ring, no round),
    on the sample of served requests that kept their tokens AND their
    module's proposals: ``kept`` is ``[(prompt ids, generated ids,
    requests sent before it, proposals)]``, ``proposals[j]`` the
    module's proposal for the position of ``generated[j]``.  Returns
    (ok, details)."""
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
    rcfg = {k: v for k, v in cfg.items()
            if not isinstance(v, dict) or k == "rope_parameters"}
    # the operands the configuration STATES are rounded are rounded in
    # the reference too; its arithmetic stays float32 at "highest"
    rcfg["matmul_inputs"] = chk.get("matmul_inputs")
    held = held_of(cfg)
    embed, blocks, module, stats = _reference_programs(ref, rcfg, chk, held)
    layers = int(cfg["num_hidden_layers"])
    kinds = list(zip(ref.kinds_of(rcfg), rcfg["mlp_layer_types"]))
    by_layer = [{k.replace("lm_l%d_" % i, "lm_l0_"): v
                 for k, v in state.items() if k.startswith("lm_l%d_" % i)}
                for i in range(layers)]
    ends = {k: v for k, v in state.items() if not k.startswith("lm_l")}
    gaps, dgaps, hits, dhits, finite = [], [], 0, 0, True
    shares = np.zeros((layers, 2))
    for prompt, got, _, drafts in kept:
        n, p = len(got), len(prompt)
        toks = np.zeros((1, s_ref), np.int32)
        toks[0, :p] = prompt
        toks[0, p:p + n] = got
        dev = jax.device_put(toks, ctx.device)
        h = embed(ends, dev)
        for i in range(layers):
            h, sh = blocks[kinds[i]](by_layer[i], h)
            shares[i] += np.asarray(sh) / len(kept)
        # position s predicts the token at s + 1 ...
        at = p - 1 + np.arange(n)
        gap, arg, fin = _gaps(stats, ends, h[0][jnp.asarray(at)], got)
        gaps.append(gap)
        hits += int((arg == got).sum())
        # ... and the module's row s the token at s + 2
        u = module(ends, h, dev)
        dgap, darg, dfin = _gaps(stats, ends, u[0][jnp.asarray(at - 1)],
                                 np.asarray(drafts, np.int32))
        dgaps.append(dgap)
        dhits += int((darg == drafts).sum())
        finite = finite and fin and dfin
        del h, u
    reused = sum(1 for k in kept if slots is not None and k[2] >= slots)
    past = sum(1 for k in kept
               if len(k[0]) + len(k[1]) > int(chk["min_context"]))
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
                "in_reused_slots": reused, "contexts_past_window": past,
                "branch_share_of_residual": {
                    "layers_x_[attention,ffn]": np.round(shares, 4).tolist()},
                "smallest_branch_share": branch_min}


def expert_counts_add_up(cfg, delta) -> bool:
    """What must hold of the four expert counters' deltas whatever the
    routing, where only ``num_experts`` of the routed-over experts are
    held: a layer-step touches between one (``peak`` > 0) and all of
    them, the largest group is at least the mean group and no more than
    all the pairs."""
    n = int(cfg["num_experts"])
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
    make_step, make_self_draft = build
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
        speculative=make_self_draft(make_cache),
        kv_dtype=sv["kv_dtype"], name="bench-" + cfg["name"])


def run(ctx):
    build, parts = builder()
    from paddle_tpu import grouped_matmul, monitor

    cfg, mix, sv = ctx.cfg, ctx.mix, ctx.cfg["serving"]
    if mix["kind"] != "closed_loop":
        raise ValueError("family pooled_mtp_routed_lm cannot drive a %r mix"
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
                   for k in ("tokens", "prefill_tokens", "ticks", "admitted",
                             "prefill_chunks", "kv_positions_live",
                             "window_positions_read",
                             "window_positions_live") + EXPERT_COUNTERS}
            for k in SPEC_COUNTERS:
                out["spec_" + k] = monitor.counter_value(
                    "serving_spec_%s_total" % k)
            out["kv_bytes"] = monitor.counter_value("serving_kv_cache_bytes")
            for k in ("held", "one_length"):
                out["kv_bytes_" + k] = monitor.counter_value(
                    "serving_decode_kv_bytes_" + k)
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
        with ctx.phase("pool_fill"):
            # one pilot request brings the pool's state to the device
            # before the traffic starts and holds its slot into the ramp
            pilot = srv.submit({"tokens": np.zeros(1, np.int32)},
                               max_new_tokens=int(mix["pilot_tokens"]),
                               speculative=True)
            next(pilot.stream())
        with ctx.phase("ramp"):
            t_ramp = time.perf_counter()
            sched = traffic.ClosedLoopSource(mix, ctx.seed, vocab)
            prompts = sched.prompts  # grows as the clients draw
            load.start_closed_loop(sched, clients, chk["sample_requests"],
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
             r.idx + 1, r.handle.draft_tokens)
            for r in load.records
            if r.keep and r.status == "done" and r.n_tok == r.output_len]
    with ctx.annotate("bench/reference_check"):
        ref_ok, ref_info = check_against_reference(ctx, state, kept, slots)
    stamps = loadgen.stamp_faults(load.records, load.sweeps, w0, t1)
    delta = {k: c1[k] - c0[k] for k in c0}
    ticks, rounds = delta["ticks"], delta["spec_rounds"]
    # tokens no round proposed for: a teacher-forced second row emits the
    # first token of a request whose prompt is even... at most one a
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
        # every tick a self-drafting round, every token out of one: a
        # change cannot win the cell by switching the module off
        "every_row_step_in_a_round": rounds == ticks > 0
        and delta["prefill_chunks"] == 0
        and 0 <= unproposed <= delta["admitted"] + slots,
        # the program's gauges against the benchmark's own arithmetic
        "pool_bytes_as_computed": c1["kv_bytes"] == c1["kv_bytes_held"]
        == costs.kv_bytes_per_slot(cfg, rung) * slots
        and c1["kv_bytes_one_length"]
        == costs.kv_bytes_per_slot(cfg, rung, one_length=True) * slots,
        "expert_counts_add_up": expert_counts_add_up(cfg, delta),
    }
    layer_steps = delta["expert_layer_steps"]
    d = parts.dims(cfg)
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
            window_counters=delta,
            accept_rate=(delta["spec_tokens_accepted"] / proposed
                         if proposed else None),
            tokens_no_round_proposed_for=unproposed,
            kv_cache_bytes=c1["kv_bytes"],
            kv_bytes_one_length=c1["kv_bytes_one_length"],
            experts_touched_per_layer_step=(
                delta["experts_touched"] / layer_steps if layer_steps
                else None),
            server=dict(metrics["decode"], queue_depth=metrics["queue_depth"],
                        shed=metrics.get("shed"), expired=metrics.get("expired")))
    ctx.say("reference_check", **ref_info)

    e2e = {"serve_tokens_per_s": s["tokens_delivered"] / s["window_s"]}
    per_round = lambda v: v / rounds if rounds else 0.0
    k = 2                                    # rows a slot a round computes
    rows = per_round(delta["spec_row_rounds"]) * k
    n_global = d.n_layer - d.window_layers
    # positions a round may read: live ones in the global layer and the
    # module's leaf (both rows of a slot read ONE set of positions: the
    # longer row's), the lesser of live and window in the window layers.
    # The program's counters count per ROW computed: halve them
    whole_pos = per_round(delta["kv_positions_live"]) * (n_global + d.n_mtp)
    window_pos = per_round(delta["window_positions_read"]) / k
    n_rows = slots * k
    pairs = -(-n_rows * d.top_k // grouped_matmul.ROW_TILE) \
        * grouped_matmul.ROW_TILE
    n_held = held_of(cfg)[1] - held_of(cfg)[0]
    ring = min(rung, d.window)
    rep = d.n_head // d.n_kv_head
    round_bytes = costs.round_min_bytes(cfg, whole_pos, window_pos, rows,
                                        touched)
    counters = {
        "window_s": s["window_s"],
        "steps": rounds, "ticks": ticks, "steps_per_dispatch": 1,
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
        # NOT the gathered rows [pairs, d_model]: at 2,048 pairs that is
        # the shape of the shared expert's down matrix
        "route_shapes": [[n_rows, d.n_expert], [n_rows, d.top_k],
                         [n_rows * d.top_k], [n_rows, d.top_k, d.d_model],
                         [n_rows * d.top_k, n_held], [n_held],
                         [d.n_expert]],
        # the shared expert's own: its gate-and-up matrix and every
        # row's gate-and-up and activation (the down product reads the
        # activation; its matrix [width, d_model] is NOT listed: at 2,048
        # pairs that is the shape of the routed experts' output)
        "shared_expert_shapes": [
            [d.d_model, 2 * d.n_shared * d.d_expert],
            [n_rows, 2 * d.n_shared * d.d_expert],
            [n_rows, d.n_shared * d.d_expert]],
        # a leaf of each length, its view by heads, the scores of two
        # fresh rows over it (a ring: the old rows and the fresh ones)
        "window_shapes": [[slots, ring, d.d_kv],
                          [slots, ring, d.n_kv_head, d.head_dim],
                          [slots, ring + k, d.n_kv_head, d.head_dim],
                          [slots, k, d.n_kv_head, rep, ring + k]],
        # the global layer's AND the module's: the rung's leaf, its view
        # by heads, the scores and the context of two rows laid beside
        # the query heads of their K/V head
        "global_shapes": [[slots, rung, d.d_kv],
                          [slots, rung, d.n_kv_head, d.head_dim],
                          [slots, d.n_kv_head, k * rep, rung],
                          [slots, d.n_kv_head, k * rep],
                          [slots, d.n_kv_head, k, rep, d.head_dim]],
        "experts_min_bytes": costs.experts_min_bytes(cfg, touched, rows),
        "attention_min_bytes": costs.attention_min_bytes(
            cfg, whole_pos, window_pos, rows),
        "round_min_bytes": round_bytes,
        "step_min_bytes": round_bytes,
    }
    counters.update({c: delta[c] for c in EXPERT_COUNTERS})
    return {"correct": all(checks.values()), "checks": checks,
            "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": e2e, "counters": counters}
