#!/usr/bin/env python
"""What ONE dispatch of the slot pool costs the host, on the chip.

The decode server's tick is device time plus what the host spends
between two chunks; this times the host's calls one at a time, with the
chip idle before each, at a decode cell's real shapes (the
configuration's widths and layers, its one rung pair, so the executables
take as many array arguments as the cell's):

* ``admit`` of one request and of a turn's worth (``--batch``, default
  6): seconds until the call RETURNS (the host's share) and until its
  result is READY (what the chip waits between two chunks);
* ``chunk``: the same two, with every slot live;
* where the pool seats and prefills in one dispatch (``gpt1_117m``
  since PR 45), one ``seat_prefill`` pass of one seat at the narrowest
  and at the widest width, of a full pass of seats and of an offline
  turn's nine: the same two;
* per executable kind, how many of the constants ``KVSlotPool._lower``
  hoisted were HOST-BORN (numpy arrays the step closes over) and their
  bytes: before PR 32 every call sent each of them to the chip again
  (one ``DevicePut`` of ~0.11 ms each), since then the pool places
  them once (``constants_placed``: the device copies it made);
* ``weight_copies``: the matrices the builder copied to the dtype of
  their products at build (``decode_weight_copies_total``; since PR 35
  ``gpt1_117m`` 73 on the chip, where a ``chunk`` used to cast them
  all again), ``block_sparse_lowered``: the reads of named blocks the
  builder lowered, by path (``block_sparse_lowered_total``:
  ``minicpm_sala`` 2 ``kernel`` on the chip, one a sparse layer of its
  ``chunk``; ``xla`` here), and ``tokens_sha1``: a digest of every slot's tokens
  after the timed calls, which are the same calls over the same prompts
  whatever the checkout — equal digests at two checkouts say the chip
  served the same tokens;
* a profiler trace over a few of each, reduced to the host events that
  ran on the calling thread inside the calls (argument handling, the
  h2d of host arguments, the runtime's ``Execute``), by name;
* ``build_table`` (PR 52), printed as a table right after the build and
  kept in the last line: per program (the pool's executable kinds,
  ``weight_copies``, ``unscoped``: the family's jitted weight draw) the
  seconds of each stage — ``trace``, ``lower``, ``compile``,
  ``cache_load``, ``place``, ``first_run`` — from
  ``program_build_seconds_total``, the executables built
  (``program_builds_total``: hit / miss / off), and from the
  ``build/<program>`` spans of the same builds the jaxpr's equations and
  the kernel sites walked (which ``*_lowered_total{path}`` moved during
  the trace); ``booked_share`` is the stage seconds of the pool's kinds
  over the wall of ``pool.warmup()``.  ``--build-only`` stops there (a
  set-up ``perf_opt``'s loop: no timed call, any decode family);
  ``--no-record`` builds with no span sink live (the table then has the
  counters' columns only): a recorded start-up against one that is not
  is what the recording costs.

    python tools/time_pool_dispatch.py gpt1_117m
    python tools/time_pool_dispatch.py gpt1_117m --repo .parent_copy \\
        --one-call-a-request
    python tools/time_pool_dispatch.py minicpm_sala
    python tools/time_pool_dispatch.py falcon_h1_34b
    python tools/time_pool_dispatch.py lfm2_24b_a2b

For a pool that prefills in chunks and keeps snapshots (``minicpm_sala``)
it also times one ``prefill`` chunk, one ``snapshot``, one
``admit_prefix`` over a snapshot, the scheduler's per-tick fetch of its
view of the state (``tokens`` is ``[slots, rung]`` int32: 8.4 MB there)
and, on the host alone, ``PrefixKVCache.probe`` of a 30k-token prompt.

``--repo`` imports ``paddle_tpu`` and the benchmark's family from
another checkout; ``--one-call-a-request`` seats a batch the way a
checkout before PR 30 does, one ``admit`` call each (a checkout from
before PR 58 has no ``decoding.spec_of``: run ITS copy of this tool
from its own tree).  ``--rehearse-cpu``
runs the cell's tiny rehearsal sizes on the CPU to prove the script, and
prints no number a reader could take for the chip's.  The last line of
output is one JSON object.
"""
import argparse
import collections
import glob
import hashlib
import json
import os
import statistics
import sys
import tempfile
import time


def _ms(samples):
    return {"median_ms": statistics.median(samples) * 1e3,
            "min_ms": min(samples) * 1e3, "max_ms": max(samples) * 1e3,
            "n": len(samples)}


def host_events_inside(xplane_path, prefixes):
    """{event name: [total seconds, count]} of the host plane's events
    that lie inside an event whose name starts with one of ``prefixes``
    (the ``tool/...`` annotations around the timed calls), per prefix."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {p: collections.defaultdict(lambda: [0.0, 0]) for p in prefixes}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in line.events]
            outer = [e for e in events if e[2].startswith(tuple(prefixes))]
            for a, b, name in events:
                for oa, ob, oname in outer:
                    if oa <= a and b <= ob and name != oname:
                        row = out[next(p for p in prefixes
                                       if oname.startswith(p))][name]
                        row[0] += (b - a) * 1e-9
                        row[1] += 1
                        break
    return {p: sorted(([k, v[0] / max(v[1], 1) * 1e3, v[1]]
                       for k, v in rows.items()), key=lambda r: -r[1])[:12]
            for p, rows in out.items()}


def build_step(root, config, rehearse):
    """``(cfg, weights, step_fn, make_cache)`` of the decode
    configuration ``config`` as its benchmark family builds it from the
    checkout at ``root``: the published widths with the weights on the
    first device, or (``rehearse``) the configuration's tiny sizes.
    ``root`` must be importable (``benchmark``, ``paddle_tpu``)."""
    import jax

    from benchmark.lib import harness
    from paddle_tpu import decoding

    dev = jax.devices()[0]
    cfg = harness.load_config(os.path.join(
        root, "benchmark", "configs", config + ".json"), rehearse)
    family = harness.load_py(os.path.join(
        root, "benchmark", "families", cfg["family"] + ".py"), cfg["family"])
    sv = cfg["serving"]
    if cfg["family"] == "pooled_decode_lm":
        weights = family.make_weights(cfg, dev)
        step_fn, make_cache = decoding.make_transformer_lm_pooled_step_fn(
            weights, int(cfg["vocab_size"]), cfg["n_embd"], cfg["n_layer"],
            cfg["n_head"], cfg["assumed"]["n_inner"],
            kv_dtype=sv["kv_dtype"])
    elif cfg["family"] == "pooled_hybrid_ssm_lm":
        build, parts = family.builder()
        weights = family.make_weights(cfg, dev, parts)
        step_fn, make_cache = build(
            weights, cfg, kv_dtype=sv["kv_dtype"],
            ssm_state_dtype=cfg["assumed"]["ssm_state_dtype"])
    elif cfg["family"] == "pooled_sparse_linear_lm":
        build, parts = family.builder()
        weights = family.make_weights(cfg, dev, parts)
        step_fn, make_cache, _ = build(
            weights, cfg, kv_dtype=sv["kv_dtype"],
            state_dtype=cfg["assumed"]["lightning_state_dtype"],
            prefill_tokens=int(sv["prefill_tokens"]))
    elif cfg["family"] == "pooled_routed_conv_lm":
        build, parts = family.builder()
        weights = family.make_weights(cfg, dev, parts)
        step_fn, make_cache = build(weights, cfg, kv_dtype=sv["kv_dtype"])
    elif cfg["family"] == "pooled_windowed_routed_lm":
        build, parts = family.builder()
        weights = family.make_weights(cfg, dev, parts)
        step_fn, make_cache, _ = build(
            weights, cfg, kv_dtype=sv["kv_dtype"],
            prefill_tokens=int(sv["prefill_tokens"]))
    else:
        sys.exit("time_pool_dispatch: no builder for family %r"
                 % cfg["family"])
    return cfg, weights, step_fn, make_cache


def host_born_constants(pool, s, t):
    """``{kind: [count, bytes]}`` of the constants each of the pool's
    executables at rung pair ``(s, t)`` was lowered with that were not
    on a device (``KVSlotPool._lower`` hoists every closed-over array
    to an argument): the pool's own record, or, in a checkout before
    PR 32, what each executable is still bound to and sends again on
    every call."""
    import jax
    import numpy as np

    if hasattr(pool, "host_born_constants"):
        return {k: list(v) for k, v in pool.host_born_constants().items()}
    out = {}
    for (kind, es, et), exe in sorted(pool._exe.items()):
        if (es, et) == (s, t):
            host = [c for c in exe.args[0] if not isinstance(c, jax.Array)]
            out[kind] = [len(host), sum(np.asarray(c).nbytes for c in host)]
    return out


def build_table(monitor, build_spans):
    """``{program: {stage: seconds, "builds": {cache: n}, "equations":
    [...], "kernels": {site: n}}}`` from the build record's two counters
    and (where a sink was live) its ``build/<program>`` spans."""
    snap = monitor.snapshot()
    table = collections.defaultdict(dict)
    for series in snap.get("program_build_seconds_total",
                           {"series": []})["series"]:
        lbl = series["labels"]
        table[lbl["program"]][lbl["stage"]] = series["value"]
    for series in snap.get("program_builds_total", {"series": []})["series"]:
        lbl = series["labels"]
        table[lbl["program"]].setdefault("builds", {})[lbl["cache"]] = int(
            series["value"])
    for sp in build_spans:
        program = sp["name"][len("build/"):]
        if program not in table or "equations" not in sp.get("args", {}):
            continue
        row = table[program]
        row.setdefault("equations", []).append(sp["args"]["equations"])
        for site, n in sp["args"].get("kernels", {}).items():
            kernels = row.setdefault("kernels", {})
            kernels[site] = kernels.get(site, 0) + n
    return dict(table)


def print_build_table(table, stages):
    print("%-14s %s  builds  equations  kernel sites walked" % (
        "program", " ".join("%10s" % st for st in stages)))
    for program, row in sorted(table.items()):
        print("%-14s %s  %-6s  %-9s  %s" % (
            program,
            " ".join("%10.3f" % row.get(st, 0.0) for st in stages),
            "+".join("%d%s" % (n, c[0]) for c, n in sorted(
                row.get("builds", {}).items())) or "-",
            ",".join(str(n) for n in row.get("equations", [])) or "-",
            " ".join("%s=%d" % kv for kv in sorted(
                row.get("kernels", {}).items())) or "-"))
    print("%-14s %s" % ("all", " ".join(
        "%10.3f" % sum(row.get(st, 0.0) for row in table.values())
        for st in stages)), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--build-only", action="store_true")
    ap.add_argument("--no-record", action="store_true")
    ap.add_argument("--repo", default=None)
    ap.add_argument("--batch", type=int, default=6)
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--one-call-a-request", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    root = os.path.abspath(args.repo or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    sys.path.insert(0, root)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.lib import harness
    from paddle_tpu import monitor
    from paddle_tpu.decoding import spec_of
    from paddle_tpu.serving.kv_pool import KVSlotPool

    harness.configure_jax(args.rehearse_cpu)
    dev = jax.devices()[0]
    if not args.rehearse_cpu and dev.platform != "tpu":
        sys.exit("time_pool_dispatch: needs the chip (or --rehearse-cpu)")
    # the build's own spans, where the checkout has a build record
    record = not args.no_record and monitor.REGISTRY.get(
        "program_build_seconds_total") is not None
    if record:
        monitor.start_recording()
    t_build0 = time.perf_counter()
    cfg, weights, step_fn, make_cache = build_step(
        root, args.config, args.rehearse_cpu)
    jax.block_until_ready(weights)
    weights_s = time.perf_counter() - t_build0
    sv, vocab = cfg["serving"], int(cfg["vocab_size"])
    s, t = sv["slot_ladder"][-1], sv["len_ladder"][-1]
    snapshots = spec_of(make_cache).prefill_fn is not None
    pool = KVSlotPool(step_fn, make_cache, eos_id=vocab, max_slots=s,
                      max_seq_len=t, slot_ladder=[s], len_ladder=[t],
                      steps=sv["steps_per_tick"], kv_dtype=sv["kv_dtype"],
                      **({"prefix": True} if snapshots else {}))
    t0 = time.perf_counter()
    pool.warmup()
    warm_s = time.perf_counter() - t0
    build_spans = [sp for sp in (monitor.stop_recording() if record else [])
                   if sp["name"].startswith("build/")]
    table = build_table(monitor, build_spans)
    stages = ("trace", "lower", "compile", "cache_load", "place",
              "first_run")
    built = {"config": args.config, "recorded": record,
             "device": {"platform": dev.platform, "kind": dev.device_kind},
             "weights_and_step_s": weights_s, "warmup_s": warm_s,
             "build_spans": len(build_spans),
             "booked_share": (sum(
                 v for kind in pool._kinds()
                 for st, v in table.get(kind, {}).items() if st in stages)
                 / warm_s if table else None),
             "build_table": table}
    if table:
        print_build_table(table, stages)
    if args.build_only:
        if args.rehearse_cpu:
            print("REHEARSAL on the CPU at tiny sizes: NOT device numbers.")
        print(json.dumps(built))
        return
    rng = np.random.RandomState(0)

    def prompt():
        return rng.randint(0, vocab, rng.randint(8, t // 2)).astype(np.int32)

    def admit(state, slots):
        prompts = [prompt() for _ in slots]
        t0 = time.perf_counter()
        if args.one_call_a_request or len(slots) == 1:
            for i, p in zip(slots, prompts):
                state = pool.admit(state, i, p, len(p), t)
        else:
            state = pool.admit(state, slots, prompts,
                               [len(p) for p in prompts], [t] * len(slots))
        t1 = time.perf_counter()
        jax.block_until_ready(state["pos"])
        return state, t1 - t0, time.perf_counter() - t0

    def chunk(state):
        t0 = time.perf_counter()
        state = pool.chunk(state)
        t1 = time.perf_counter()
        jax.block_until_ready(state["pos"])
        return state, t1 - t0, time.perf_counter() - t0

    # every slot live (the state crosses to the device with the first
    # call), then a few calls of each kind outside the samples
    state = pool.alloc(s, t)
    for i in range(s):
        state, _, _ = admit(state, [i])
    for _ in range(3):
        state, _, _ = chunk(state)
    batch = list(range(0, s, max(1, s // args.batch)))[:args.batch]
    samples = collections.defaultdict(list)
    for r in range(args.reps):
        for name, fn in (("admit_1", lambda st: admit(st, [r % s])),
                         ("admit_%d" % len(batch), lambda st: admit(st, batch)),
                         ("chunk", chunk)):
            state, call_s, ready_s = fn(state)
            samples[name + ".call"].append(call_s)
            samples[name + ".ready"].append(ready_s)

    if getattr(pool, "seats_prefilled", False):
        # one seat-and-prefill dispatch (PR 45): a chat turn's one seat
        # at the narrowest width and at the widest, a full pass of
        # seats, and an offline turn's nine
        def seat_prefill(state, lens):
            prompts = [rng.randint(0, vocab, n).astype(np.int32)
                       for n in lens]
            t0 = time.perf_counter()
            state, passes = pool.seat_prefill(
                state, list(range(len(lens))), prompts, [t] * len(lens))
            t1 = time.perf_counter()
            jax.block_until_ready(state["pos"])
            if passes != 1:
                sys.exit("time_pool_dispatch: %d passes for %d seats"
                         % (passes, len(lens)))
            return state, t1 - t0, time.perf_counter() - t0

        full = pool._seat_rows(s, t)
        narrow = pool.prefill_classes(t)[0][0]
        mixes = {
            "seat_prefill_1": [min(narrow, 3 * t // 16)],
            "seat_prefill_1_widest": [t * 5 // 8],
            "seat_prefill_%d" % full: [
                int(n) for n in rng.randint(max(2, t // 32), narrow, full)],
            "seat_prefill_9_offline": [int(n) for n in np.clip(np.exp(
                rng.normal(np.log(t / 8.0), 0.5, 9)), t // 32, t // 2)]}
        for r in range(args.reps + 3):
            for name, lens in mixes.items():
                state, call_s, ready_s = seat_prefill(state, lens)
                if r >= 3:
                    samples[name + ".call"].append(call_s)
                    samples[name + ".ready"].append(ready_s)

    if snapshots:
        from paddle_tpu.serving.prefix_cache import PrefixKVCache

        def timed(name, fn, ready):
            t0 = time.perf_counter()
            out = fn()
            t1 = time.perf_counter()
            jax.block_until_ready(ready(out))
            samples[name + ".call"].append(t1 - t0)
            samples[name + ".ready"].append(time.perf_counter() - t0)
            return out

        c = pool.prefill_tokens
        long_prompt = rng.randint(0, vocab, t - 2 * c).astype(np.int32)
        head = long_prompt[:(len(long_prompt) // c - 1) * c]
        cache = PrefixKVCache(capacity_bytes=1 << 40, name="tool")
        for r in range(min(args.reps, 10)):
            state = pool.admit(state, 0, long_prompt, len(long_prompt), t)
            state = pool.release(state, [0])
            jax.block_until_ready(state["pos"])
            # a chunk in the middle of a long prompt: its attend reads
            # half the rung
            state = timed("prefill_mid_rung", lambda: pool.prefill(
                state, 0, (t // 2 // c) * c, False), lambda st: st["pos"])
            snap = timed("snapshot", lambda: pool.snapshot(state, 0),
                         lambda leaves: leaves)
            cache.invalidate()
            cache.put(head, snap)
            timed("probe_%d_tokens" % len(long_prompt),
                  lambda: cache.probe(long_prompt), lambda hit: hit[1])
            state = timed("admit_prefix", lambda: pool.admit_prefix(
                state, 0, long_prompt, len(long_prompt), t, snap,
                len(head)), lambda st: st["pos"])
            timed("tick_view_fetch", lambda: jax.device_get(
                {k: state[k] for k in ("tokens", "pos", "active",
                                       "finished", "n_gen")}),
                lambda view: [])
        cache.close()

    trace_dir = tempfile.mkdtemp(prefix="pool_dispatch_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for r in range(10):
        with jax.profiler.TraceAnnotation("tool/admit_1"):
            state, _, _ = admit(state, [r % s])
        with jax.profiler.TraceAnnotation("tool/admit_n"):
            state, _, _ = admit(state, batch)
        with jax.profiler.TraceAnnotation("tool/chunk"):
            state, _, _ = chunk(state)
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    inside = (host_events_inside(
        found[-1], ["tool/admit_1", "tool/admit_n", "tool/chunk"])
        if found else {})

    n_args = len(jax.tree.leaves(state)) + len(jax.tree.leaves(weights))
    out = {"config": args.config, "repo": root,
           "one_call_a_request": args.one_call_a_request,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "rung_pair": [s, t], "batch": len(batch),
           "state_and_weight_arrays": n_args, "warmup_s": warm_s,
           "booked_share": built["booked_share"], "build_table": table,
           "host_born_constants": host_born_constants(pool, s, t),
           "constants_placed": getattr(pool, "constants_placed", None),
           "weight_copies": monitor.counter_value(
               "decode_weight_copies_total"),
           "block_sparse_lowered": {
               path: monitor.counter_value("block_sparse_lowered_total",
                                           path=path)
               for path in ("kernel", "xla")},
           "tokens_sha1": hashlib.sha1(np.ascontiguousarray(
               jax.device_get(state["tokens"])).tobytes()).hexdigest(),
           "host_events_inside_ms_each": inside,
           "times": {k: _ms(v) for k, v in sorted(samples.items())}}
    if args.rehearse_cpu:
        print("REHEARSAL on the CPU at tiny sizes: NOT device numbers.")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
