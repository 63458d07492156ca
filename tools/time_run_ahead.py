#!/usr/bin/env python
"""What one chunk QUEUED behind a running one costs and saves, at a
decode cell's own pool, with no scheduler in the way.

``DecodeServer._tick`` dispatches chunk N + 1 before it waits for N
wherever N's view can hold nothing to decide (run-ahead of depth one,
PR 62).  Three questions of that mechanism are the device's to answer,
and this tool asks them of the pool alone (``KVSlotPool.chunk_view``
over the cell's one rung pair, every slot live, as
``tools/time_pool_dispatch.py`` builds it):

* **memory** — ``memory_stats()`` at rest, with one chunk on the device,
  with a second queued behind it, and after both: whether an execution's
  temporaries are taken when it is ENQUEUED or when it starts (the
  ``memory`` condition of ``DecodeServer._why_serial`` reads the free
  bytes with one chunk on the device), beside the executable's own
  ``memory_analysis()`` (``KVSlotPool.queued_bytes``) and what one
  ``memory_stats()`` call costs the host;
* **the view** — the tokens of ``--rounds`` chunks read serially, and
  read again from a fresh state with every view fetched only AFTER the
  next chunk was dispatched (the state it was copied from donated by
  then): equal digests = no view died with its state (donation is off
  on the CPU: only the chip can say);
* **the gap** — ms a chunk of both orders over the same rounds: serial
  is the chunk plus the host's fetch and relaunch, ahead the device's
  own launch-to-launch time.

    chiprun -- python tools/time_run_ahead.py smallthinker_21b_a3b
    python tools/time_run_ahead.py gpt1_117m --rehearse-cpu

``--rehearse-cpu`` proves the script here at the cell's tiny sizes and
prints no number a reader could take for the chip's.  The last line of
output is one JSON object.
"""
import argparse
import hashlib
import json
import os
import statistics
import sys
import time


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--rounds", type=int, default=24)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.abspath(os.path.join(here, ".."))
    sys.path[:0] = [root, here]
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.lib import harness
    from paddle_tpu.serving.kv_pool import KVSlotPool
    from time_pool_dispatch import build_step

    harness.configure_jax(args.rehearse_cpu)
    dev = jax.devices()[0]
    if not args.rehearse_cpu and dev.platform != "tpu":
        sys.exit("time_run_ahead: needs the chip (or --rehearse-cpu)")
    cfg, weights, step_fn, make_cache = build_step(
        root, args.config, args.rehearse_cpu)
    jax.block_until_ready(weights)
    sv, vocab = cfg["serving"], int(cfg["vocab_size"])
    s, t = sv["slot_ladder"][-1], sv["len_ladder"][-1]
    pool = KVSlotPool(step_fn, make_cache, eos_id=vocab, max_slots=s,
                      max_seq_len=t, slot_ladder=[s], len_ladder=[t],
                      steps=sv["steps_per_tick"], kv_dtype=sv["kv_dtype"])
    pool.warmup()

    def seated():
        """Every slot live over the same prompts, two chunks in."""
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, vocab, rng.randint(8, max(9, t // 4)))
                   .astype(np.int32) for _ in range(s)]
        state = pool.admit(pool.alloc(s, t), list(range(s)), prompts,
                           [len(p) for p in prompts], [t] * s)
        for _ in range(2):
            state = pool.chunk(state)
        jax.block_until_ready(state["pos"])
        return state

    def stats():
        m = dev.memory_stats() or {}
        return {k: m.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}

    def digest(views):
        h = hashlib.sha1()
        for v in views:     # the packed vector: tokens, pos, flags, counts
            h.update(np.ascontiguousarray(v).tobytes())
        return h.hexdigest()

    # --- memory: one chunk on the device, then a second behind it
    state = seated()
    memory = {"at_rest": stats()}
    state, v1 = pool.chunk_view(state)
    memory["one_dispatched"] = stats()
    state, v2 = pool.chunk_view(state)
    memory["second_queued"] = stats()
    jax.block_until_ready(v1)
    memory["first_done"] = stats()
    jax.block_until_ready(v2)
    memory["both_done"] = stats()
    calls = []
    for _ in range(200):
        t0 = time.perf_counter()
        dev.memory_stats()
        calls.append(time.perf_counter() - t0)
    memory["queued_bytes_by_memory_analysis"] = pool.queued_bytes(state)
    memory["memory_stats_call_us"] = statistics.median(calls) * 1e6
    del state, v1, v2

    # --- the view and the gap: the same rounds in both orders
    def serial(state):
        views, t0 = [], time.perf_counter()
        for _ in range(args.rounds):
            state, view = pool.chunk_view(state)
            views.append(jax.device_get(view))
        return views, (time.perf_counter() - t0) / args.rounds

    def ahead(state):
        views, t0 = [], time.perf_counter()
        state, older = pool.chunk_view(state)
        for _ in range(args.rounds - 1):
            state, newer = pool.chunk_view(state)   # donates older's state
            views.append(jax.device_get(older))
            older = newer
        views.append(jax.device_get(older))
        return views, (time.perf_counter() - t0) / args.rounds

    rows = {}
    for name, order in (("serial", serial), ("ahead", ahead),
                        ("serial_again", serial), ("ahead_again", ahead)):
        views, per_chunk = order(seated())
        rows[name] = {"ms_a_chunk": per_chunk * 1e3,
                      "views_sha1": digest(views)}
    same = len({r["views_sha1"] for r in rows.values()}) == 1
    out = {"config": args.config, "rounds": args.rounds,
           "rung_pair": [s, t], "steps": pool.steps,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "memory": memory, "orders": rows,
           "views_equal_in_both_orders": same}
    if args.rehearse_cpu:
        print("REHEARSAL on the CPU at tiny sizes: NOT device numbers.")
        for row in rows.values():
            row.pop("ms_a_chunk")
        memory.pop("memory_stats_call_us")
    print(json.dumps(out))
    if not same:
        sys.exit("time_run_ahead: a view read after the next dispatch "
                 "differs from the serial order's")


if __name__ == "__main__":
    main()
