#!/usr/bin/env python
"""What ONE work item of the ragged decode kernel costs, on the chip.

The kernel alone (``paddle_tpu/decode_attention.py``:
``ragged_decode_attention`` over ``decode_work_items``) at a decode
cell's shapes — 320 slots x 512 positions x 768 lanes, 12 heads, fp32
leaves — with EVERY slot at one position ``ts``, for each ``ts`` asked
for (``31/255``: the slots turn about between the two): the work list
then holds ``slots x (ts // 128 + 1)`` items whose last one a slot is
``ts % 128 + 1`` rows full.  One jitted program runs
the kernel ``--calls`` times in a row on the same leaves (a step's
twelve layers share one work list the same way); its time on the host's
clock, from dispatch until the last context is ready, over calls and
items, is an item's time.  ``mixed`` draws every slot's ``ts`` from
``benchmark/traffic/offline_batch.json``'s requests (a request at a
step drawn evenly over its life), the cell's own distribution of tails.

    python tools/time_ragged_decode.py                      # this checkout
    python tools/time_ragged_decode.py --repo .parent_copy --repo .
    python tools/time_ragged_decode.py --tail 16 --tail 32 --tail 64

``--repo`` loads ``paddle_tpu/decode_attention.py`` from another
checkout (several may be given: all run in this one process, turn and
turn about, so they share the chip and its clock); ``--tail`` sets the
module's ``KV_TAIL`` and ``--ahead`` its ``_READS_AHEAD`` before the
kernel is traced (the experiments that chose them; a checkout without
the constant ignores it).  Every variant's
contexts are compared with the first's (equal to fp32 rounding: the same
products in another order).  ``--rehearse-cpu`` runs tiny shapes under
Pallas interpret mode to prove the script and prints no number a reader
could take for the chip's.  The last line of output is one JSON object.
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # decode_attention registers a counter


def load_module(repo, tail, ahead):
    """``decode_attention`` of the checkout at ``repo`` as a module of
    its own (it imports nothing of its package at the top)."""
    path = os.path.join(repo, "paddle_tpu", "decode_attention.py")
    name = "decode_attention_%d" % len(sys.modules)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if tail is not None and hasattr(mod, "KV_TAIL"):
        mod.KV_TAIL = tail
    if ahead is not None and hasattr(mod, "_READS_AHEAD"):
        mod._READS_AHEAD = ahead
    return mod


def offline_positions(rng, slots, rung):
    """One ``ts`` a slot as ``offline_batch``'s traffic leaves them: a
    request (prompt and output lognormal, clipped) at a step drawn
    evenly over the steps it runs."""
    import numpy as np

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "offline_batch.json")) as fh:
        traffic = json.load(fh)

    def draw(spec):
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], slots))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)

    total = np.minimum(draw(traffic["prompt"]) + draw(traffic["output"]),
                       min(traffic["max_total"], rung))
    return (rng.rand(slots) * (total - 1)).astype(np.int32)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", action="append", default=None)
    ap.add_argument("--tail", action="append", type=int, default=None)
    ap.add_argument("--ahead", action="append", type=int, default=None)
    ap.add_argument("--ts", default="31,63,95,127,255,mixed")
    ap.add_argument("--slots", type=int, default=320)
    ap.add_argument("--rung", type=int, default=512)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-check", action="store_true",
                    help="do not compare the variants' contexts (a copy "
                         "of the kernel cut down for an experiment)")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.slots, args.rung, args.width, args.heads = 4, 256, 128, 2
        args.calls, args.reps = 2, 1

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit("no TPU here (%s): the kernel's time is a chip "
                         "number; --rehearse-cpu proves the script"
                         % dev.platform)
    variants, mods = [], []
    for repo in args.repo or ["."]:
        for tail in args.tail or [None]:
            for ahead in args.ahead or [None]:
                mod = load_module(os.path.join(ROOT, repo), tail, ahead)
                name = (repo, getattr(mod, "KV_TAIL", None),
                        getattr(mod, "_READS_AHEAD", None))
                if name not in variants:    # nothing to set there: once
                    variants.append(name)
                    mods.append(mod)
    S, T, D, H = args.slots, args.rung, args.width, args.heads

    def program(mod):
        block = mod.kv_read_block(T)

        def run(q, kn, vn, kc, vc, ts):
            work = mod.decode_work_items(ts, T, block)
            ctx = q
            for _ in range(args.calls):     # a step's layers, one list
                ctx, kc, vc = mod.ragged_decode_attention(
                    q + 0.0 * ctx, kn, vn, kc, vc, ts, work, n_head=H,
                    scale=(D // H) ** -0.5, block=block,
                    interpret=args.rehearse_cpu)
            return ctx, kc, vc, work[0]

        return jax.jit(run, donate_argnums=(3, 4))

    programs = [program(m) for m in mods]
    rng = np.random.RandomState(args.seed)
    q, kn, vn = (jnp.asarray(rng.randn(S, D), jnp.float32)
                 for _ in range(3))
    key = jax.random.PRNGKey(args.seed)
    # one pair of leaves, donated from call to call: every variant
    # appends the same rows, so each finds them as the parent would
    kc, vc = (jax.random.normal(k, (S, T, D), jnp.float32)
              for k in jax.random.split(key))
    rows = []
    for name in args.ts.split(","):
        if name == "mixed":
            ts = offline_positions(rng, S, T)
        else:       # "31": every slot there; "31/255": slots turn about
            each = [int(x) for x in name.split("/")]
            ts = np.resize(np.asarray(each, np.int32), S)
        ts_dev = jnp.asarray(ts)
        times = [[] for _ in mods]
        first = None
        for rep in range(args.reps + 1):    # rep 0 compiles
            for i, prog in enumerate(programs):
                t0 = time.perf_counter()
                ctx, kc, vc, n_items = prog(q, kn, vn, kc, vc, ts_dev)
                ctx.block_until_ready()
                dt = time.perf_counter() - t0
                if rep:
                    times[i].append(dt)
                elif first is None:
                    first = np.asarray(ctx)
                elif not args.no_check:
                    np.testing.assert_allclose(np.asarray(ctx), first,
                                               rtol=0, atol=2e-5)
        items = int(n_items[0])
        for (repo, tail, ahead), mod, tt in zip(variants, mods, times):
            # an interpreter's time is no number
            tt = [None] if args.rehearse_cpu else tt
            us = (lambda x: None if x is None else x / args.calls * 1e6)
            call_us = us(statistics.median(tt))
            read = (int(np.sum(mod.kv_positions_read(ts, mod.kv_read_block(T))))
                    if hasattr(mod, "kv_positions_read") else
                    int(np.sum(ts // 128 + 1)) * 128)
            rows.append({
                "repo": repo, "tail": tail, "ahead": ahead,
                "ts": name, "items": items, "live": int(np.sum(ts + 1)),
                "read": read, "call_us": call_us,
                "call_us_min": us(min(tt)),
                "item_us": call_us and call_us / items})
            print(json.dumps(rows[-1]), flush=True)
    out = json.dumps({"tool": "time_ragged_decode",
                      "rehearsal": bool(args.rehearse_cpu),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "shape": [S, T, D, H], "calls": args.calls,
                      "reps": args.reps, "rows": rows})
    if not args.rehearse_cpu:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "time_ragged_decode.json"), "w") as fh:
            fh.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
