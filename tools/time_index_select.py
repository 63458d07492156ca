#!/usr/bin/env python
"""What ONE selection of a latent layer's positions costs, on the chip:
``latent_sparse_lm.select_positions`` (a threshold found by counting, the
tie rule, a compaction: the list in ascending position order) beside the
form it replaced at PR 55 (``lax.top_k`` of the masked scores: a stable
sort of the whole rung, the list in score order), turn and turn about in
one process over the same scores.

The shape is ``deepseek_v3_2.shared_docs_qa_32k``'s: 24 slots, a rung of
32768, the 2048 best, every slot at 16k-29k positions as the cell's
documents leave them; the latent leaf ``bf16[24,32768,640]`` and 128
absorbed query heads for the read.  Three stages, each timed for both
forms:

    select        scores -> (sel, valid)
    select_read   the same, then ``decode_attention.
                  selected_latent_attention`` over the list (the old form
                  gathers by ``take_along_axis``: its list is in score
                  order and may not be declared sorted)
    chunk_mask    the prefill chunk's membership at ``[512, 32768]``
                  (``latent_sparse_lm.top_members`` beside a ``lax.top_k``
                  threshold with a rung-long ``cumsum`` of the ties)

One jitted program runs a stage ``--calls`` times in a row — a step's five
layers, each call behind an optimization barrier — and a second program
does that ``--rounds`` times over; a launch costs the host's clock about a
millisecond whatever the program holds, so a call's time is the SLOPE
between the two: (t of the rounds - t of one) / ((rounds - 1) x calls)
(``tools/time_delta_update.py``).  ``select_read`` less ``select`` is what
the read costs behind each form.

    python tools/time_index_select.py
    python tools/time_index_select.py --radix 1 --radix 2 --radix 4

``--radix`` times the new form at that many bits a pass of the threshold's
search and ``--block`` at that many positions a block of the two-level
count (they set the module's ``_RADIX_BITS`` / ``_SELECT_BLOCK`` before
tracing: how the constants were chosen).  ``--rehearse-cpu`` runs a tiny shape to prove the
script and prints no number a reader could take for the chip's.  Before
any clock both forms are held to the same SET a row.  The last line of
output is one JSON object.
"""
import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # decode_attention registers counters

#: slots, rung, top_k, lanes of a latent row, query heads, the row's
#: width, the value lanes, a chunk's queries
SHAPE = (24, 32768, 2048, 640, 128, 576, 512, 512)
REHEARSAL = (4, 256, 32, 128, 3, 24, 16, 8)
STAGES = ("select", "select_read", "chunk_mask")


def sorted_select(scores, ts, top_k):
    """``select_positions`` as it was until PR 55: ``lax.top_k`` of the
    masked scores, the list in score order."""
    import jax
    import jax.numpy as jnp

    t = scores.shape[1]
    live = jnp.arange(t)[None, :] <= ts[:, None]
    top, sel = jax.lax.top_k(jnp.where(live, scores, -jnp.inf),
                             min(int(top_k), t))
    return sel.astype(jnp.int32), top > -jnp.inf


def sorted_read(da, q, kv, ts, sel, valid, *, d_value, scale):
    """``selected_latent_attention`` as it was until PR 55: a gather that
    knows nothing of its list's order."""
    import jax.numpy as jnp

    leaf = kv["latent"]
    rows = jnp.take_along_axis(leaf, sel[:, :, None], axis=1)
    q = da.pad_lanes((q * scale).astype(leaf.dtype), leaf.shape[2])
    s = jnp.einsum("shd,skd->shk", q, rows,
                   preferred_element_type=jnp.float32)
    return da._latent_softmax(s, valid & (sel <= ts[:, None]), rows,
                              d_value)


def sorted_mask(scores, live, k):
    """``chunk_select``'s membership as it was until PR 55."""
    import jax
    import jax.numpy as jnp

    scores = jnp.where(live, scores, -jnp.inf)
    least = jax.lax.top_k(scores, k)[0][:, -1:]
    above = scores > least
    ties = (scores == least) & live
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))) & live


def stage_fns(ls, da, form, shape):
    """``{stage: f(scores, ts, q, leaf) -> float32 scalar-ish}`` of one
    form; every output depends on the whole of the stage's result."""
    import jax.numpy as jnp

    _, _, top_k, _, _, _, d_value, _ = shape
    select = sorted_select if form == "sort" else ls.select_positions
    read = (functools.partial(sorted_read, da) if form == "sort"
            else da.selected_latent_attention)

    def only_select(scores, ts, q, leaf):
        sel, valid = select(scores, ts, top_k)
        return jnp.sum(jnp.where(valid, sel, 0), axis=-1).astype(
            jnp.float32)

    def select_read(scores, ts, q, leaf):
        sel, valid = select(scores, ts, top_k)
        u = read(q, {"latent": leaf}, ts, sel, valid, d_value=d_value,
                 scale=0.135)
        return jnp.sum(u, axis=(1, 2))

    def chunk_mask(scores, ts, q, leaf):
        t = scores.shape[1]
        live = jnp.arange(t)[None, :] <= ts[:, None]
        if form == "sort":
            member = sorted_mask(scores, live, top_k)
        else:
            member = ls.top_members(scores, live, top_k)[0].reshape(
                scores.shape)
        at = jnp.arange(t, dtype=jnp.int32)[None, :]
        return jnp.sum(jnp.where(member, at, 0), axis=-1).astype(
            jnp.float32)

    return {"select": only_select, "select_read": select_read,
            "chunk_mask": chunk_mask}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--radix", action="append", type=int, default=None,
                    help="bits a pass of the threshold's search (sets the "
                         "module's _RADIX_BITS before tracing); unsaid: "
                         "the module's")
    ap.add_argument("--block", action="append", type=int, default=None,
                    help="positions a block of the two-level count (sets "
                         "the module's _SELECT_BLOCK before tracing); "
                         "unsaid: the module's")
    ap.add_argument("--stage", action="append", choices=STAGES,
                    default=None, help="unsaid: all three")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=4,
                    help="passes over the calls in the longer program")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.calls, args.rounds, args.reps = 2, 2, 1

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import decode_attention as da
    from paddle_tpu import latent_sparse_lm as ls

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit("no TPU here (%s): the selection's time is a chip "
                         "number; --rehearse-cpu proves the script"
                         % dev.platform)
    shape = REHEARSAL if args.rehearse_cpu else SHAPE
    n, t, top_k, lanes, heads, width, _, chunk = shape
    stages = args.stage or list(STAGES)

    rng = np.random.RandomState(args.seed)
    # index scores as the cell's are: a weighed sum of ReLUs, some exact
    # ties (rows that score nothing read 0.0)
    raw = (rng.randn(chunk, t) * (rng.rand(chunk, t) < 0.9)).astype(
        np.float32)
    # the step's rows stand where the documents leave them; the chunk's
    # queries at 512 positions in a row near the rung's end
    ts_step = rng.randint(t // 2, t * 29 // 32, n).astype(np.int32)
    ts_chunk = (t - 2 * chunk + np.arange(chunk)).astype(np.int32)
    inputs = {
        "step": (jnp.asarray(raw[:n]), jnp.asarray(ts_step)),
        "chunk": (jnp.asarray(raw), jnp.asarray(ts_chunk))}
    q = jnp.asarray(rng.randn(n, heads, width), jnp.float32)
    leaf = jax.random.normal(jax.random.PRNGKey(args.seed), (n, t, lanes),
                             jnp.bfloat16)

    def programs(fn):
        def run(scores, ts, q, leaf, *, rounds):
            acc = jnp.zeros((scores.shape[0],), jnp.float32)
            for _ in range(rounds * args.calls):
                # a layer's scores are its own: nothing is shared
                scores, acc = jax.lax.optimization_barrier(
                    (scores + 0.0 * acc[:, None], acc))
                acc = acc + fn(scores, ts, q, leaf)
            return acc

        return [jax.jit(functools.partial(run, rounds=r))
                for r in (1, args.rounds)]

    def operands_of(stage):
        return inputs["chunk" if stage == "chunk_mask" else "step"]

    def compiled(form, stage):
        """Traced and compiled now, while the module's constant is what
        the caller set."""
        return [p.lower(*operands_of(stage), q, leaf).compile()
                for p in programs(stage_fns(ls, da, form, shape)[stage])]

    variants = []       # (form, (radix, block), stage, programs)
    for stage in stages:
        variants.append(("sort", (None, None), stage,
                         compiled("sort", stage)))
        for radix in args.radix or [ls._RADIX_BITS]:
            for block in args.block or [ls._SELECT_BLOCK]:
                ls._RADIX_BITS, ls._SELECT_BLOCK = radix, block
                variants.append(("threshold", (radix, block), stage,
                                 compiled("threshold", stage)))

    # the same set from both forms, before any clock
    first = {}
    for _, _, stage, progs in variants:
        got = np.asarray(progs[0](*operands_of(stage), q, leaf))
        want = first.setdefault(stage, got)
        if stage == "select_read":      # a sum in another order
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
        else:                           # sums of positions: exact
            np.testing.assert_array_equal(got, want)
    times = [([], []) for _ in variants]
    for _ in range(args.reps):
        for i, (_, _, stage, progs) in enumerate(variants):
            for prog, tt in zip(progs, times[i]):
                t0 = time.perf_counter()
                prog(*operands_of(stage), q, leaf).block_until_ready()
                tt.append(time.perf_counter() - t0)
    rows = []
    for (form, (radix, block), stage, _), (one, many) in zip(variants,
                                                             times):
        one, many = statistics.median(one), statistics.median(many)
        call_ms = (many - one) / ((args.rounds - 1) * args.calls) * 1e3
        row = {"stage": stage, "form": form, "radix_bits": radix,
               "block": block, "call_ms": call_ms,
               "launch_ms": one * 1e3 - call_ms * args.calls}
        if args.rehearse_cpu:       # no CPU's time
            row.update(call_ms=None, launch_ms=None)
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = json.dumps({"tool": "time_index_select",
                      "rehearsal": bool(args.rehearse_cpu),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "shape": list(shape), "calls": args.calls,
                      "rounds": args.rounds, "reps": args.reps,
                      "seed": args.seed, "rows": rows})
    if not args.rehearse_cpu:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "time_index_select.json"), "w") as fh:
            fh.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
