#!/usr/bin/env python
"""What ONE dense latent read costs, on the chip: the Pallas kernel
(``decode_attention.dense_latent_kernel_attention``: each slot's OWN live
key blocks from a work list) and the XLA form it replaces there
(``decode_attention._dense_latent_xla``: every slot walks the blocks of
the pool's longest context), turn and turn about in one process over the
same leaves, contexts compared first.

The shape is ``openpangu_ultra_moe_718b.shared_docs_qa_mtp_16k``'s: 32
slots x 16,384 positions of one 640-lane bf16 row (576 filled), 128 heads,
TWO fresh rows a slot, a round's SIX leaves a pass, contexts drawn as the
cell's traffic leaves them (a document of 8,192-15,360 tokens, a question,
part of an answer).

One jitted program makes ``--passes`` passes over the six leaves (each
call's queries depend on the context before, so none is elided), a second
makes one; a launch costs the host's clock about a millisecond whatever
the program holds, so a call's time is the SLOPE between the two
(``tools/time_delta_update.py`` says why).  The pad, scale and cast of the
queries (10 MB written a call) are inside both forms' times.  Printed a
variant: the largest difference of its context from the XLA form's, ms a
call, the positions it touched over those that were live, and its
arithmetic as a share of the chip's bf16 peak over what was live and over
what it touched (a position: ``2 * K * H * (lanes + d_value)`` FLOPs, the
lanes padded to whole tiles).

    python tools/time_dense_latent.py
    python tools/time_dense_latent.py --block 256 --block 512 --block 1024 \\
        --ahead 1 --ahead 2 --cut none --cut copies --cut arithmetic

``--block`` sets the key block (both forms'), ``--ahead`` the module's
``_DENSE_LATENT_AHEAD`` before the kernel is traced, ``--cut`` leaves the
DMAs or a block's arithmetic out of the kernel (every variant is traced
in a copy of the module of its own; an option given twice is measured
twice).  ``--rehearse-cpu`` runs a
tiny shape under Pallas interpret mode to prove the script and prints no
number a reader could take for the chip's.  The last line of output is
one JSON object.

Chip runs, PR 61 (371,575 live positions in 31 slots): the XLA form 2.425
ms a call in blocks of 512 (62.7% of the bf16 peak over the 1.367 of the
live positions it touches, 45.9% over what is live); the kernel with one
read ahead 1.565 in blocks of 512, **1.438 in 1024 (77.4% over what is
live)**, 1.399 in 2048; with two reads ahead 1.578 / 1.446 / 1.403; in
1024 with its copies cut out 1.420, with its arithmetic cut out 0.703
(``_DENSE_LATENT_KERNEL_BLOCK`` and ``_DENSE_LATENT_AHEAD`` in
``paddle_tpu/decode_attention.py`` keep the readings).
"""
import argparse
import contextlib
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # decode_attention registers counters
sys.path.insert(0, os.path.join(ROOT, "tools"))

from time_block_sparse import (equations, load_module,  # noqa: E402,F401
                               no_copies)
from time_grouped_decode import positions  # noqa: E402

#: slots, rung, a row's filled lanes, fresh rows a slot, heads, value
#: lanes, leaves a round, the cell's traffic
SHAPE = (32, 16384, 576, 2, 128, 512, 6, "shared_docs_qa_mtp_16k")
REHEARSAL = (4, 256, 200, 2, 16, 128, 2, "shared_docs_qa_mtp_16k")
BF16_PEAK = 197e12      # FLOP/s of one v5e chip (benchmark/lib: peaks)


def no_arithmetic(q, k, v, ok, m, l, acc):
    """In ``_block_part``'s place: a block's buffers touched, nothing
    multiplied."""
    import jax.numpy as jnp

    rows = q.shape[0]
    part = (k[:rows, :v.shape[1]] + v[:rows]).astype(jnp.float32)
    return m, l + 1.0, acc + part + q[:, :v.shape[1]].astype(jnp.float32)


def forms(da, shape, block, interpret):
    """``{name: read(q, kv, ts) -> ctx}``: the XLA form and the kernel,
    both in key blocks of ``block``."""
    d_value = shape[5]
    kw = dict(d_value=d_value, scale=shape[2] ** -0.5)

    def xla(q, kv, ts):
        leaf = kv["latent"]
        S, K, H, _ = q.shape
        return da._dense_latent_xla(
            da._dense_latent_queries(q, leaf, kw["scale"]), leaf, ts,
            heads=H, d_value=d_value, key_block=block).reshape(
                S, K, H, d_value)

    return {"xla": xla,
            "kernel": functools.partial(
                da.dense_latent_kernel_attention, key_block=block,
                interpret=interpret, **kw)}


def abstract(shape, sharding=None):
    import jax
    import jax.numpy as jnp

    S, T, row, K, H, _, leaves, _ = shape
    lanes = -(-row // 128) * 128

    def sd(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding)

    return (sd((S, K, H, row)),
            [{"latent": sd((S, T, lanes), jnp.bfloat16)}
             for _ in range(leaves)], sd((S,), jnp.int32))


def program(read, passes: int):
    """``passes`` passes over the leaves through ``read``, each call's
    queries moved by the context before it."""
    import jax

    def run(q, kvs, ts):
        ctx = None
        for _ in range(passes):
            for kv in kvs:
                ctx = read(q if ctx is None
                           else q * (1.0 + 1e-6 * ctx[..., :1]), kv, ts)
        return ctx

    return jax.jit(run)


def round_program(da, shape, block=None, interpret=False):
    """``(f, abstract arguments)``: a round's leaves through the kernel,
    as the builder's layers and module call it (what the tests count the
    body's equations on)."""
    read = forms(da, shape, block or da.dense_latent_kernel_block(shape[1]),
                 interpret)["kernel"]
    return program(read, 1).__wrapped__, abstract(shape)


def timed(fn, args, calls: int) -> float:
    """The MEDIAN wall time of a call, each waited for (one stall of the
    host inside a mean of ten short calls moved a slope by 13%: PR 61)."""
    import statistics

    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(calls):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--block", type=int, action="append", default=[])
    ap.add_argument("--ahead", type=int, action="append", default=[])
    ap.add_argument("--cut", action="append", default=None,
                    choices=["none", "copies", "arithmetic"])
    ap.add_argument("--passes", type=int, default=5)
    ap.add_argument("--calls", type=int, default=15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import decode_attention as da

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit("no TPU here (%s): the read's time is a chip "
                         "number; --rehearse-cpu proves the script"
                         % dev.platform)
    shape = REHEARSAL if args.rehearse_cpu else SHAPE
    S, T, row, K, H, d_value, leaves, traffic = shape
    lanes = -(-row // 128) * 128
    rng = np.random.RandomState(args.seed)
    ts_host = positions(rng, S, T, traffic, args.rehearse_cpu)
    ts_host[rng.randint(S)] = -1        # an idle slot among them
    q = jnp.asarray(rng.randn(S, K, H, row), jnp.float32)
    kvs = [{"latent": da.pad_lanes(jax.random.normal(
        key, (S, T, row), jnp.bfloat16), lanes)}
        for key in jax.random.split(jax.random.PRNGKey(args.seed), leaves)]
    data = (q, kvs, jnp.asarray(ts_host))
    last = np.where(ts_host >= 0, np.minimum(ts_host + K, T), 0)
    live = int(last.sum())
    flops_a_position = 2 * K * H * (lanes + d_value)
    blocks = args.block or [64 if args.rehearse_cpu
                            else da.dense_latent_kernel_block(T)]
    variants = []       # (form, block, ahead, cut)
    for block in blocks:
        variants.append(("xla", block, None, "none"))
        variants += [("kernel", block, ahead, cut)
                     for ahead in args.ahead or [da._DENSE_LATENT_AHEAD]
                     for cut in args.cut or ["none"]]
    rows, ref = [], {}
    for form, block, ahead, cut in variants:
        # a module of its own a variant: jax keys its traces on the
        # function, so a second trace of ONE module's kernel at the same
        # static arguments would be the first one's, whatever was cut
        mod = load_module(ROOT)
        if ahead is not None:
            mod._DENSE_LATENT_AHEAD = ahead
        if cut == "arithmetic":
            mod._block_part = no_arithmetic
        read = forms(mod, shape, block, args.rehearse_cpu)[form]
        # the kernel: each slot's own blocks; the XLA form: the longest
        # context's, for every slot (idle ones too: it is batched)
        touched = (int((-(-last // block) * block).sum()) if form == "kernel"
                   else S * da.dense_latent_positions_touched(
                       int(last.max()), T, block))
        row_out = {"form": form, "block": block, "ahead": ahead, "cut": cut,
                   "live_positions": live, "touched_positions": touched,
                   "touched_over_live": touched / live}
        with (no_copies() if cut == "copies" else contextlib.nullcontext()):
            ctx = jax.jit(read)(q, kvs[0], data[2])
            if cut == "none":
                ref.setdefault(block, ctx)
                row_out["max_abs_diff"] = float(
                    jnp.abs(ctx - ref[block]).max())
                row_out["idle_slots_zero"] = not bool(
                    np.asarray(ctx)[ts_host < 0].any())
            if not args.rehearse_cpu:
                one, many = (timed(program(read, p), data, args.calls)
                             for p in (1, args.passes))
                ms = 1e3 * (many - one) / (args.passes - 1) / leaves
                row_out.update(
                    ms_a_call=ms,
                    us_a_block=1e3 * ms / (touched / block),
                    peak_share_over_live=(
                        live * flops_a_position / (ms * 1e-3) / BF16_PEAK),
                    peak_share_over_touched=(
                        touched * flops_a_position / (ms * 1e-3)
                        / BF16_PEAK))
        rows.append(row_out)
        print(json.dumps(row_out), flush=True)
    print(json.dumps({"tool": "time_dense_latent",
                      "rehearsal": bool(args.rehearse_cpu),
                      "shape": shape[:-1], "traffic": traffic,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "rows": rows}))


if __name__ == "__main__":
    main()
