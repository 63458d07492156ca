#!/usr/bin/env python
"""What ONE K-row read of a window layer's ring costs, on the chip: the
Pallas kernel (``decode_attention.ring_rows_decode_attention``) and the
plain XLA form (``grouped_masked_decode_attention(..., window=)``), turn
and turn about in one process over the same ring leaves, contexts
compared first.

The shape is ``k_exaone_236b_a23b.long_answers_mtp_4k``'s: 128 slots,
rings of 128 rows, 8 K/V heads of 128 lanes under 64 query heads, bf16
leaves ``[128,128,1024]`` (33.5 MB each), TWO fresh rows a slot, a
round's FOUR window layers a pass, positions spread over the rung with
idle slots among them.

One jitted program makes ``--passes`` passes over the four layers (each
pass's queries depend on the pass before, so none is elided), a second
makes two; a launch costs the host's clock about a millisecond whatever
the program holds, so a layer's time is the SLOPE between the two
(``tools/time_delta_update.py`` says why).  Printed a form: the largest
difference of its context from the XLA form's, whether it left the rings
equal, ms a layer and GB/s on the two leaves a layer reads whole.

    python tools/time_ring_rows.py
    python tools/time_ring_rows.py --step-bytes 262144 --step-bytes 2097152

``--step-bytes`` times the kernel with that many bytes of a leaf a grid
step (it sets the module's ``_RING_STEP_BYTES`` before the kernel is
traced).  Chip runs, PR 59: 0.213 ms a layer (append, masks and q layout
with it) at 256 KiB, 512 KiB, 1 MiB and 2 MiB alike, the XLA form alone
0.262; unrolling the kernel's loop over a step's slots (2, 4, 8) and a
reciprocal for the softmax's division moved nothing (0.203-0.215): the
kernel is bound by its per-head products (a head's 128 x 128 keys meet 16
query rows), not by its copies nor by a slot's chain.  ``--rehearse-cpu``
runs a tiny shape under Pallas interpret mode to prove the script and
prints no number a reader could take for the chip's.  The last line of
output is one JSON object.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # decode_attention registers counters

#: slots, K/V heads, query heads a K/V head, lanes a head, ring rows,
#: fresh rows a slot, window layers a round
SHAPE = (128, 8, 8, 128, 128, 2, 4)
REHEARSAL = (4, 2, 8, 128, 16, 2, 2)


def inputs(shape, seed=3):
    import jax.numpy as jnp
    import numpy as np

    S, G, rep, D, L, K, layers = shape
    rng = np.random.RandomState(seed)
    kvs = [{n: jnp.asarray(rng.randn(S, L, G * D), jnp.bfloat16)
            for n in "kv"} for _ in range(layers)]
    q = jnp.asarray(rng.randn(S, K, G * rep * D), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.randn(S, K, G * D), jnp.float32)
                    for _ in range(2))
    ts = jnp.asarray(rng.randint(-1, 32 * L, S), jnp.int32)
    return q, k_new, v_new, kvs, ts


def program(form, shape, passes: int):
    """``passes`` passes over the layers' rings through ``form``, each
    pass's queries moved by the contexts before it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _, G, rep, D, L = shape[:5]
    kw = dict(n_head=G * rep, n_kv_head=G, scale=1.0 / np.sqrt(D), window=L)

    def run(q, k_new, v_new, kvs, ts):
        acc = jnp.zeros_like(q)
        for _ in range(passes):
            for kv in kvs:
                ctx, _ = form(q + 1e-3 * acc, k_new, v_new, kv, ts, **kw)
                acc = acc + ctx
        return acc

    return jax.jit(run), kw


def timed(fn, args, calls: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--step-bytes", type=int, action="append", default=[])
    ap.add_argument("--passes", type=int, default=10)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu import decode_attention as da

    shape = REHEARSAL if args.rehearse_cpu else SHAPE
    layers, leaf_bytes = shape[6], 2 * shape[0] * shape[4] * shape[1] * shape[3]
    data = inputs(shape)
    kernel = functools.partial(da.ring_rows_decode_attention,
                               interpret=args.rehearse_cpu)

    def sized(step_bytes):
        def form(*a, **k):
            da._RING_STEP_BYTES = step_bytes
            da._ring_call.cache_clear()
            return kernel(*a, **k)
        return form

    forms = {"xla": da.grouped_masked_decode_attention}
    forms.update({"kernel_%d" % b: sized(b)
                  for b in args.step_bytes or [da._RING_STEP_BYTES]})
    rows, ref = [], None
    for name, form in forms.items():
        _, kw = program(form, shape, 1)
        ctx, kv = jax.jit(functools.partial(form, **kw))(
            *data[:3], data[3][0], data[4])
        ref = ref or (ctx, kv)
        row = {"form": name,
               "max_abs_diff": float(jnp.abs(ctx - ref[0]).max()),
               "rings_equal": all(bool((kv[n] == ref[1][n]).all())
                                  for n in "kv")}
        if not args.rehearse_cpu:
            few, many = (timed(program(form, shape, p)[0], data, args.calls)
                         for p in (2, args.passes))
            row["ms_a_layer"] = 1e3 * (many - few) / (args.passes - 2) / layers
            row["gb_s"] = 2 * leaf_bytes / row["ms_a_layer"] / 1e6
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"rehearsal": args.rehearse_cpu, "shape": shape,
                      "device": jax.devices()[0].device_kind,
                      "rows": rows}))


if __name__ == "__main__":
    main()
