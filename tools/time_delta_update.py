#!/usr/bin/env python
"""What ONE gated delta-rule state update of a decode step costs, on the
chip: the Pallas kernel (``paddle_tpu/delta_hybrid_lm.py``:
``kernel_gated_delta_step``) and the two-fusion XLA form
(``xla_gated_delta_step``), turn and turn about in one process over the
same state leaf, updated in place.

The shape is ``olmo_hybrid_7b.long_answers_batch``'s: 80 slots, 30 heads
of 96 key and 192 value lanes, the leaf ``f32[80,15,96,384]`` (two heads
a row of lanes, 177 MB), every slot live as the cell's traffic leaves
them (one caller a slot).

One jitted program runs a form on ``--calls`` (donated) leaves in a row —
a step's nine linear layers, each call behind an optimization barrier so
that no call's pass over its leaf is fused with the next call's — and a
second program does that ``--rounds`` times over.  A launch costs the
host's clock about a millisecond whatever the program holds (0.84-1.02 ms
here: as much as two calls), so a call's time is the SLOPE between the
two programs: (t of the rounds - t of one) / ((rounds - 1) x calls).
Printed a row: ms a call, the launch's fixed ms, and GB/s on the two moves the rule NEEDS (each stepped row's state once in
and once out, with q, k, v, both gates and o: what
``benchmark/lib/costs_delta_hybrid.delta_update_min_bytes`` counts),
whatever the form moves.

    python tools/time_delta_update.py                  # the rule's block
    python tools/time_delta_update.py --slots 8 --slots 16
    python tools/time_delta_update.py --build

``--slots`` times the kernel at that many slots a block (it sets the
module's ``_BLOCK_BYTES`` to so many slots' state before the kernel is
traced, so the module's own rule ``_block_slots`` arrives there: how the
constant was chosen).  ``--build`` first asks what the kernel costs a
process to BUILD: a fresh child process (before this one touches jax)
traces and lowers, without compiling, a program that calls the kernel on
two layers' leaves, and prints the seconds of the trace and of the
lowering, the equations of the traced program and how many kernels the
lowered module holds; without a TPU (and without ``--rehearse-cpu``) it
lowers for a described v5e and the tool stops there.
``--rehearse-cpu`` runs a tiny shape under Pallas interpret mode to
prove the script and prints no number a reader could take for the
chip's.  The last line of output is one JSON object.
"""
import argparse
import functools
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # delta_hybrid_lm registers a counter
sys.path.insert(0, os.path.join(ROOT, "tools"))

from time_block_sparse import equations  # noqa: E402

#: slots, heads, key lanes, value lanes
SHAPE = (80, 30, 96, 192)
REHEARSAL = (16, 4, 16, 64)


def leaf_shape(dh, shape):
    n, h, dk, dv = shape
    g = dh.heads_per_tile(h, dv)
    return (n, h // g, dk, g * dv)


def forms(dh, interpret):
    """``{name: step(q, k, v, alpha, beta, s, ts) -> (o, s)}``."""
    def kernel(*a):
        return dh.kernel_gated_delta_step(*a, interpret=interpret)

    return {"xla": dh.xla_gated_delta_step, "kernel": kernel}


def set_slots(dh, shape, slots):
    """The module's block budget set to ``slots`` slots of state (None:
    as the module has it): what is traced next takes the rule's new
    answer.  Returns the slots a block the rule then gives."""
    _, _, dk, lanes = leaf_shape(dh, shape)
    if slots is not None:
        dh._BLOCK_BYTES = slots * 4 * dk * lanes
    return dh._block_slots(shape[0], dk, lanes)


def abstract(dh, shape, sharding=None):
    import jax
    import jax.numpy as jnp

    n, h, dk, dv = shape

    def sd(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding)

    leaf = sd(leaf_shape(dh, shape))
    return (sd((n, h, dk)), sd((n, h, dk)), sd((n, h, dv)), sd((n, h)),
            sd((n, h)), leaf, leaf, sd((n,), jnp.int32))


def two_layer_program(dh, shape, interpret, sharding=None):
    """``(f, abstract arguments)``: a step's two linear layers through
    the kernel at ``shape``, both layers' leaves donated."""
    step = forms(dh, interpret)["kernel"]

    def f(q, k, v, alpha, beta, s0, s1, ts):
        o, s0 = step(q, k, v, alpha, beta, s0, ts)
        o, s1 = step(q, k, v + o, alpha, beta, s1, ts)
        return o, s0, s1

    return f, abstract(dh, shape, sharding)


def build_cost(shape, slots, rehearse):
    """Trace and lower (no compile) a step's two linear layers through
    the kernel, in THIS process, which is a fresh one
    (``--build-child``).  Returns the row."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.experimental.pallas  # noqa: F401  (before the clock)
    import jax.experimental.pallas.tpu  # noqa: F401

    from paddle_tpu import delta_hybrid_lm as dh

    sharding, target = None, jax.devices()[0].platform
    if target != "tpu" and not rehearse:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding, target = SingleDeviceSharding(topo.devices[0]), "v5e described"
    block = set_slots(dh, shape, slots)
    f, args = two_layer_program(dh, tuple(shape), rehearse, sharding)
    t0 = time.perf_counter()
    traced = jax.jit(f, donate_argnums=(5, 6)).trace(*args)
    t1 = time.perf_counter()
    text = traced.lower().as_text()
    t2 = time.perf_counter()
    return {"lowered_for": target, "block_slots": block,
            "trace_s": t1 - t0, "lower_s": t2 - t1,
            "equations": equations(traced.jaxpr.jaxpr),
            "kernels_in_module": text.count("tpu_custom_call"),
            "module_bytes": len(text)}


def needed_bytes(shape, live_rows):
    """What the rule needs moved for ``live_rows`` stepped rows: the
    state once in and once out, q, k, v, both gates in and o out
    (``benchmark/lib/costs_delta_hybrid.delta_update_min_bytes`` for one
    layer and step)."""
    _, h, dk, dv = shape
    return 4 * live_rows * h * (2 * dk * dv + 2 * dk + 2 * dv + 2)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", action="append", type=int, default=None,
                    help="slots a block of the kernel (sets the module's "
                         "_BLOCK_BYTES before tracing); unsaid: the rule's")
    ap.add_argument("--calls", type=int, default=9)
    ap.add_argument("--rounds", type=int, default=4,
                    help="passes over the leaves in the longer program")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--build", action="store_true",
                    help="first, in a fresh process: seconds to trace and "
                         "to lower the kernel (no compile)")
    ap.add_argument("--build-child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.build_child:    # the fresh process: one row, nothing else
        print(json.dumps(build_cost(**json.loads(args.build_child))))
        return
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.calls, args.rounds, args.reps = 2, 2, 1
    shape = REHEARSAL if args.rehearse_cpu else SHAPE
    builds = []
    for slots in (args.slots or [None]) if args.build else []:
        # before this process touches jax: the child may need the chip
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--build-child",
             json.dumps({"shape": shape, "slots": slots,
                         "rehearse": args.rehearse_cpu})],
            capture_output=True, text=True)
        if child.returncode:
            raise SystemExit("--build: the child failed:\n%s"
                             % child.stderr[-2000:])
        builds.append(json.loads(child.stdout.strip().splitlines()[-1]))
        if args.rehearse_cpu:   # an interpreter's lowering is no number
            builds[-1].update(trace_s=None, lower_s=None)
        print(json.dumps(builds[-1]), flush=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import delta_hybrid_lm as dh

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        if builds:      # what a build costs needs no chip
            print(json.dumps({"tool": "time_delta_update", "rows": [],
                              "builds": builds}))
            return
        raise SystemExit("no TPU here (%s): the update's time is a chip "
                         "number; --rehearse-cpu proves the script"
                         % dev.platform)
    n, h, dk, dv = shape
    step = forms(dh, args.rehearse_cpu)

    shapes = abstract(dh, shape)
    shapes = shapes[:5] + ([shapes[5]] * args.calls,) + shapes[7:]

    def programs(form):
        """The form over the leaves once and ``--rounds`` times, traced
        and compiled at once (while the module's budget says so)."""
        def run(q, k, v, alpha, beta, leaves, ts, *, rounds):
            o = jnp.zeros_like(v)
            for _ in range(rounds):
                out = []
                for s in leaves:            # a step's linear layers
                    o, s = jax.lax.optimization_barrier(step[form](
                        q, k, v + 0.0 * o, alpha, beta, s, ts))
                    out.append(s)
                leaves = out
            return o, leaves

        return [jax.jit(functools.partial(run, rounds=r), donate_argnums=(5,))
                .lower(*shapes).compile() for r in (1, args.rounds)]

    variants = [("xla", None, programs("xla"))]
    for slots in args.slots or [None]:
        block = set_slots(dh, shape, slots)
        variants.append(("kernel", block, programs("kernel")))
    rng = np.random.RandomState(args.seed)
    q = dh.l2_norm(jnp.asarray(rng.randn(n, h, dk), jnp.float32)) * dk ** -0.5
    k = dh.l2_norm(jnp.asarray(rng.randn(n, h, dk), jnp.float32))
    v = jnp.asarray(rng.randn(n, h, dv), jnp.float32)
    alpha = jnp.asarray(rng.uniform(0.9, 1.0, (n, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (n, h)), jnp.float32)
    ts_host = rng.randint(1, 1000, n).astype(np.int32)  # every slot live
    ts = jnp.asarray(ts_host)

    def fresh_leaves():
        return [jax.random.normal(key, leaf_shape(dh, shape), jnp.float32)
                for key in jax.random.split(jax.random.PRNGKey(args.seed),
                                            args.calls)]

    # parity first, each form from the same leaves
    first = None
    for form, block, progs in variants:
        o, s = progs[0](q, k, v, alpha, beta, fresh_leaves(), ts)
        got = (np.asarray(o), np.asarray(s[-1][:2]))
        if first is None:
            first = got
        for a, b in zip(got, first):
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-4)
        del o, s
    s = fresh_leaves()
    times = [([], []) for _ in variants]
    for _ in range(args.reps):
        for i, (_, _, progs) in enumerate(variants):
            for prog, tt in zip(progs, times[i]):
                t0 = time.perf_counter()
                o, s = prog(q, k, v, alpha, beta, s, ts)
                o.block_until_ready()
                tt.append(time.perf_counter() - t0)
    need = needed_bytes(shape, int(np.sum(ts_host >= 0)))
    rows = []
    for (form, block, _), (one, many) in zip(variants, times):
        one, many = statistics.median(one), statistics.median(many)
        call_ms = (many - one) / ((args.rounds - 1) * args.calls) * 1e3
        row = {"form": form, "block_slots": block, "needed_bytes": need,
               "call_ms": call_ms, "launch_ms": one * 1e3
               - call_ms * args.calls,
               "needed_gb_per_s": need / call_ms / 1e6}
        if args.rehearse_cpu:       # no interpreter's time
            row.update(call_ms=None, launch_ms=None, needed_gb_per_s=None)
        rows.append(row)
        print(json.dumps(rows[-1]), flush=True)
    out = json.dumps({"tool": "time_delta_update",
                      "rehearsal": bool(args.rehearse_cpu),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "shape": list(shape),
                      "leaf": list(leaf_shape(dh, shape)),
                      "calls": args.calls, "rounds": args.rounds,
                      "reps": args.reps,
                      "seed": args.seed, "rows": rows, "builds": builds})
    if not args.rehearse_cpu:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "time_delta_update.json"), "w") as fh:
            fh.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
