#!/usr/bin/env python
"""What ONE grouped-head append-and-read of a decode step costs, on the
chip: the Pallas kernel (``paddle_tpu/decode_attention.py``:
``append_rows`` + ``grouped_decode_attention``) and the XLA form
(``grouped_masked_decode_attention``), turn and turn about in one
process over the same leaves.

Five shapes (``--shape``, all unless said), every slot's position drawn
as the cell's traffic file leaves them (a prompt or a document and a
question, then a step drawn evenly over the answer's life):

* ``smallthinker`` — ``smallthinker_21b_a3b.shared_docs_qa_16k``'s global
  layers: 40 slots x 16,384 positions x 512 lanes of bf16, 4 K/V heads
  of 128, 7 query heads a K/V head;
* ``falcon`` — ``falcon_h1_34b.long_answers_batch``: 80 slots x 1,024 x
  512, 4 K/V heads of 128, 5 query heads a K/V head;
* ``k_exaone`` — ``k_exaone_236b_a23b.long_answers_mtp_4k``'s global
  layer and module: 128 slots x 4,096 x 1,024, 8 K/V heads of 128, 8
  query heads a K/V head, TWO fresh rows a slot (a self-drafting
  round's verify, and its module's pass);
* ``olmo`` — ``olmo_hybrid_7b.long_answers_batch``'s full layers: 80
  slots x 1,024 x 3,840, 30 K/V heads of 128, ONE query head each (a
  head is one row of a unit: ``--heads`` 5 / 6 / 10 / 15 / 30 a unit);
  the comparison and parity reference is the form that reads the leaves
  as they lie, ``lane_masked_decode_attention``, over the whole rung;
* ``lfm2`` — ``lfm2_24b_a2b.long_answers_2k``'s attention layers: 256
  slots x 2,048 x 512, 8 K/V heads of 64 lanes (two a lane tile), 4
  query heads a K/V head; the comparison is that lane form too (a view
  of 64-lane heads would copy the rung), and a checkout whose kernel
  wants whole-lane-tile heads runs it alone.

``--rows K`` hands every slot of every shape ``K`` fresh rows (row ``j``
at ``ts + j``) instead of its cell's: the XLA form of ``K`` rows is the
comparison and the parity reference, a checkout whose kernel takes one
row runs that form alone.

One jitted program runs a form ``--calls`` times in a row on the same
(donated) leaves; its time on the host's clock over the calls is a
call's time.  Printed a row: ms a call, GB/s on the LIVE bytes (K and V
rows ``<= ts``) and on the bytes the form READS (the kernel: what
``kv_positions_read`` rounds to; the XLA form: the whole rung).

    python tools/time_grouped_decode.py                     # this checkout
    python tools/time_grouped_decode.py --repo .parent_copy --repo .
    python tools/time_grouped_decode.py --cut none --cut copies --cut arithmetic
    python tools/time_grouped_decode.py --block 512 --block 2048 --heads 1 --heads 0

``--repo`` loads ``paddle_tpu/decode_attention.py`` from another checkout
(several may be given: all run in this one process, so they share the
chip and its clock); a checkout without the kernel runs the XLA form
alone.  ``--block``, ``--classes``, ``--ahead``, ``--heads`` and
``--slab`` set the module's ``_GROUPED_BLOCK``, ``_GROUPED_CLASSES``,
``_GROUPED_AHEAD``, ``_GROUPED_HEADS`` and ``_GROUPED_SLAB`` before the
kernel is traced (the experiments that chose them; a block is halved
until a leaf's slab of it fits ``_GROUPED_SLAB`` bytes, so a block of
512 at ``olmo`` is ``--block 512 --slab 4194304``; a row's
``read_block`` says what was read in).  ``--cut copies`` traces the kernel with its DMAs left out
(what the arithmetic costs alone, over whatever the buffers hold),
``--cut arithmetic`` with a block's products and softmax left out (what
the copies cost alone); neither is compared.  Every other variant's
contexts are compared with the XLA form's (bf16 weights: the same sums
in another order).
``--build`` first asks what the kernel costs a process to BUILD: one
fresh child process a checkout (before this one touches jax) traces and
lowers, without compiling, a program that calls the kernel on two
layers' leaves at the first shape, and prints the seconds of the trace
and of the lowering, the equations of the traced program and how many
kernels the lowered module holds.  Without a TPU (and without
``--rehearse-cpu``) ``--build`` lowers for a described v5e and the tool
stops there.
``--rehearse-cpu`` runs tiny shapes under Pallas interpret mode to prove
the script and prints no number a reader could take for the chip's.  The
last line of output is one JSON object.
"""
import argparse
import contextlib
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # decode_attention registers a counter
sys.path.insert(0, os.path.join(ROOT, "tools"))

from time_block_sparse import (equations, load_module, no_arithmetic,  # noqa: E402
                               no_copies)

#: slots, rung, K/V heads, head lanes, query heads a K/V head, traffic
SHAPES = {
    "smallthinker": (40, 16384, 4, 128, 7, "shared_docs_qa_16k"),
    "falcon": (80, 1024, 4, 128, 5, "long_answers_batch"),
    "k_exaone": (128, 4096, 8, 128, 8, "long_answers_mtp_4k"),
    "olmo": (80, 1024, 30, 128, 1, "long_answers_batch"),
    "lfm2": (256, 2048, 8, 64, 4, "long_answers_2k"),
}
REHEARSAL = {"smallthinker": (4, 512, 4, 128, 7, "shared_docs_qa_16k"),
             "falcon": (6, 256, 4, 128, 5, "long_answers_batch"),
             "k_exaone": (4, 1024, 8, 128, 8, "long_answers_mtp_4k"),
             "olmo": (6, 256, 6, 128, 1, "long_answers_batch"),
             "lfm2": (6, 256, 4, 64, 4, "long_answers_2k")}
#: fresh rows a slot, as the shape's cell hands them
ROWS = {"smallthinker": 1, "falcon": 1, "k_exaone": 2, "olmo": 1,
        "lfm2": 1}
KNOBS = ("block", "classes", "ahead", "heads", "slab")


def load(repo, knobs):
    """``decode_attention`` of the checkout at ``repo`` with the grouped
    kernel's constants set (None: as the checkout has them)."""
    mod = load_module(os.path.join(ROOT, repo))
    for name, value in zip(KNOBS, knobs):
        if value is not None and hasattr(mod, "_GROUPED_" + name.upper()):
            setattr(mod, "_GROUPED_" + name.upper(), value)
    return mod


def positions(rng, slots, rung, traffic, rehearse):
    """One ``ts`` a slot as the cell's traffic leaves them."""
    import numpy as np

    if rehearse:
        return rng.randint(rung // 3, rung, slots).astype(np.int32)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           traffic + ".json")) as fh:
        mix = json.load(fh)

    def draw(spec):
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], slots))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)

    if "documents" in mix:
        first = rng.choice(mix["documents"], slots) + draw(mix["question"])
    else:
        first = draw(mix["prompt"])
    ts = first + (rng.rand(slots) * (draw(mix["output"]) - 1)).astype(int)
    return np.minimum(ts, min(mix["max_total"], rung) - 1).astype(np.int32)


def last_rows(mod, ts, rows, rung):
    """The slots' last fresh rows, by the checkout's own rule (one that
    takes one row a slot has none: ``ts``)."""
    return ts if rows == 1 else mod.last_fresh_row(ts, rows, rung)


def forms(mod, shape, interpret, rows=1):
    """``{name: attend(q, k_new, v_new, kv, ts) -> (ctx, kv)}`` of the
    checkout ``mod``: its XLA form, and its kernel if it has one that
    takes ``rows`` fresh rows a slot."""
    S, T, G, D, R, _ = shape
    kw = dict(n_head=G * R, n_kv_head=G, scale=D ** -0.5)
    # one row of ONE query head a K/V head, or of heads narrower than a
    # lane tile: the form that reads the leaves as they lie (the per-head
    # view's is six float32 copies of a leaf a call at olmo's widths, a
    # re-tiled copy of each leaf at lfm2's)
    masked = (mod.lane_masked_decode_attention
              if (R == 1 or D % 128) and rows == 1
              and hasattr(mod, "lane_masked_decode_attention")
              else mod.grouped_masked_decode_attention)
    out = {"xla": lambda q, kn, vn, kv, ts: masked(q, kn, vn, kv, ts, **kw)}
    if hasattr(mod, "grouped_decode_attention") and (
            rows == 1 or hasattr(mod, "last_fresh_row")):
        sizes = kernel_sizes(mod, shape)

        def kernel(q, kn, vn, kv, ts):
            kv = mod.append_rows(kv, kn, vn, ts)
            work = mod.decode_work_items(last_rows(mod, ts, rows, T), T,
                                         *sizes)
            return mod.grouped_decode_attention(
                q, kv["k"], kv["v"], ts, work, block=sizes[0],
                tail=sizes[1], interpret=interpret, **kw), kv

        if sizes is not None:
            out["kernel"] = kernel
    return out


def kernel_sizes(mod, shape):
    """``(block, tail)`` the checkout's kernel reads ``shape`` in."""
    import jax.numpy as jnp

    _, T, G, D, R, _ = shape
    return mod.step_read_sizes(T, G * D, jnp.bfloat16, n_head=G * R,
                               n_kv_head=G, backend="tpu")


def read_positions(mod, form, ts, shape, rows=1):
    """Positions a step of ``form`` reads, summed over the slots."""
    import numpy as np

    if form == "xla":
        return int(shape[1] * len(ts))
    return int(np.sum(mod.kv_positions_read(
        last_rows(mod, ts, rows, shape[1]), *kernel_sizes(mod, shape))))


def abstract(shape, dtype, sharding=None, rows=1):
    import jax
    import jax.numpy as jnp

    S, T, G, D, R, _ = shape
    by = (S,) if rows == 1 else (S, rows)

    def sd(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding)

    leaf = sd((S, T, G * D), jnp.dtype(dtype))
    return (sd(by + (G * R * D,)), sd(by + (G * D,)), sd(by + (G * D,)),
            leaf, leaf, leaf, leaf, sd((S,), jnp.int32))


def two_layer_program(mod, shape, dtype, interpret, sharding=None, rows=1):
    """``(f, abstract arguments)``: a step's two grouped layers through
    the kernel of ``mod`` at ``shape``, both layers' leaves donated."""
    attend = forms(mod, shape, interpret, rows)["kernel"]

    def f(q, kn, vn, k0, v0, k1, v1, ts):
        ctx, a = attend(q, kn, vn, {"k": k0, "v": v0}, ts)
        ctx, b = attend(q + ctx, kn, vn, {"k": k1, "v": v1}, ts)
        return ctx, a, b

    return f, abstract(shape, dtype, sharding, rows)


def build_cost(repo, shape, dtype, knobs, rehearse, rows=1):
    """Trace and lower (no compile) a step's two grouped layers with the
    kernel of the checkout at ``repo``, in THIS process, which is a
    fresh one (``--build-child``).  Returns the row."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.experimental.pallas  # noqa: F401  (before the clock)
    import jax.experimental.pallas.tpu  # noqa: F401

    sharding, target = None, jax.devices()[0].platform
    if target != "tpu" and not rehearse:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding, target = SingleDeviceSharding(topo.devices[0]), "v5e described"
    f, args = two_layer_program(load(repo, knobs), shape, dtype, rehearse,
                                sharding, rows)
    t0 = time.perf_counter()
    traced = jax.jit(f, donate_argnums=(3, 4, 5, 6)).trace(*args)
    t1 = time.perf_counter()
    text = traced.lower().as_text()
    t2 = time.perf_counter()
    return {"repo": repo, "lowered_for": target,
            "trace_s": t1 - t0, "lower_s": t2 - t1,
            "equations": equations(traced.jaxpr.jaxpr),
            "kernels_in_module": text.count("tpu_custom_call"),
            "module_bytes": len(text)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None,
                    choices=sorted(SHAPES))
    ap.add_argument("--cut", action="append", default=None,
                    choices=["none", "copies", "arithmetic"])
    for knob in KNOBS:
        ap.add_argument("--" + knob, action="append", type=int, default=None,
                        help="the module's _GROUPED_%s, set before tracing"
                        % knob.upper())
    ap.add_argument("--rows", type=int, default=None,
                    help="fresh rows a slot (a speculative round's K); "
                         "unsaid: as the shape's cell hands them")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--build", action="store_true",
                    help="first, in a fresh process a checkout: seconds "
                         "to trace and to lower the kernel (no compile)")
    ap.add_argument("--build-child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.build_child:    # the fresh process: one row, nothing else
        print(json.dumps(build_cost(**json.loads(args.build_child))))
        return
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.calls, args.reps = 2, 1
    shapes = {name: (REHEARSAL if args.rehearse_cpu else SHAPES)[name]
              for name in args.shape or list(SHAPES)}
    sets = [(b, c, a, h, x) for b in args.block or [None]
            for c in args.classes or [None] for a in args.ahead or [None]
            for h in args.heads or [None] for x in args.slab or [None]]
    builds = {}
    for repo in (args.repo or ["."]) if args.build else []:
        # before this process touches jax: the child may need the chip
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--build-child",
             json.dumps({"repo": repo, "shape": next(iter(shapes.values())),
                         "dtype": args.dtype, "knobs": sets[0],
                         "rehearse": args.rehearse_cpu,
                         "rows": args.rows or ROWS[next(iter(shapes))]})],
            capture_output=True, text=True)
        if child.returncode:
            print("--build: the child for %r failed:\n%s"
                  % (repo, child.stderr[-2000:]), flush=True)
            continue        # a checkout without the kernel builds none
        builds[repo] = json.loads(child.stdout.strip().splitlines()[-1])
        if args.rehearse_cpu:   # an interpreter's lowering is no number
            builds[repo].update(trace_s=None, lower_s=None)
        print(json.dumps(builds[repo]), flush=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        if builds:      # what a build costs needs no chip
            print(json.dumps({"tool": "time_grouped_decode", "rows": [],
                              "builds": list(builds.values())}))
            return
        raise SystemExit("no TPU here (%s): the kernel's time is a chip "
                         "number; --rehearse-cpu proves the script"
                         % dev.platform)
    rows = []
    for shape_name, shape in shapes.items():
        S, T, G, D, R, traffic = shape
        fresh = args.rows or ROWS[shape_name]
        variants = []       # (repo, form, cut, module, attend)
        for repo in args.repo or ["."]:
            mod = load(repo, sets[0])   # nothing of the XLA form to set
            variants.append((repo, "xla", "none", mod, forms(
                mod, shape, args.rehearse_cpu, fresh)["xla"]))
            for knobs in sets:
                for cut in args.cut or ["none"]:
                    mod = load(repo, knobs)
                    if cut == "arithmetic":
                        mod._block_part = no_arithmetic
                    attend = forms(mod, shape, args.rehearse_cpu,
                                   fresh).get("kernel")
                    if attend is not None:
                        variants.append((repo, "kernel", cut, mod, attend))

        def program(attend):
            def run(q, kn, vn, k, v, ts):
                ctx, kv = q, {"k": k, "v": v}
                for _ in range(args.calls):     # a chunk's layers and steps
                    ctx, kv = attend(q + 0.0 * ctx, kn, vn, kv, ts)
                return ctx, kv["k"], kv["v"]

            return jax.jit(run, donate_argnums=(3, 4))

        programs = [program(v[-1]) for v in variants]
        rng = np.random.RandomState(args.seed)
        by = (S,) if fresh == 1 else (S, fresh)
        q = jnp.asarray(rng.randn(*by, G * R * D), jnp.float32)
        kn, vn = (jnp.asarray(rng.randn(*by, G * D), jnp.float32)
                  for _ in range(2))
        k, v = (jax.random.normal(key, (S, T, G * D), jnp.dtype(args.dtype))
                for key in jax.random.split(jax.random.PRNGKey(args.seed)))
        ts_host = positions(rng, S, T, traffic, args.rehearse_cpu)
        ts = jnp.asarray(ts_host)
        times = [[] for _ in variants]
        first = None
        for rep in range(args.reps + 1):    # rep 0 traces and compiles
            for i, (prog, var) in enumerate(zip(programs, variants)):
                cut = var[2]
                with (no_copies() if cut == "copies" and not rep
                      else contextlib.nullcontext()):
                    t0 = time.perf_counter()
                    ctx, k, v = prog(q, kn, vn, k, v, ts)
                    ctx.block_until_ready()
                    dt = time.perf_counter() - t0
                if rep:
                    times[i].append(dt)
                elif cut == "none" and first is None:
                    first = np.asarray(ctx)
                elif cut == "none":
                    np.testing.assert_allclose(np.asarray(ctx), first,
                                               rtol=0, atol=2e-2)
        row_bytes = 2 * G * D * jnp.dtype(args.dtype).itemsize  # K and V
        # what is live: every position the slot's LAST row may read
        live = int(np.sum(np.minimum(ts_host + fresh, T))) * row_bytes
        for (repo, form, cut, mod, _), tt in zip(variants, times):
            tt = [None] if args.rehearse_cpu else tt  # no interpreter's time
            ms = (lambda x: None if x is None else x / args.calls * 1e3)
            call_ms = ms(statistics.median(tt))
            read = read_positions(mod, form, ts_host, shape,
                                  fresh) * row_bytes
            rows.append({
                "shape": shape_name, "fresh_rows": fresh, "repo": repo,
                "form": form, "cut": cut,
                **{name: getattr(mod, "_GROUPED_" + name.upper(), None)
                   for name in KNOBS},
                "read_block": (kernel_sizes(mod, shape)[0]
                               if form == "kernel" else None),
                # K/V heads a product scored (the knob, else the rule)
                "unit_heads": (mod._unit_heads(
                    G, mod._head_rows(fresh, R)
                    if hasattr(mod, "_head_rows") else -(-fresh * R // 8) * 8,
                    mod._GROUPED_HEADS,
                    *([mod._heads_a_tile(D)]    # heads a lane tile
                      if hasattr(mod, "_heads_a_tile") else []))
                    if form == "kernel" and hasattr(mod, "_unit_heads")
                    else None),
                "live_bytes": live, "read_bytes": read,
                "read_over_live": read / live,
                "call_ms": call_ms, "call_ms_min": ms(min(tt)),
                **{"build_" + key: builds[repo][key] for key in (
                    "trace_s", "lower_s", "equations") if repo in builds},
                "live_gb_per_s": call_ms and live / call_ms / 1e6,
                "read_gb_per_s": call_ms and read / call_ms / 1e6})
            print(json.dumps(rows[-1]), flush=True)
        del k, v
    out = json.dumps({"tool": "time_grouped_decode",
                      "rehearsal": bool(args.rehearse_cpu),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "shapes": {n: list(s[:5]) for n, s in shapes.items()},
                      "dtype": args.dtype, "calls": args.calls,
                      "reps": args.reps, "rows": rows,
                      "builds": list(builds.values())})
    if not args.rehearse_cpu:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "time_grouped_decode.json"), "w") as fh:
            fh.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
