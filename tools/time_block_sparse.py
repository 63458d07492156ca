#!/usr/bin/env python
"""What ONE call of the block-sparse decode read costs, on the chip.

The kernel alone (``paddle_tpu/decode_attention.py``:
``block_sparse_decode_attention``) at ``minicpm_sala``'s shapes — 64
slots x 32,768 positions x 256 lanes of bf16, 2 K/V heads of 128, 16
query heads a group, 98 named blocks of 64: the first, the window's 33,
the top 64 — with every slot's position drawn as
``benchmark/traffic/shared_docs_qa.json`` leaves them (a document, a
question, a step drawn evenly over the request's life) and its lists
laid out as ``sparse_linear_lm.select_blocks`` lays them.  Three cases
of the top 64: ``drawn`` (any 64 of the blocks between the first and
the window, each head its own, in any order: what random keys select),
``scattered`` (evenly spread over them, the two heads interleaved: no
two tiles adjoin) and ``consecutive`` (the 64 blocks under the window,
both heads alike).  One jitted program runs the kernel ``--calls``
times in a row on the same leaves (a chunk's two sparse layers and
eight steps are sixteen); its time on the host's clock over the calls
is a call's time, and the bytes the lists name over it a rate.

    python tools/time_block_sparse.py                       # this checkout
    python tools/time_block_sparse.py --repo .parent_copy --repo .
    python tools/time_block_sparse.py --cut none --cut copies --cut arithmetic
    python tools/time_block_sparse.py --repo .parent_copy --repo . --build

``--repo`` loads ``paddle_tpu/decode_attention.py`` from another
checkout (several may be given: all run in this one process, turn and
turn about, so they share the chip and its clock); a checkout whose
kernel takes no ``shared_runs`` is called without.  ``--unroll`` and
``--score-rows`` set the module's ``_TILE_UNROLL`` and ``_SCORE_ROWS``
before the kernel is traced (the experiments that chose them).
``--cut copies`` traces the kernel with its DMAs left out (what the
arithmetic costs alone, over whatever the buffers hold), ``--cut
arithmetic`` with a chunk's products and softmax left out (what the
copies cost alone): both only where the checkout's
kernel is the hand-pipelined one, and neither is compared.  Every
other variant's contexts are compared with the first's (bf16
probabilities: the same sums in another order).
``--build`` first asks what the kernel costs a process to BUILD: one
fresh child process a checkout (before this one touches jax: a chip
belongs to one process) traces and lowers, without compiling, a program
that calls the kernel at the same shapes on two layers' leaves, and
prints the seconds of the trace and of the lowering (jax's and Pallas'
imports done before the clock starts), the equations of the traced
program, kernel body included, and how many kernels the lowered module
holds.  Every process that serves the cell pays those seconds, compile
cache hit or not: no compile is in them (PR 41's body: +8-9 s of a
cell's set-up with nine cache hits of nine).  The rows carry them as
``build_trace_s``, ``build_lower_s`` and ``build_equations``, beside us
a call.  Without a TPU (and without
``--rehearse-cpu``) ``--build`` still works: the child lowers for a
described v5e.
``--rehearse-cpu`` runs tiny shapes under Pallas interpret mode to
prove the script and prints no number a reader could take for the
chip's.  The last line of output is one JSON object.
"""
import argparse
import contextlib
import importlib.util
import inspect
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # decode_attention registers a counter


def load_module(repo, unroll=None, score_rows=None):
    """``decode_attention`` of the checkout at ``repo`` as a module of
    its own: its own jit cache, its own ``_block_part`` to cut and
    ``_TILE_UNROLL`` / ``_SCORE_ROWS`` to set."""
    path = os.path.join(repo, "paddle_tpu", "decode_attention.py")
    name = "decode_attention_%d" % len(sys.modules)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if unroll is not None and hasattr(mod, "_TILE_UNROLL"):
        mod._TILE_UNROLL = unroll
    if score_rows is not None and hasattr(mod, "_SCORE_ROWS"):
        mod._SCORE_ROWS = score_rows
    return mod


@contextlib.contextmanager
def no_copies():
    """While a kernel is traced in here its DMAs are neither started nor
    waited for."""
    from jax.experimental.pallas import tpu as pltpu

    class Nothing:
        def start(self, *a, **kw):
            pass

        wait = start

    real = pltpu.make_async_copy
    pltpu.make_async_copy = lambda *a, **kw: Nothing()
    try:
        yield
    finally:
        pltpu.make_async_copy = real


def no_arithmetic(q, k, v, ok, m, l, acc):
    """In ``_block_part``'s place: a chunk's buffers touched, nothing
    multiplied."""
    import jax.numpy as jnp

    rep = q.shape[0]
    rows = (k[:rep] + v[:rep]).astype(jnp.float32) + q.astype(jnp.float32)
    return m, l + 1.0, acc + rows


def equations(jaxpr):
    """Equations of ``jaxpr`` and of every jaxpr its equations carry (a
    kernel's body, a loop's, a branch's): what a trace binds and a
    lowering walks."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += equations(sub)
    return n


def kernel_keywords(mod, heads, group, head_dim, block, runs, interpret):
    """The keywords ``mod``'s kernel is called with; a checkout whose
    kernel takes no ``shared_runs`` is called without."""
    kw = dict(n_head=heads * group, n_kv_head=heads, scale=head_dim ** -0.5,
              block=block, interpret=interpret)
    if "shared_runs" in inspect.signature(
            mod.block_sparse_decode_attention).parameters:
        kw["shared_runs"] = tuple(map(tuple, runs))
    return kw


def two_layer_program(mod, shape, dtype, runs, interpret, sharding=None):
    """``(f, abstract arguments)``: the kernel of ``mod`` called on two
    layers' leaves at ``shape`` = ``(S, T, G, D, R, block, B)``, as a
    step with two sparse layers calls it."""
    import jax
    import jax.numpy as jnp

    S, T, G, D, R, block, B = shape
    kw = kernel_keywords(mod, G, R, D, block, runs, interpret)

    def f(q, k0, v0, k1, v1, ts, blocks, valid):
        ctx = mod.block_sparse_decode_attention(
            q, k0, v0, ts, blocks, valid, **kw).reshape(q.shape)
        return mod.block_sparse_decode_attention(
            q + ctx, k1, v1, ts, blocks, valid, **kw)

    def sd(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding)

    leaf = sd((S, T, G * D), jnp.dtype(dtype))
    return f, (sd((S, G * R * D), jnp.float32), leaf, leaf, leaf, leaf,
               sd((S,), jnp.int32), sd((S, G, B), jnp.int32),
               sd((S, G, B), jnp.bool_))


def build_cost(repo, shape, dtype, runs, rehearse):
    """Trace and lower (no compile) the two-layer program with the
    kernel of the checkout at ``repo``, in THIS process, which is a
    fresh one (``--build-child``).  Returns the row."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.experimental.pallas  # noqa: F401  (before the clock)
    import jax.experimental.pallas.tpu  # noqa: F401

    sharding, target = None, jax.devices()[0].platform
    if target != "tpu" and not rehearse:
        # no chip: lower for a described one
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding, target = SingleDeviceSharding(topo.devices[0]), "v5e described"
    mod = load_module(os.path.join(ROOT, repo))
    f, args = two_layer_program(mod, shape, dtype, runs, rehearse, sharding)
    t0 = time.perf_counter()
    traced = jax.jit(f).trace(*args)
    t1 = time.perf_counter()
    text = traced.lower().as_text()
    t2 = time.perf_counter()
    return {"repo": repo, "lowered_for": target,
            "trace_s": t1 - t0, "lower_s": t2 - t1,
            "equations": equations(traced.jaxpr.jaxpr),
            "kernels_in_module": text.count("tpu_custom_call"),
            "module_bytes": len(text)}


def positions(rng, slots, rung, rehearse):
    """One ``ts`` a slot as ``shared_docs_qa`` leaves them."""
    import numpy as np

    if rehearse:
        return rng.randint(rung // 3, rung, slots).astype(np.int32)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "shared_docs_qa.json")) as fh:
        traffic = json.load(fh)

    def draw(spec):
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], slots))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)

    doc = rng.choice(traffic["documents"], slots)
    life = draw(traffic["question"]) + draw(traffic["output"])
    ts = doc + (rng.rand(slots) * (life - 1)).astype(int)
    return np.minimum(ts, min(traffic["max_total"], rung) - 1).astype(np.int32)


def lists(rng, case, ts, rung, heads, block, window, topk):
    """``(blocks, valid)`` ``[S, heads, 1 + window + topk]`` for every
    slot at ``ts``: the first block, the window's, the top-k (``case``)
    among the blocks between them, as ``select_blocks`` orders and
    clamps them."""
    import numpy as np

    n_b = rung // block
    blocks, valid = [], []
    for t in ts:
        last = t // block
        lo = max((t + 1 - (window - 1) * block) // block, 0)
        win = lo + np.arange(window)
        fixed = np.concatenate([[0], win])
        fixed_ok = np.concatenate([[True], (win <= last) & (win >= 1)])
        between = np.arange(1, lo)
        rows, oks = [], []
        for g in range(heads):
            if case == "consecutive":
                top = between[-topk:]
            elif case == "scattered":
                top = between[g::max(len(between) // topk, 1)][:topk]
            else:
                top = rng.permutation(between)[:topk]
            ok = np.arange(topk) < len(top)
            top = np.concatenate([top, np.zeros(topk - len(top), int)])
            rows.append(np.concatenate([fixed, top]))
            oks.append(np.concatenate([fixed_ok, ok]))
        blocks.append(rows)
        valid.append(oks)
    return (np.minimum(np.asarray(blocks), n_b - 1).astype(np.int32),
            np.asarray(valid))


def named_bytes(ts, blocks, valid, block, row_bytes):
    """K and V bytes of the live rows of the blocks the lists name."""
    import numpy as np

    live = np.clip(ts[:, None, None] + 1 - blocks * block, 0, block)
    return int(2 * row_bytes * np.sum(live * valid))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", action="append", default=None)
    ap.add_argument("--cut", action="append", default=None,
                    choices=["none", "copies", "arithmetic"])
    ap.add_argument("--unroll", action="append", type=int, default=None,
                    help="tile reads issued a turn of the kernel's loop "
                         "(the module's _TILE_UNROLL, set before tracing)")
    ap.add_argument("--score-rows", action="append", type=int, default=None,
                    help="keys of a unit scored at a time (the module's "
                         "_SCORE_ROWS, set before tracing)")
    ap.add_argument("--case", default="drawn,scattered,consecutive")
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--rung", type=int, default=32768)
    ap.add_argument("--heads", type=int, default=2, help="K/V heads")
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--group", type=int, default=16,
                    help="query heads a K/V head")
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--window", type=int, default=33, help="window entries")
    ap.add_argument("--topk", type=int, default=64)
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
        args.slots, args.rung, args.window, args.topk = 4, 1024, 5, 4
        args.calls, args.reps = 2, 1
    runs = ((0, 1), (1, args.window))
    shape = (args.slots, args.rung, args.heads, args.head_dim, args.group,
             args.block, 1 + args.window + args.topk)
    builds = {}
    for repo in (args.repo or ["."]) if args.build else []:
        # before this process touches jax: the child may need the chip
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--build-child",
             json.dumps({"repo": repo, "shape": shape, "dtype": args.dtype,
                         "runs": runs, "rehearse": args.rehearse_cpu})],
            capture_output=True, text=True)
        if child.returncode:
            raise SystemExit("--build: the child for %r failed:\n%s"
                             % (repo, child.stderr[-2000:]))
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
            print(json.dumps({"tool": "time_block_sparse", "rows": [],
                              "builds": list(builds.values())}))
            return
        raise SystemExit("no TPU here (%s): the kernel's time is a chip "
                         "number; --rehearse-cpu proves the script"
                         % dev.platform)
    S, T, G, D, R = (args.slots, args.rung, args.heads, args.head_dim,
                     args.group)
    variants = []
    cuts = args.cut or ["none"]
    sets = [(u, r) for u in args.unroll or [None]
            for r in args.score_rows or [None]]
    for repo in args.repo or ["."]:
        for cut in cuts:
            for unroll, score_rows in sets:
                mod = load_module(os.path.join(ROOT, repo), unroll,
                                  score_rows)
                if not hasattr(mod, "_SCORE_ROWS") and (
                        cut, (unroll, score_rows)) != ("none", sets[0]):
                    continue    # nothing of that kernel to cut or to set
                if cut == "arithmetic":
                    mod._block_part = no_arithmetic
                variants.append((repo, cut, mod))

    def program(mod):
        kw = kernel_keywords(mod, G, R, D, args.block, runs,
                             args.rehearse_cpu)

        def run(q, kc, vc, ts, blocks, valid):
            ctx = q
            for _ in range(args.calls):     # a chunk's layers and steps
                ctx = mod.block_sparse_decode_attention(
                    q + 0.0 * ctx, kc, vc, ts, blocks, valid,
                    **kw).reshape(q.shape)
            return ctx

        return jax.jit(run)

    programs = [program(mod) for _, _, mod in variants]
    rng = np.random.RandomState(args.seed)
    q = jnp.asarray(rng.randn(S, G * R * D), jnp.float32)
    kc, vc = (jax.random.normal(k, (S, T, G * D), jnp.dtype(args.dtype))
              for k in jax.random.split(jax.random.PRNGKey(args.seed)))
    ts = positions(rng, S, T, args.rehearse_cpu)
    rows = []
    for case in args.case.split(","):
        blocks, valid = lists(rng, case, ts, T, G, args.block, args.window,
                              args.topk)
        dev_args = (q, kc, vc, jnp.asarray(ts), jnp.asarray(blocks),
                    jnp.asarray(valid))
        times = [[] for _ in variants]
        first = None
        for rep in range(args.reps + 1):    # rep 0 traces and compiles
            for i, (prog, (_, cut, _)) in enumerate(zip(programs, variants)):
                with (no_copies() if cut == "copies" and not rep
                      else contextlib.nullcontext()):
                    t0 = time.perf_counter()
                    ctx = prog(*dev_args)
                    ctx.block_until_ready()
                    dt = time.perf_counter() - t0
                if rep:
                    times[i].append(dt)
                elif cut == "none" and first is None:
                    first = np.asarray(ctx)
                elif cut == "none":
                    np.testing.assert_allclose(np.asarray(ctx), first,
                                               rtol=0, atol=2e-2)
        named = named_bytes(ts, blocks, valid, args.block,
                            D * jnp.dtype(args.dtype).itemsize)
        for (repo, cut, mod), tt in zip(variants, times):
            # an interpreter's time is no number
            tt = [None] if args.rehearse_cpu else tt
            us = (lambda x: None if x is None else x / args.calls * 1e6)
            call_us = us(statistics.median(tt))
            rows.append({
                "repo": repo, "cut": cut, "case": case,
                "unroll": getattr(mod, "_TILE_UNROLL", None),
                "score_rows": getattr(mod, "_SCORE_ROWS", None),
                "named_bytes": named, "call_us": call_us,
                "call_us_min": us(min(tt)),
                **{"build_" + k: builds[repo][k] for k in (
                    "trace_s", "lower_s", "equations") if repo in builds},
                "named_gb_per_s": call_us and named / call_us / 1e3})
            print(json.dumps(rows[-1]), flush=True)
    out = json.dumps({"tool": "time_block_sparse",
                      "rehearsal": bool(args.rehearse_cpu),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "shape": [S, T, G * D, G, R, args.block,
                                1 + args.window + args.topk],
                      "dtype": args.dtype, "calls": args.calls,
                      "reps": args.reps, "ts_min_max": [int(ts.min()),
                                                        int(ts.max())],
                      "rows": rows})
    if not args.rehearse_cpu:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "time_block_sparse.json"), "w") as fh:
            fh.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
