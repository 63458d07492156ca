"""chip_smoke.py — the quickest proof that the main path still starts on the chip.

Drives Program -> Executor -> DecodeServer once, in ONE process on ONE
chip, through the public package surface, at the full width of the
repo's BERT-base configuration (weights random, from a seed):

* train leg:  BERT-base pretraining at the ``bert_base`` cells' widths
  (V=30522, D=768, L=12, H=12, d_inner=3072, S=128, batch 128, Adam
  under bf16 AMP) -> ``Executor(TPUPlace(0))`` -> startup, two single
  steps, one ``run(steps=8, per_step_feed=True)`` chunk, then the same
  again inside a window that must not compile.
* serve leg:  the transformer LM at the same width -> pooled KV step fn
  -> ``DecodeServer`` -> ``warmup()`` -> concurrent ``submit()`` /
  ``stream()`` requests -> every generated token checked against a
  full-prefix forward of the Program.
* four-chip leg (only when jax reports >= 4 devices): the same LM as an
  Adam training program, fsdp-4 over real devices vs one chip.

It FAILS (non-zero exit, no result line) when jax finds no TPU, when a
leg raises, or when a check does not hold: nothing here records an
error and carries on, and nothing substitutes a CPU device or a
reference implementation for the chip.  The last line of stdout is one
JSON object ``{"ok": true, "device": {...}}`` with the device as jax
reports it.  Times printed are host-clock times labelled with the
device; they are information, not benchmark metrics.

``--rehearse-cpu`` runs the same code at tiny sizes on the CPU, to find
bugs before spending chip time.  It says so on every line it can, and
its last line carries no ``"ok"``: a rehearsal is never a chip result.

The compile cache is wherever ``JAX_COMPILATION_CACHE_DIR`` says, else
``<checkout>/.jax_cache`` (paddle_tpu/compile_cache.py).
"""
import argparse
import collections
import importlib.metadata
import json
import os
import sys
import threading
import time

import numpy as np

# full = the widths above; tiny = the rehearsal.  Same keys, same code.
FULL = dict(
    vocab=30522, d_model=768, n_layer=12, n_head=12, d_inner=3072,
    train_seq=128, train_batch=128, chunk=8,
    max_pos=512, kv_len=256, slots=4, steps_per_tick=4,
    prompt_lens=(5, 17, 33, 64, 9, 48), new_tokens=16, ref_seq=128,
    # dim-0 fsdp sharding needs every sharded dim to divide the mesh
    # (PartitionRules.check_divisible); 30522 % 4 == 2, so the four-chip
    # LM pads its vocab to the next multiple of 128, as deployments do
    fsdp_vocab=30592, fsdp_batch=8, fsdp_seq=128, fsdp_steps=4,
)
TINY = dict(
    vocab=211, d_model=32, n_layer=2, n_head=4, d_inner=64,
    train_seq=16, train_batch=8, chunk=4,
    max_pos=64, kv_len=32, slots=4, steps_per_tick=2,
    prompt_lens=(3, 5, 9, 12, 4, 7), new_tokens=6, ref_seq=32,
    fsdp_vocab=212, fsdp_batch=8, fsdp_seq=16, fsdp_steps=4,
)

# Serve-leg tolerance.  The served step multiplies fp32 weights at the
# TPU's default matmul precision (bf16 passes, 8 mantissa bits per
# product) and so does every layer before it; the reference forward runs
# at precision "highest".  With random weights the top two of 30522
# logits sit ~0.05 apart on a range of ~2, so the argmax flips on
# rounding — tokens cannot be compared, logits can: each generated
# token's reference logit must lie within this share of that position's
# logit range (max - min) of the position's maximum.  At 2% only about
# two of 30522 random logits are that close to the top, so the check
# still pins the token to the top handful.  Measured on a v5e (PR 21):
# 96/96 generated tokens were the reference argmax itself, gap 0.0.
LOGIT_GAP_SHARE = 0.02

# Four-chip tolerance: one chip and fsdp-4 run the same fp32 program
# from the same seed on the same batches at the same matmul precision;
# fsdp only changes where operands live, so the losses differ by
# reduction order and fusion choices, not by the math.  Measured on a
# v5e 2x2: 5.9e-6 (PR 21); two orders of magnitude of room.
FSDP_LOSS_RTOL = 5e-4


class CompileWatch:
    """Counts XLA compiles and persistent-cache traffic through jax's own
    monitoring events — ground truth for 'nothing compiled in this
    window', stronger than the executor's jit-key accounting."""

    def __init__(self):
        import jax

        self.n = collections.Counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.n[event.rsplit("/", 1)[1]] += 1

    def _on_dur(self, event, secs, **_):
        # one event per executable built OR loaded from the disk cache
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["compiles"] += 1
            self.compile_s += secs

    def mark(self):
        return dict(self.n), self.compile_s

    def since(self, mark):
        n0, s0 = mark
        out = {k: self.n[k] - n0.get(k, 0)
               for k in ("compiles", "cache_hits", "cache_misses")}
        out["compile_s"] = round(self.compile_s - s0, 2)
        return out


def check(cond, msg):
    if not cond:
        raise AssertionError("check failed: " + msg)


def say(tag, **kv):
    print("%s %s" % (tag, json.dumps(kv, sort_keys=True, default=str)),
          flush=True)


def persistables_on(prog, scope, dev):
    """Every persistable of ``prog`` that ``scope`` holds must be a
    jax.Array living on ``dev`` and nowhere else."""
    import jax

    n = 0
    for v in prog.list_vars():
        if not v.persistable:
            continue
        val = scope.get(v.name)
        if val is None:
            continue  # feed/fetch holders: never materialized
        check(isinstance(val, jax.Array),
              "persistable %r is a %s, not a jax.Array"
              % (v.name, type(val).__name__))
        check(val.devices() == {dev},
              "persistable %r lives on %s, expected %s"
              % (v.name, val.devices(), dev))
        n += 1
    check(n > 0, "scope holds no persistables")
    return n


# ---------------------------------------------------------------------------
# train leg
# ---------------------------------------------------------------------------
def train_leg(cfg, place, watch):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import framework, models

    V, D, L, H, DI = (cfg[k] for k in (
        "vocab", "d_model", "n_layer", "n_head", "d_inner"))
    S, B, CH = cfg["train_seq"], cfg["train_batch"], cfg["chunk"]
    M = max(1, int(S * 0.15))
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 42
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [S], dtype="int64")
        sent = fluid.layers.data("sent", [S], dtype="int64")
        mask = fluid.layers.data("mask", [S])
        mpos = fluid.layers.data("mpos", [1], dtype="int64")
        mlab = fluid.layers.data("mlab", [1], dtype="int64")
        nlab = fluid.layers.data("nlab", [1], dtype="int64")
        total, _, _ = models.bert_pretrain(
            src, sent, mask, mpos, mlab, nlab,
            vocab_size=V, d_model=D, n_layer=L, n_head=H, d_inner=DI,
            seq_len=S, max_pos=max(S, cfg["max_pos"]), dropout_rate=0.0)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(1e-4))
        opt.minimize(total)

    # ONE batch, repeated: the loss must fall when the model sees the
    # same batch ten times.  The chunk still takes the per_step_feed
    # path (a leading steps axis, one slice per fori_loop iteration).
    rng = np.random.RandomState(0)
    one = {
        "src": rng.randint(0, V, (B, S)).astype(np.int32),
        "sent": rng.randint(0, 2, (B, S)).astype(np.int32),
        "mask": np.ones((B, S), np.float32),
        "mpos": (np.arange(B)[:, None] * S
                 + rng.randint(0, S, (B, M))).reshape(-1, 1).astype(np.int32),
        "mlab": rng.randint(0, V, (B * M, 1)).astype(np.int32),
        "nlab": rng.randint(0, 2, (B, 1)).astype(np.int32),
    }
    exe = fluid.Executor(place)
    dev = exe._device()  # raises when the place's backend is missing
    feed1 = {k: jax.device_put(v, dev) for k, v in one.items()}
    feedn = {k: jax.device_put(np.stack([v] * CH), dev)
             for k, v in one.items()}

    def step():
        (l,) = exe.run(prog, feed=feed1, fetch_list=[total],
                       return_numpy=False)
        return float(jax.block_until_ready(l))

    def chunk():
        (l,) = exe.run(prog, feed=feedn, fetch_list=[total],
                       return_numpy=False, steps=CH, per_step_feed=True)
        return float(jax.block_until_ready(l))

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        m0, t0 = watch.mark(), time.perf_counter()
        exe.run(startup)
        losses = [step(), step(), chunk()]
        setup_s = time.perf_counter() - t0
        setup = watch.since(m0)

        # the warmed window: the same three shapes again, nothing may
        # compile — neither a new jit key nor an XLA build behind one
        misses0, m1 = exe.jit_cache_stats()["misses"], watch.mark()
        t0 = time.perf_counter()
        losses.append(step())
        step_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        losses.append(chunk())
        chunk_s = time.perf_counter() - t0
        window = watch.since(m1)
        new_misses = exe.jit_cache_stats()["misses"] - misses0
        n_state = persistables_on(prog, scope, dev)

    check(all(np.isfinite(losses)), "non-finite loss in %r" % (losses,))
    check(losses[-1] < losses[0],
          "loss did not fall on a repeated batch: %r" % (losses,))
    check(new_misses == 0 and window["compiles"] == 0,
          "train leg compiled after warm-up: jit misses +%d, XLA "
          "compiles %d" % (new_misses, window["compiles"]))
    out = {
        "losses": [round(x, 6) for x in losses],
        "setup_wall_s": round(setup_s, 2),
        "setup_xla_compile_s": setup["compile_s"],
        "setup_compiles": setup["compiles"],
        "persistent_cache_hits": setup["cache_hits"],
        "persistent_cache_misses": setup["cache_misses"],
        "warm_single_step_host_s": round(step_s, 4),
        "warm_chunk_host_s_per_step": round(chunk_s / CH, 4),
        "chunk_steps": CH,
        "compiles_in_warm_window": window["compiles"],
        "persistables_on_device": n_state,
        "device": str(dev),
    }
    say("train_leg", **out)
    return out


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------
def serve_leg(cfg, place, watch):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import decoding, framework, models
    from paddle_tpu.serving.decode import DecodeServer

    V, D, L, H, DI = (cfg[k] for k in (
        "vocab", "d_model", "n_layer", "n_head", "d_inner"))
    SR, NEW = cfg["ref_seq"], cfg["new_tokens"]
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 7
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [SR], dtype="int64")
        _, logits = models.transformer.transformer_lm(
            src, None, vocab_size=V, d_model=D, n_layer=L, n_head=H,
            d_inner=DI, seq_len=SR, max_pos=cfg["max_pos"],
            dropout_rate=0.0, is_test=True)
    exe = fluid.Executor(place)
    dev = exe._device()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        persistables_on(prog, scope, dev)
        state = {v.name: scope.get(v.name) for v in prog.list_vars()
                 if v.persistable and scope.get(v.name) is not None}

    step_fn, make_cache = decoding.make_transformer_lm_pooled_step_fn(
        state, V, D, L, H, DI)
    # eos_id = V lies outside the vocabulary: no argmax can hit it, so
    # every request runs to its max_new_tokens whatever the random
    # weights prefer
    srv = DecodeServer(
        step_fn, make_cache, eos_id=V, max_seq_len=cfg["kv_len"],
        max_slots=cfg["slots"], slot_ladder=(cfg["slots"],),
        len_ladder=(cfg["kv_len"],),
        steps_per_tick=cfg["steps_per_tick"], name="smoke-lm")
    try:
        mem0 = dev.memory_stats() or {}
        m0, t0 = watch.mark(), time.perf_counter()
        warm_compiles = srv.warmup()
        warm_s = time.perf_counter() - t0
        warm = watch.since(m0)
        mem1 = dev.memory_stats() or {}

        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, V, n).astype(np.int32)
                   for n in cfg["prompt_lens"]]
        outs = [None] * len(prompts)
        errs = []

        def client(i):
            # even requests block on result(), odd ones drain stream():
            # both client surfaces, concurrently, more requests than
            # slots so admission and slot reuse are exercised
            try:
                req = srv.submit({"tokens": prompts[i]},
                                 max_new_tokens=NEW, timeout_ms=600e3)
                if i % 2:
                    got = np.concatenate([c for c in req.stream()])
                else:
                    (got,) = req.result()
                outs[i] = np.asarray(got, np.int32)
            except BaseException as e:  # re-raised on the main thread
                errs.append(e)

        m1, t0 = watch.mark(), time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        serve_s = time.perf_counter() - t0
        window = watch.since(m1)
        if errs:
            raise errs[0]
        metrics = srv.metrics()
    finally:
        srv.stop(drain=False)

    for i, got in enumerate(outs):
        check(got is not None and got.shape == (NEW,),
              "request %d returned %r, wanted %d tokens"
              % (i, None if got is None else got.shape, NEW))
        check(((0 <= got) & (got < V)).all(),
              "request %d produced out-of-vocabulary tokens" % i)
    check(metrics["recompiles"] == 0 and window["compiles"] == 0,
          "serve leg compiled after warmup: recompiles=%s, XLA compiles "
          "%d" % (metrics["recompiles"], window["compiles"]))

    # reference: ONE full-prefix forward of the Program over prompt +
    # generated tokens, at the highest matmul precision.  The weights go
    # in as an argument: closed over, jit would bake 0.5 GB of constants
    # into the executable (and into its compile-cache entry).
    ref_fn = jax.jit(lambda st, feeds: decoding.make_program_logits_fn(
        prog, st, ["src"], logits.name)(feeds))
    toks = np.zeros((len(prompts), SR), np.int32)
    for i, (p, g) in enumerate(zip(prompts, outs)):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + NEW] = g
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(ref_fn(state, {"src": jax.device_put(toks, dev)}))
    check(ref.shape == (len(prompts), SR, V) and np.isfinite(ref).all(),
          "reference logits: shape %s, finite=%s"
          % (ref.shape, bool(np.isfinite(ref).all())))
    worst, argmax_hits = 0.0, 0
    for i, (p, g) in enumerate(zip(prompts, outs)):
        for j, tok in enumerate(g):
            row = ref[i, len(p) + j - 1]  # predicts position len(p)+j
            share = float((row.max() - row[tok]) / (row.max() - row.min()))
            worst = max(worst, share)
            argmax_hits += int(row.argmax() == tok)
            check(share <= LOGIT_GAP_SHARE,
                  "request %d token %d (id %d): reference logit is %.4f "
                  "of the logit range below the maximum (> %.4f)"
                  % (i, j, tok, share, LOGIT_GAP_SHARE))
    n_tok = len(prompts) * NEW
    out = {
        "requests": len(prompts),
        "prompt_lens": list(cfg["prompt_lens"]),
        "new_tokens_each": NEW,
        "tokens": [g.tolist() for g in outs],
        "warmup_wall_s": round(warm_s, 2),
        "warmup_xla_compile_s": warm["compile_s"],
        "warmup_compiles": int(warm_compiles),
        "persistent_cache_hits": warm["cache_hits"],
        "persistent_cache_misses": warm["cache_misses"],
        "recompiles_after_warmup": int(metrics["recompiles"]),
        "serve_wall_s": round(serve_s, 3),
        # prefill is the decode step run once per prompt token, so the
        # wall divides over prefill + generated tokens alike
        "host_s_per_generated_token": round(serve_s / n_tok, 5),
        "argmax_agreement": "%d/%d" % (argmax_hits, n_tok),
        "worst_logit_gap_share": round(worst, 6),
        "logit_gap_share_allowed": LOGIT_GAP_SHARE,
        "hbm_bytes_in_use_before_warmup": mem0.get("bytes_in_use"),
        "hbm_bytes_in_use_after_warmup": mem1.get("bytes_in_use"),
        "device": str(dev),
    }
    say("serve_leg", **out)
    return out


# ---------------------------------------------------------------------------
# four-chip leg
# ---------------------------------------------------------------------------
def four_chip_leg(cfg, place):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import framework, memory, models, sharding

    V, D, L, H, DI = (cfg[k] for k in (
        "fsdp_vocab", "d_model", "n_layer", "n_head", "d_inner"))
    S, B, STEPS = cfg["fsdp_seq"], cfg["fsdp_batch"], cfg["fsdp_steps"]

    def build():
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 11
        with framework.program_guard(prog, startup):
            src = fluid.layers.data("src", [S], dtype="int64")
            lbl = fluid.layers.data("lbl", [S, 1], dtype="int64")
            loss, _ = models.transformer.transformer_lm(
                src, lbl, vocab_size=V, d_model=D, n_layer=L, n_head=H,
                d_inner=DI, seq_len=S, max_pos=cfg["max_pos"])
            opt = fluid.optimizer.AdamOptimizer(1e-4)
            opt.minimize(loss)
        return prog, startup, loss, opt

    rng = np.random.RandomState(5)
    feeds = []
    for _ in range(STEPS):
        toks = rng.randint(0, V, (B, S + 1))
        feeds.append({"src": toks[:, :-1].astype(np.int32),
                      "lbl": toks[:, 1:, None].astype(np.int32)})

    def train(target, startup, loss):
        exe = fluid.Executor(place)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [float(np.asarray(exe.run(
                target, feed=f, fetch_list=[loss])[0])) for f in feeds]
        return losses, scope

    prog, startup, loss, _ = build()
    t0 = time.perf_counter()
    one_chip, scope1 = train(prog, startup, loss)
    one_chip_s = time.perf_counter() - t0
    del scope1  # free the one-chip state before the sharded run

    prog, startup, loss, opt = build()
    compiled = sharding.sharded_train_program(
        prog, sharding.canonical_rules("transformer_lm", "fsdp"),
        optimizer=opt, mesh_axes={"fsdp": 4})
    t0 = time.perf_counter()
    fsdp, scope = train(compiled, startup, loss)
    fsdp_s = time.perf_counter() - t0

    check(all(np.isfinite(one_chip + fsdp)),
          "non-finite loss: one chip %r, fsdp-4 %r" % (one_chip, fsdp))
    rel = max(abs(a - b) / abs(a) for a, b in zip(one_chip, fsdp))
    check(rel <= FSDP_LOSS_RTOL,
          "fsdp-4 loss departs from one chip by %.2e (> %.0e): %r vs %r"
          % (rel, FSDP_LOSS_RTOL, fsdp, one_chip))
    names = [p.name for p in prog.global_block().all_parameters()]
    names += list(opt.accumulator_map())
    platform = jax.devices()[0].platform
    sharded = 0
    for n in names:
        v = scope.get(n)
        devs = v.sharding.device_set
        check(len(devs) == 4 and {d.platform for d in devs} == {platform},
              "%r is on %d device(s): %s" % (n, len(devs), devs))
        sharded += int(not v.sharding.is_fully_replicated)
    check(sharded > 0, "no parameter or moment is actually partitioned")
    mem = memory.device_memory_stats()
    in_use = [m["bytes_in_use"] for m in mem[:4]]
    if platform != "cpu":  # a CPU reports no memory stats
        check(all(b for b in in_use),
              "a chip holds no bytes after the sharded run: %r" % in_use)
    out = {
        "mesh": {"fsdp": 4},
        "mesh_devices": [str(d) for d in compiled._mesh.devices.flat],
        "one_chip_losses": [round(x, 6) for x in one_chip],
        "fsdp4_losses": [round(x, 6) for x in fsdp],
        "max_rel_loss_diff": float("%.3g" % rel),
        "loss_rtol_allowed": FSDP_LOSS_RTOL,
        "state_arrays_on_4_devices": len(names),
        "state_arrays_partitioned": sharded,
        "bytes_in_use_per_device": in_use,
        "one_chip_wall_s": round(one_chip_s, 2),
        "fsdp4_wall_s": round(fsdp_s, 2),
    }
    say("four_chip_leg", **out)
    return out


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="tiny sizes on the CPU, to debug before spending chip time; "
             "NOT a chip run and never reported as one")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # explicit, by argument only
        print("REHEARSAL: --rehearse-cpu given. This is NOT a chip run: "
              "tiny sizes on the CPU, and no time below is a device "
              "number.", flush=True)

    import jax

    import paddle_tpu as fluid
    from paddle_tpu import compile_cache, native

    cache_from_env = "JAX_COMPILATION_CACHE_DIR" in os.environ
    cache_dir = compile_cache.configure()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse_cpu and device["platform"] != "tpu":
        sys.stderr.write(
            "chip_smoke: no TPU found — jax.devices()[0] is %r "
            "(platform %r, JAX_PLATFORMS=%r). This script proves the "
            "path on the chip and does not fall back; run it where a TPU "
            "is attached (or pass --rehearse-cpu to debug at tiny sizes, "
            "which is not a chip run).\n"
            % (devs[0], device["platform"], os.environ.get("JAX_PLATFORMS")))
        return 2

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "not installed"

    say("header", rehearsal=args.rehearse_cpu, device=device,
        versions={p: version(p) for p in ("jax", "jaxlib", "libtpu")},
        python=sys.version.split()[0],
        native_available=native.native_available(),
        compile_cache_dir=cache_dir,
        compile_cache_placed_by=(
            "JAX_COMPILATION_CACHE_DIR" if cache_from_env
            else "default <checkout>/.jax_cache"))

    cfg = TINY if args.rehearse_cpu else FULL
    place = fluid.CPUPlace() if args.rehearse_cpu else fluid.TPUPlace(0)
    watch = CompileWatch()
    t0 = time.perf_counter()
    train_leg(cfg, place, watch)
    serve_leg(cfg, place, watch)
    if len(devs) >= 4:
        four_chip_leg(cfg, place)
    else:
        print("four_chip: skipped (n_devices=%d)" % len(devs), flush=True)
    say("total", wall_s=round(time.perf_counter() - t0, 1),
        xla_compile_s=round(watch.compile_s, 1),
        compiles=watch.n["compiles"],
        persistent_cache_hits=watch.n["cache_hits"],
        persistent_cache_misses=watch.n["cache_misses"])
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": True, "passed": True,
                          "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
