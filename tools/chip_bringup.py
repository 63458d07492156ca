#!/usr/bin/env python
"""Bring-up checks a builder runs on the chip by hand (PR 21).

chip_smoke.py is the check the driver runs; these are the experiments
beside it, kept as code so the next session can repeat them.  Each
subcommand is ONE process on the chip host, prints one JSON line per
finding, and raises on a failed check.  None of them runs on a CPU.

    python tools/chip_bringup.py child      # one chip
    python tools/chip_bringup.py flash      # one chip
    python tools/chip_bringup.py four_chip  # a four-chip host

* ``child``: what happens when a process that has touched jax starts a
  child that needs the chip (the pattern the benches must not use).
* ``flash``: the microbenchmark behind ``fused_attention``'s lowering
  rule: the Pallas kernel pair, the op's XLA form and the four-op
  lowering (matmul, add, softmax, matmul under bf16 AMP), forward +
  backward in the model's own layout at the two BERT cells' shapes, each
  held to a float32 reference on padded sequences (pad QUERY rows
  included) with and without ``causal``.
* ``four_chip``: the host's topology against ``make_mesh``, the sp-4
  ring and a pp-2 ``PipelinePredictor`` at their tier-1 test sizes
  (placement and parity only — not a speed run), and four one-chip
  replicas of one endpoint in one ``InferenceServer``.
"""
import functools
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chip_smoke import check, say  # noqa: E402 — one print/assert form


def _require_tpu(n=1):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(
            "chip_bringup needs %d TPU device(s); jax reports %d x %r"
            % (n, len(devs), devs[0].platform))
    return devs


# ---------------------------------------------------------------------------
def cmd_child():
    """The parent initialises the TPU backend, then starts a child that
    asks for it.  Bounded: the child is killed after 60 s."""
    devs = _require_tpu()
    say("child/parent", holds=[str(d) for d in devs])
    probe = ("import jax; d = jax.devices(); "
             "print('CHILD_DEVICES', d[0].platform, len(d))")
    for label, env in (("inherit", {}), ("pinned_cpu", {"JAX_PLATFORMS": "cpu"})):
        t0 = time.perf_counter()
        try:
            p = subprocess.run(
                [sys.executable, "-c", probe], env=dict(os.environ, **env),
                capture_output=True, text=True, timeout=60)
            outcome = {"rc": p.returncode,
                       "stdout": p.stdout.strip()[-200:],
                       "stderr_tail": p.stderr.strip()[-400:]}
        except subprocess.TimeoutExpired:
            outcome = {"rc": None, "hung": True, "killed_after_s": 60}
        outcome["wall_s"] = round(time.perf_counter() - t0, 1)
        say("child/" + label, **outcome)


# ---------------------------------------------------------------------------
# bf16 operands, float32 scores: the context is a softmax-weighted mean
# of V (|v| ~ 0.5), so an elementwise gap of a few 2^-9 against the
# float32 reference is rounding, not a wrong kernel.
FLASH_ATOL = 1e-2
# the two BERT cells', then the longest the rule gives the kernel
FLASH_SHAPES = ((32, 12, 512, 64), (128, 12, 128, 64), (16, 12, 1024, 64),
                (8, 12, 2048, 64), (16, 6, 1024, 128))


def cmd_flash():
    import jax
    import jax.numpy as jnp

    _require_tpu()
    from paddle_tpu import fused_attention as fa

    bf = jnp.bfloat16

    def heads(x, n_head):  # [N, S, H*D] -> [N, H, S, D], as the model does
        n, s, hd = x.shape
        return x.reshape(n, s, n_head, hd // n_head).transpose(0, 2, 1, 3)

    def merge(x):
        n, h, s, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(n, s, h * d)

    def four_op(q, k, v, mask, causal, scale):
        s = (jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale).astype(bf)
        s = s + fa._bias(mask, causal, q.shape[2], k.shape[2]).astype(bf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(bf), v)

    def reference(q, k, v, mask, causal, scale):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
        s = s + fa._bias(mask, causal, q.shape[2], k.shape[2])
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest")

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
    def kernel(q, k, v, mask, causal, scale):
        return fa.kernel_attention(q, k, v, mask, causal, scale)[0]

    def kernel_fwd(q, k, v, mask, causal, scale):
        out, lse = fa.kernel_attention(q, k, v, mask, causal, scale)
        return out, (q, k, v, mask, out, lse)

    def kernel_bwd(causal, scale, res, dout):
        q, k, v, mask, out, lse = res
        return fa.kernel_attention_grad(
            q, k, v, mask, out, lse, dout, causal, scale) + (None,)

    kernel.defvjp(kernel_fwd, kernel_bwd)
    forms = {"kernel": kernel, "four_op": four_op, "reference": reference,
             "xla": lambda *a: fa.xla_attention(*a)[0]}

    def step(form, n_head, causal, scale):
        def f(xq, xk, xv, mask, dctx):
            ctx, vjp = jax.vjp(
                lambda a, b, c: merge(forms[form](
                    heads(a, n_head), heads(b, n_head), heads(c, n_head),
                    mask, causal, scale)), xq, xk, xv)
            return (ctx,) + vjp(dctx.astype(ctx.dtype))
        return jax.jit(f)

    def timed(fn, args, iters=20):
        out = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3, out

    for n, n_head, s, d in FLASH_SHAPES:
        rng = np.random.RandomState(0)
        args = [jnp.asarray(rng.randn(n, s, n_head * d) * 0.5, bf)
                for _ in range(3)]
        dctx = jnp.asarray(rng.randn(n, s, n_head * d) * 0.5, bf)
        lens = rng.randint(s // 2, s + 1, n)
        mask = jnp.asarray((np.arange(s)[None, :] < lens[:, None])
                           .astype(np.float32))
        scale = 1.0 / np.sqrt(d)
        say("flash/rule", shape=[n, n_head, s, d],
            lowering=fa.attention_lowering("tpu", s, s, n_head, d, bf))
        for causal in (False, True):
            f32 = [x.astype(jnp.float32) for x in args]
            want = step("reference", n_head, causal, scale)(
                *f32, mask, dctx.astype(jnp.float32))
            for form in ("kernel", "xla", "four_op"):
                ms, got = timed(step(form, n_head, causal, scale),
                                (*args, mask, dctx))
                # every position, pad QUERY rows included
                errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                              / max(1.0, float(jnp.max(jnp.abs(b)))))
                        for a, b in zip(got, want)]
                say("flash/" + form, shape=[n, n_head, s, d], causal=causal,
                    fwd_bwd_ms=ms, gap_ctx_dq_dk_dv=errs, atol=FLASH_ATOL)
                check(max(errs) <= FLASH_ATOL,
                      "%s vs float32 reference at %r causal=%s: %r"
                      % (form, (n, n_head, s, d), causal, errs))


# ---------------------------------------------------------------------------
# sp ring / pp pipeline vs the unsharded predictor: same fp32 weights and
# inputs, both at the TPU's default matmul precision, but the ring sums
# attention blockwise and the pipeline splits the batch, so products are
# rounded in another order.  Logits of this random D=32 LM are O(1).
# Measured on a v5e 2x2 (PR 21): sp-4 1.65e-2 (thin), pp-2 and the
# replicas 0.0.
PARITY_ATOL = 2e-2
# ...and with fp32 products (precision "highest") only summation order
# differs: the tier-1 tests' own bound for this comparison on the CPU.
# Measured: 1.2e-6 — the 1.65e-2 above is rounding, not the ring.
EXACT_ATOL = 2e-4


def cmd_four_chip():
    import jax

    devs = _require_tpu(4)
    import paddle_tpu as fluid
    from paddle_tpu import framework, memory, models, serving, sharding
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.parallel.pipeline_predictor import PipelinePredictor

    # --- topology: what make_mesh builds against the flat id order
    say("four_chip/topology", devices=[
        {"id": d.id, "coords": getattr(d, "coords", None),
         "core_on_chip": getattr(d, "core_on_chip", None)} for d in devs])
    ring = mesh_lib.make_mesh({"sp": 4})
    grid = mesh_lib.make_mesh({"dp": 2, "tp": 2})
    say("four_chip/make_mesh",
        flat_ids=[d.id for d in devs[:4]],
        ring_ids=[d.id for d in ring.devices.flat],
        ring_coords=[getattr(d, "coords", None) for d in ring.devices.flat],
        grid_ids=[[d.id for d in row] for row in grid.devices])

    SEQ, VOCAB, D = 32, 64, 32  # tests/test_long_context.py sizes

    def save_lm(dirname, sp_n=0):
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 19
        with framework.program_guard(prog, startup):
            ids = fluid.layers.data("src_ids", [SEQ], dtype="int64")
            _, logits = models.transformer_lm(
                ids, None, vocab_size=VOCAB, d_model=D, n_layer=2,
                n_head=4, d_inner=64, seq_len=SEQ, max_pos=2 * SEQ)
        exe = fluid.Executor(fluid.TPUPlace(0))
        kw = {}
        if sp_n > 1:
            kw = dict(sharding_rules=sharding.transformer_lm_rules("sp"),
                      sharding_mesh={"sp": sp_n})
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.save_inference_model(dirname, ["src_ids"], [logits], exe,
                                       prog, **kw)
        return dirname

    x = np.random.RandomState(3).randint(1, VOCAB, (4, SEQ)).astype(np.int64)
    with tempfile.TemporaryDirectory() as tmp:
        plain = save_lm(os.path.join(tmp, "plain"))
        sp4 = save_lm(os.path.join(tmp, "sp4"), sp_n=4)

        def on_chip(i):
            cfg = AnalysisConfig(plain)
            cfg.enable_use_gpu(device_id=i)
            return create_paddle_predictor(cfg)

        ref = on_chip(0)
        out_r, = ref.run({"src_ids": x})
        out_r = np.asarray(out_r)

        # --- (b) sp-4 ring attention over real ICI
        sp = create_paddle_predictor(AnalysisConfig(sp4))
        check(sp.sharded, "sp manifest did not build a sharded group")
        out_s, = sp.run({"src_ids": x})
        err = float(np.abs(np.asarray(out_s) - out_r).max())
        stats = sp.sharding_stats()
        say("four_chip/sp4_ring", max_abs_err=err, atol=PARITY_ATOL,
            mesh_axes=stats["mesh_axes"],
            mesh_device_ids=[d.id for d in sp._compiled._mesh.devices.flat],
            activation_bytes_unsharded=stats["activation_bytes_unsharded"],
            activation_bytes_per_device=stats["activation_bytes_per_device"])
        check(err <= PARITY_ATOL, "sp-4 logits vs one chip: %g" % err)
        # the same pair with exact fp32 products: what is left is the
        # ring's own arithmetic, not bf16 rounding in another order
        with jax.default_matmul_precision("highest"):
            exact_s, = sp.run({"src_ids": x})
            exact_r, = ref.run({"src_ids": x})
        err_exact = float(np.abs(np.asarray(exact_s)
                                 - np.asarray(exact_r)).max())
        say("four_chip/sp4_ring_highest_precision", max_abs_err=err_exact,
            atol=EXACT_ATOL)
        check(err_exact <= EXACT_ATOL,
              "sp-4 logits vs one chip at precision=highest: %g" % err_exact)
        check(len({d.id for d in sp._compiled._mesh.devices.flat}) == 4,
              "sp mesh does not span four distinct chips")
        check(stats["activation_bytes_per_device"] * 4
              == stats["activation_bytes_unsharded"],
              "sp activations are not 1/4 per device: %r" % stats)

        # --- (b) pp-2 pipeline predictor, micro-batched
        pipe = PipelinePredictor(plain, n_stages=2, num_microbatches=4)
        out_p, = pipe.run({"src_ids": x})
        err = float(np.abs(np.asarray(out_p) - out_r).max())
        pst = pipe.pipeline_stats()
        pp_ids = [d.id for d in pipe._mesh.devices.flat]
        say("four_chip/pp2_pipeline", max_abs_err=err, atol=PARITY_ATOL,
            mesh_device_ids=pp_ids, n_stages=pst["n_stages"],
            schedule_slots=pst["schedule_slots"],
            bubble_ratio_analytic=pst["bubble_ratio"])
        check(err <= PARITY_ATOL, "pp-2 logits vs one chip: %g" % err)
        check(len(set(pp_ids)) == 2, "pp mesh is not two distinct chips")

        # --- (c) four one-chip replicas in ONE InferenceServer
        preds = [on_chip(i) for i in range(4)]
        server = serving.InferenceServer(
            preds, max_batch_size=4, batch_timeout_ms=1.0, name="four")
        try:
            compiles = server.warmup()
            client = serving.Client(server)
            outs = [None] * 32

            def one(i):
                outs[i], = client.infer({"src_ids": x[i % 4:i % 4 + 1]})

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(len(outs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            m = server.metrics()
        finally:
            server.stop(drain=False)
        homes = []
        for i, p in enumerate(preds):
            where = set()
            for v in p._program.list_vars():
                val = p._scope.get(v.name) if v.persistable else None
                if val is not None:
                    check(isinstance(val, jax.Array),
                          "replica %d holds %r as %s"
                          % (i, v.name, type(val).__name__))
                    where |= val.devices()
            check(where == {devs[i]},
                  "replica %d params live on %s, expected %s"
                  % (i, where, devs[i]))
            homes.append(str(devs[i]))
        err = max(float(np.abs(np.asarray(o)[0] - out_r[i % 4]).max())
                  for i, o in enumerate(outs))
        executed = {k: r.get("executed") for k, r in m["replicas"].items()}
        say("four_chip/replicas", param_homes=homes, warmup_compiles=compiles,
            recompiles=m["recompiles"], completed=m["completed"],
            executed_per_replica=executed, max_abs_err=err,
            atol=PARITY_ATOL)
        check(err <= PARITY_ATOL and m["recompiles"] == 0
              and m["completed"] == len(outs),
              "replica storm: err %g, metrics %r" % (err, m))
    in_use = [s["bytes_in_use"] for s in memory.device_memory_stats()[:4]]
    say("four_chip/memory", bytes_in_use_per_device=in_use)
    check(all(b for b in in_use), "a chip holds no bytes: %r" % in_use)


if __name__ == "__main__":
    cmds = {"child": cmd_child, "flash": cmd_flash, "four_chip": cmd_four_chip}
    if len(sys.argv) != 2 or sys.argv[1] not in cmds:
        raise SystemExit(__doc__)
    cmds[sys.argv[1]]()
    print("chip_bringup %s: ok" % sys.argv[1], flush=True)
