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
* ``flash``: lowers and runs ``fused_attention`` under
  ``PADDLE_TPU_FLASH_ATTENTION=1`` at BERT-base head shape and compares
  it with the einsum path.
* ``four_chip``: the host's topology against ``make_mesh``, the sp-4
  ring and a pp-2 ``PipelinePredictor`` at their tier-1 test sizes
  (placement and parity only — not a speed run), and four one-chip
  replicas of one endpoint in one ``InferenceServer``.
"""
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
# Both paths multiply at the TPU's default precision (bf16 passes); the
# outputs are softmax-weighted means of V (|v| ~ 1), so an elementwise
# error of a few 2^-8 is rounding, not a wrong kernel.
FLASH_ATOL = 2e-2


def cmd_flash():
    import jax
    import jax.numpy as jnp

    _require_tpu()
    from paddle_tpu.ops.nn_ops import fused_attention

    B, H, S, D = 16, 12, 1024, 64
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3))
    lens = rng.randint(S // 2, S + 1, B)
    mask = jnp.asarray((np.arange(S)[None, :] < lens[:, None])
                       .astype(np.float32))
    scale = 1.0 / np.sqrt(D)

    def run(flash, use_mask, causal, grad=False):
        os.environ["PADDLE_TPU_FLASH_ATTENTION"] = "1" if flash else "0"
        ins = {"Q": [q], "K": [k], "V": [v]}
        if use_mask:
            ins["Mask"] = [mask]

        def f(q_, k_, v_):
            out = fused_attention(
                dict(ins, Q=[q_], K=[k_], V=[v_]),
                {"causal": causal, "scale": scale})["Out"]
            return out

        fn = (jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * v), (0, 1, 2)))
              if grad else jax.jit(f))
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(q, k, v))  # the flag is read here
        return out, time.perf_counter() - t0

    for use_mask, causal in ((False, False), (False, True),
                             (True, False), (True, True)):
        ref, _ = run(False, use_mask, causal)
        got, first_s = run(True, use_mask, causal)
        ref, got = np.asarray(ref), np.asarray(got)
        if use_mask:
            # pad rows are garbage by construction in both paths
            keep = np.asarray(mask).astype(bool)[:, None, :, None]
            ref, got = ref * keep, got * keep
        err = float(np.abs(ref - got).max())
        say("flash/forward", mask=use_mask, causal=causal,
            max_abs_err=err, atol=FLASH_ATOL,
            compile_and_first_run_s=round(first_s, 2))
        check(np.isfinite(got).all() and err <= FLASH_ATOL,
              "flash vs einsum (mask=%s causal=%s): %g" % (use_mask, causal, err))
    gref, _ = run(False, False, True, grad=True)
    ggot, first_s = run(True, False, True, grad=True)
    errs = [float(np.abs(np.asarray(a) - np.asarray(b)).max()
                  / max(1e-6, float(np.abs(np.asarray(a)).max())))
            for a, b in zip(gref, ggot)]
    say("flash/backward", causal=True, max_rel_err_dq_dk_dv=errs,
        rtol=FLASH_ATOL, compile_and_first_run_s=round(first_s, 2))
    check(max(errs) <= FLASH_ATOL, "flash grads vs einsum grads: %r" % errs)
    os.environ.pop("PADDLE_TPU_FLASH_ATTENTION", None)


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
                n_head=4, d_inner=64, seq_len=SEQ, max_pos=2 * SEQ,
                fused_attention=True)
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
