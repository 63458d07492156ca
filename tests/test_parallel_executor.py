"""Data-parallel CompiledProgram tests on the 8-device virtual CPU mesh.

Reference test style: python/paddle/fluid/tests/unittests/test_dist_base.py
— the assertion is *loss parity*: data-parallel losses must match
single-process losses within delta (test_dist_base.py:432).  Here both runs
happen in-process: GSPMD sharding replaces the subprocess NCCL cluster.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework


def _build_mlp(seed):
    prog = framework.Program()
    startup = framework.Program()
    prog.random_seed = seed
    startup.random_seed = seed
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", shape=[16], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = fluid.optimizer.SGDOptimizer(learning_rate=0.1)
        opt.minimize(loss)
    return prog, startup, loss


def _train(compiled, prog, startup, loss, steps=5, batch=32):
    rng = np.random.RandomState(7)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(steps):
            xb = rng.uniform(-1, 1, (batch, 16)).astype("float32")
            yb = (xb.sum(axis=1, keepdims=True) * 0.5).astype("float32")
            target = compiled if compiled is not None else prog
            (l,) = exe.run(target, feed={"x": xb, "y": yb}, fetch_list=[loss])
            losses.append(float(np.asarray(l)))
    return losses


def test_data_parallel_loss_parity():
    import jax

    if len(fluid.parallel.mesh.local_devices()) < 2:
        pytest.skip("needs multi-device mesh")
    prog, startup, loss = _build_mlp(seed=5)
    single = _train(None, prog, startup, loss)

    prog2, startup2, loss2 = _build_mlp(seed=5)
    compiled = fluid.CompiledProgram(prog2).with_data_parallel(loss_name=loss2.name)
    par = _train(compiled, prog2, startup2, loss2)

    assert single[0] > single[-1]  # actually learning
    np.testing.assert_allclose(single, par, rtol=1e-4, atol=1e-5)


def test_tensor_parallel_sharding_specs():
    """Column-parallel fc weight over a tp axis still matches replicated run."""
    import jax

    if len(fluid.parallel.mesh.local_devices()) < 4:
        pytest.skip("needs >=4 devices")
    prog, startup, loss = _build_mlp(seed=9)
    single = _train(None, prog, startup, loss)

    prog2, startup2, loss2 = _build_mlp(seed=9)
    # find the first fc weight (16x32) and shard its output dim over tp
    wname = [p.name for p in prog2.all_parameters() if tuple(p.shape) == (16, 32)][0]
    strat = fluid.DistributedStrategy()
    strat.mesh_axes = {"dp": 2, "tp": 2}
    strat.sharding_specs = {wname: (None, "tp")}
    compiled = fluid.CompiledProgram(prog2).with_strategy(strat)
    par = _train(compiled, prog2, startup2, loss2)
    np.testing.assert_allclose(single, par, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_sequence_sharded_transformer_program_parity():
    """Program-level sequence/context parallelism via GSPMD: the token
    feeds shard over an 'sp' mesh axis (DistributedStrategy.sharding_specs
    on the FEED vars), XLA inserts the attention collectives, and the
    loss matches the single-device run — the fluid-path long-context
    story (SURVEY §5; the hybrid engine's ring attention is the
    shard_map variant of the same design)."""
    import jax

    from paddle_tpu import models

    if len(fluid.parallel.mesh.local_devices()) < 4:
        pytest.skip("needs >=4 devices")
    V, S, B = 32, 16, 4

    def build(seed):
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = seed
        with framework.program_guard(prog, startup):
            src = fluid.layers.data("src", [S], dtype="int64")
            tgt = fluid.layers.data("tgt", [S, 1], dtype="int64")
            loss, _ = models.transformer.transformer_lm(
                src, tgt, vocab_size=V, d_model=16, n_layer=2, n_head=2,
                d_inner=32, seq_len=S, max_pos=S)
            fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
        return prog, startup, loss

    def train(target, startup, loss, steps=3):
        rng = np.random.RandomState(4)
        exe = fluid.Executor(fluid.CPUPlace())
        out = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(steps):
                toks = rng.randint(0, V, (B, S + 1))
                feed = {"src": toks[:, :-1].astype("int64"),
                        "tgt": toks[:, 1:, None].astype("int64")}
                (l,) = exe.run(target, feed=feed, fetch_list=[loss])
                out.append(float(np.asarray(l)))
        return out

    prog, startup, loss = build(21)
    single = train(prog, startup, loss)

    prog2, startup2, loss2 = build(21)
    strat = fluid.DistributedStrategy()
    strat.mesh_axes = {"dp": 2, "sp": 2}
    # tokens [B, S] shard batch over dp AND sequence over sp; labels too
    strat.sharding_specs = {"src": ("dp", "sp"), "tgt": ("dp", "sp", None)}
    compiled = fluid.CompiledProgram(prog2).with_strategy(strat)
    par = train(compiled, startup2, loss2)
    np.testing.assert_allclose(par, single, rtol=2e-4)


def test_batch_norm_under_data_parallel_and_sync():
    """BN under dp sharding: per-shard stats by default (ParallelExecutor
    per-device BN), GLOBAL batch stats with sync=True — parity vs the
    full-batch single-device run (round-1 weakness #9; reference:
    sync_batch_norm_op.cu)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.core import lowering
    from paddle_tpu.parallel import env as penv

    devs = jax.devices("cpu")
    if len(devs) < 4:
        pytest.skip("needs 4 devices")

    B, C, H, W = 16, 4, 3, 3

    def build(sync):
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 19
        with framework.program_guard(prog, startup):
            x = fluid.layers.data("x", [C, H, W])
            y = fluid.layers.data("y", [1])
            h = fluid.layers.batch_norm(x, act="relu", sync=sync)
            pool = fluid.layers.pool2d(h, pool_type="avg", global_pooling=True)
            pred = fluid.layers.fc(pool, 1, name="bn_head")
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        return prog, startup, loss

    rng = np.random.RandomState(6)
    xb = (rng.randn(B, C, H, W) * np.arange(1, C + 1).reshape(1, C, 1, 1)).astype("float32")
    yb = rng.randn(B, 1).astype("float32")

    # single-device full batch
    prog, startup, loss = build(False)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (l_single,) = exe.run(prog, feed={"x": xb, "y": yb}, fetch_list=[loss])
    l_single = float(np.asarray(l_single))

    def run_sharded(sync):
        prog, startup, loss = build(sync)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            persist = {
                v.name: scope.get(v.name)
                for v in prog.list_vars()
                if v.persistable and scope.get(v.name) is not None
            }
        fn = lowering.lower_block(prog.global_block(), ["x", "y"], [loss.name], [])
        mesh = Mesh(np.array(devs[:4]), ("dp",))
        penv.set_ring_axis(0, "dp")

        def step(state, xs, ys):
            with penv.active_axes(["dp"]):
                fetches, _ = fn(dict(state), {"x": xs, "y": ys})
            return jax.lax.pmean(fetches[0], "dp")

        sharded = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(), P("dp"), P("dp")), out_specs=P(),
            check_vma=False,
        ))
        return float(np.asarray(sharded(persist, xb, yb)))

    l_sync = run_sharded(True)
    l_local = run_sharded(False)
    # sync BN == full-batch stats: exact parity with single device
    np.testing.assert_allclose(l_sync, l_single, rtol=1e-5)
    # per-shard BN differs (different normalization statistics)
    assert abs(l_local - l_single) > 1e-6
