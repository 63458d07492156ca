"""Auxiliary subsystems: memory stats, io/fs shim, data_generator,
AsyncExecutor facade, dataset zoo additions (reference:
memory/allocation/allocator_facade.h stats, framework/io/fs.cc,
incubate/data_generator/__init__.py, async_executor.h,
python/paddle/dataset/{wmt16,movielens,flowers,voc2012}.py)."""
import io as _io
import os

import numpy as np

from conftest import WAIT

import paddle_tpu as fluid


def test_device_memory_stats_surface():
    stats = fluid.memory.device_memory_stats()
    assert stats and "bytes_in_use" in stats[0] and "platform" in stats[0]
    summary = fluid.memory.memory_summary()
    assert "device" in summary and "in_use" in summary


def test_io_fs_local_roundtrip(tmp_path):
    from paddle_tpu import io_fs as fs

    d = str(tmp_path / "x")
    fs.fs_mkdir(d)
    with fs.open_write(os.path.join(d, "a.txt")) as f:
        f.write("hello")
    assert fs.fs_exists(os.path.join(d, "a.txt"))
    assert fs.fs_ls(d) == [os.path.join(d, "a.txt")]
    with fs.open_read(os.path.join(d, "a.txt")) as f:
        assert f.read() == "hello"
    fs.fs_mv(os.path.join(d, "a.txt"), os.path.join(d, "b.txt"))
    assert not fs.fs_exists(os.path.join(d, "a.txt"))
    fs.fs_rm(d)
    assert not fs.fs_exists(d)
    assert fs.file_shard(["a", "b", "c", "d"], 0, 2) == ["a", "c"]

    # fs.cc surface extensions: tail / file_size / .gz converters /
    # hdfs command override (reference: fs.cc fs_tail, fs_file_size,
    # converters, hdfs_set_command)
    d2 = str(tmp_path / "y")
    fs.fs_mkdir(d2)
    p = os.path.join(d2, "log.txt")
    with fs.open_write(p) as f:
        f.write("first\nsecond\nlast\n")
    assert fs.fs_tail(p) == "last"
    assert fs.fs_file_size(p) == len("first\nsecond\nlast\n")
    gz = os.path.join(d2, "c.txt.gz")
    with fs.open_write(gz) as f:
        f.write("compressed body")
    with fs.open_read(gz) as f:
        assert f.read() == "compressed body"
    assert fs.fs_file_size(gz) == os.path.getsize(gz)
    import pytest as _pytest

    try:
        fs.set_hdfs_command("hadoop fs -Dfs.default.name=x")
        assert fs._HDFS_COMMAND[-1] == "-Dfs.default.name=x"
        with _pytest.raises(ValueError):
            fs.set_hdfs_command("")
    finally:
        fs.set_hdfs_command("hadoop fs")
    # raw=True bypasses the .gz converter (byte-for-byte download path)
    with fs.open_read(gz, "rb", raw=True) as f:
        assert f.read() == open(gz, "rb").read()


def test_data_generator_multislot_roundtrip():
    from paddle_tpu import native
    from paddle_tpu.incubate.data_generator import MultiSlotDataGenerator

    class Gen(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def r():
                toks = line.split()
                yield [("ids", [int(t) for t in toks[:-1]]),
                       ("label", [float(toks[-1])])]

            return r

    g = Gen()
    g.set_batch(2)
    buf = _io.StringIO()
    g.run_from_memory(["1 2 3 0.5", "4 5 1.0"], buf)
    n, slots = native.parse_multislot(buf.getvalue().encode(), 2)
    assert n == 2
    np.testing.assert_allclose(slots[0][0], [1, 2, 3, 4, 5])
    np.testing.assert_array_equal(slots[0][1], [3, 2])
    np.testing.assert_allclose(slots[1][0], [0.5, 1.0])


def test_dataset_zoo_shapes():
    from paddle_tpu.dataset import flowers, movielens, voc2012, wmt14, wmt16

    src, trg, trg_next = next(wmt16.train(size=4)())
    assert trg.shape[0] == trg_next.shape[0] == src.shape[0] + 1
    assert trg[0] == wmt16.BOS and trg_next[-1] == wmt16.EOS

    s14 = next(wmt14.train(size=2)())
    assert len(s14) == 3

    m = next(movielens.train(size=2)())
    assert len(m) == 8 and 1.0 <= m[7] <= 5.0

    img, label = next(flowers.train(size=2)())
    assert img.shape == (3, 224, 224) and 0 <= label < 102

    img, mask = next(voc2012.train(size=2)())
    assert img.shape[0] == 3 and mask.shape == img.shape[1:]
    assert mask.max() <= 20


def test_async_executor_facade(tmp_path):
    """AsyncExecutor.run trains over a MultiSlot filelist (reference:
    async_executor.h contract)."""
    from paddle_tpu import framework

    f = tmp_path / "part-0.txt"
    rng = np.random.RandomState(0)
    lines = []
    for _ in range(64):
        x = rng.rand(4)
        y = x.sum() * 0.5
        lines.append("4 " + " ".join("%.4f" % v for v in x) + " 1 %.4f" % y)
    f.write_text("\n".join(lines) + "\n")

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 12
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(x, 1), y)
        )
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)

    class Feed:
        slots = [x, y]

    exe = fluid.AsyncExecutor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        results = exe.run(prog, Feed(), [str(f)], fetch_list=[loss], scope=scope)
    assert results, "no batches ran"
    first = float(np.asarray(results[0][0]))
    last = float(np.asarray(results[-1][0]))
    assert last < first, (first, last)


def test_pass_framework_and_pattern_matcher():
    """Pass registry + PassManager + op-chain matcher (ir/pass.h:38 +
    GraphPatternDetector analogs); eager shape errors at append_op."""
    from paddle_tpu import framework
    from paddle_tpu.core import passes

    assert "amp_bf16" in passes.list_passes()
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [8])
        h = fluid.layers.fc(x, 4, act="relu", name="pm_fc")
        out = fluid.layers.fc(h, 2, name="pm_out")

    block = prog.global_block()
    # fc lowers to mul (+elementwise_add bias) + relu: match the chain
    chains = passes.match_chain(block, ["mul", "elementwise_add", "relu"])
    assert len(chains) == 1
    assert [op.type for op in chains[0]] == ["mul", "elementwise_add", "relu"]

    # amp pass through the manager == direct rewrite: fc weights cast in
    passes.PassManager(["amp_bf16"]).apply(prog)
    assert any(op.type == "cast" for op in block.ops)

    # prune pass returns a clone sliced to the target
    pruned = passes.apply_pass("prune_to_targets", prog, feeds=["x"], targets=[out.name])
    assert len(pruned.global_block().ops) <= len(block.ops)


def test_eager_shape_error_at_append_op():
    """A static-shape mismatch raises AT BUILD TIME with the op named
    (round-1 weakness #6: errors surfaced deep inside jax tracing)."""
    import pytest
    from paddle_tpu import framework

    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        block = prog.global_block()
        block.create_var(name="sa", shape=[3, 4], dtype="float32", is_data=True)
        block.create_var(name="sb", shape=[5, 6], dtype="float32", is_data=True)
        block.create_var(name="sc", shape=[3, 6], dtype="float32")
        with pytest.raises(ValueError, match="shape inference failed for op 'matmul'"):
            block.append_op(
                type="matmul",
                inputs={"X": ["sa"], "Y": ["sb"]},
                outputs={"Out": ["sc"]},
                attrs={"transpose_X": False, "transpose_Y": False, "alpha": 1.0},
            )


def test_trainer_factory_surface():
    """Trainer/DeviceWorker descriptor surface (trainer.h:38,
    device_worker.h:103 analogs)."""
    from paddle_tpu.trainer_desc import (
        DistMultiTrainer, DownpourSGD, Hogwild, MultiTrainer,
        PipelineTrainer, Section, TrainerFactory,
    )

    t = TrainerFactory().create_trainer()
    assert isinstance(t, MultiTrainer) and isinstance(t._worker, Hogwild)
    t2 = TrainerFactory().create_trainer(
        {"trainer": "DistMultiTrainer", "device_worker": "DownpourSGD"}
    )
    assert isinstance(t2, DistMultiTrainer) and isinstance(t2._worker, DownpourSGD)
    t3 = TrainerFactory().create_trainer(
        {"trainer": "PipelineTrainer", "device_worker": "Section"}
    )
    assert isinstance(t3, PipelineTrainer) and t3._worker.worker_kind == "Section"
    t3.set_fetch_var_and_info(["loss"], ["loss"], 10)
    t3.set_thread(4)


def test_trainer_desc_wired_into_train_from_dataset():
    """TrainerDesc is consumed: worker/program mismatch raises; fetch
    config defaults flow through."""
    import pytest
    from paddle_tpu import framework
    from paddle_tpu.trainer_desc import TrainerFactory

    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(x, 1), y)
        )
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    sec = TrainerFactory().create_trainer(
        {"trainer": "PipelineTrainer", "device_worker": "Section",
         "num_microbatches": 4}
    )
    assert sec._worker.num_microbatches == 4
    with pytest.raises(ValueError, match="Section worker"):
        exe.train_from_dataset(program=prog, dataset=[], trainer_desc=sec)
    dps = TrainerFactory().create_trainer({"device_worker": "DownpourSGD"})
    with pytest.raises(ValueError, match="DownpourSGD worker"):
        exe.train_from_dataset(program=prog, dataset=[], trainer_desc=dps)
    # Hogwild + fetch config defaults: runs the loop
    hog = TrainerFactory().create_trainer()
    hog.set_fetch_var_and_info([loss], ["loss"], 1)
    rng = np.random.RandomState(0)
    feed = [{"x": rng.rand(8, 4).astype("float32"),
             "y": rng.rand(8, 1).astype("float32")} for _ in range(3)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        out = exe.train_from_dataset(program=prog, dataset=feed,
                                     scope=scope, trainer_desc=hog)
    assert len(out) == 3


def test_executor_multi_step_parity():
    """run(steps=N) — one jitted fori_loop over N optimizer steps — must
    match N single-step run() calls exactly (the dispatch-amortizing path
    the ``bert_base`` cells train through; analog of the reference
    DeviceWorker multi-batch loop)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework

    def build():
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 7
        with framework.program_guard(prog, startup):
            x = fluid.layers.data("x", [4])
            y = fluid.layers.data("y", [1])
            h = fluid.layers.fc(x, size=8, act="relu")
            p = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(fluid.layers.square(p - y))
            fluid.optimizer.MomentumOptimizer(0.05, 0.9).minimize(loss)
        return prog, startup, loss

    rng = np.random.RandomState(0)
    feed = {
        "x": rng.randn(16, 4).astype(np.float32),
        "y": rng.randn(16, 1).astype(np.float32),
    }
    prog, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())

    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe.run(startup)
        for _ in range(6):
            (la,) = exe.run(prog, feed=feed, fetch_list=[loss])
    params_a = {
        p.name: np.asarray(scope_a.get(p.name)) for p in prog.all_parameters()
    }

    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe.run(startup)
        (lb,) = exe.run(prog, feed=feed, fetch_list=[loss], steps=6)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for n, want in params_a.items():
        np.testing.assert_allclose(
            np.asarray(scope_b.get(n)), want, rtol=1e-5, atol=1e-6, err_msg=n
        )


def test_executor_per_step_feed_parity():
    """run(steps=N, per_step_feed=True) feeds N *distinct* batches inside
    one jitted fori_loop (stacked leading axis + dynamic_index_in_dim) and
    must match N single-step run() calls on those same batches — the
    compiled analog of the reference's buffered reader
    (operators/reader/buffered_reader.cc)."""
    import pytest

    import paddle_tpu as fluid
    from paddle_tpu import framework

    def build():
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 7
        with framework.program_guard(prog, startup):
            x = fluid.layers.data("x", [4])
            y = fluid.layers.data("y", [1])
            h = fluid.layers.fc(x, size=8, act="relu")
            p = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(fluid.layers.square(p - y))
            fluid.optimizer.AdamOptimizer(0.05).minimize(loss)
        return prog, startup, loss

    rng = np.random.RandomState(1)
    xs = rng.randn(5, 16, 4).astype(np.float32)
    ys = rng.randn(5, 16, 1).astype(np.float32)
    prog, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())

    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a):
        exe.run(startup)
        for i in range(5):
            (la,) = exe.run(prog, feed={"x": xs[i], "y": ys[i]},
                            fetch_list=[loss])
    params_a = {
        p.name: np.asarray(scope_a.get(p.name)) for p in prog.all_parameters()
    }

    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b):
        exe.run(startup)
        (lb,) = exe.run(prog, feed={"x": xs, "y": ys}, fetch_list=[loss],
                        steps=5, per_step_feed=True)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for n, want in params_a.items():
        np.testing.assert_allclose(
            np.asarray(scope_b.get(n)), want, rtol=1e-5, atol=1e-6, err_msg=n
        )

    # a feed whose leading axis isn't `steps` is a loud error, not a
    # silent broadcast
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(ValueError, match="leading"):
            exe.run(prog, feed={"x": xs[0], "y": ys[0]}, fetch_list=[loss],
                    steps=5, per_step_feed=True)


def test_prune_late_writer_guard():
    """An op that writes a pruned param after its mask op raises instead
    of silently resurrecting pruned weights (ADVICE r2)."""
    import pytest

    from paddle_tpu import framework
    from paddle_tpu.contrib.slim import prune as slim_prune

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 4
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [6])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1, name="pr_fc", bias_attr=False,
                               param_attr=fluid.ParamAttr(name="pr_w"))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(4, 6).astype("float32"),
            "y": rng.randn(4, 1).astype("float32")}
    with fluid.scope_guard(scope):
        exe.run(startup)
        pruner = slim_prune.Pruner()
        pruner.prune(prog, scope, ["pr_w"], [0.5])
        exe.run(prog, feed=feed, fetch_list=[loss])  # fine
        # sneak in a late writer of the pruned param
        with framework.program_guard(prog, startup):
            blk = prog.global_block()
            blk.append_op(
                type="scale", inputs={"X": ["pr_w"]},
                outputs={"Out": ["pr_w"]}, attrs={"scale": 1.0},
            )
        with pytest.raises(RuntimeError, match="resurrect"):
            exe.run(prog, feed=feed, fetch_list=[loss])


def test_device_workers_carry_real_behavior():
    """Hogwild flips a dense-PS program to async rounds, DownpourSGD
    installs the async Communicator, and thread_num>1 prefetches batches
    on a background thread (VERDICT r2 weak #6: descriptors were
    configuration-theater)."""
    import threading

    from paddle_tpu import framework
    from paddle_tpu.trainer_desc import DownpourSGD, Hogwild, TrainerFactory

    # --- Hogwild on a sync dense-PS trainer program -> async
    class FakeProg:
        pass

    p = FakeProg()
    p._dense_ps_ctx = {"sync": True, "initialized": False}
    Hogwild()._prepare(p)
    assert p._dense_ps_ctx["sync"] is False
    p2 = FakeProg()
    p2._dense_ps_ctx = {"sync": True, "initialized": True}
    import pytest

    with pytest.raises(ValueError, match="sync_mode=False"):
        Hogwild()._prepare(p2)

    # --- DownpourSGD installs a Communicator from the bound client
    class FakeClient:
        def push_sparse(self, *a):
            pass

    p3 = FakeProg()
    p3._ps_client = FakeClient()
    DownpourSGD(max_merge=7)._prepare(p3)
    comm = p3._ps_communicator
    try:
        assert comm is not None and comm._max_merge == 7
    finally:
        comm.stop()

    # --- thread prefetch: batches produced on a different thread, all
    # consumed, order preserved
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [2])
        loss = fluid.layers.mean(fluid.layers.fc(x, 1))
    exe = fluid.Executor(fluid.CPUPlace())
    main_thread = threading.current_thread().name
    producer_threads = []

    def gen():
        for i in range(5):
            producer_threads.append(threading.current_thread().name)
            yield {"x": np.full((2, 2), float(i), "float32")}

    desc = TrainerFactory().create_trainer()
    desc.set_fetch_var_and_info([loss], ["loss"], 100)
    desc.set_thread(3)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        out = exe.train_from_dataset(program=prog, dataset=gen(),
                                     scope=scope, trainer_desc=desc)
    assert len(out) == 5
    assert all(t != main_thread for t in producer_threads)
    # deterministic order: loss is monotone in the fed constant
    vals = [float(np.asarray(o[0])) for o in out]
    diffs = np.diff(vals)
    assert (diffs > 0).all() or (diffs < 0).all(), vals


def test_unified_flags_tier():
    """gflags-style registry (VERDICT r2 partial #60): env > default,
    set_flags overrides AND mirrors to env so point-of-use os.environ
    reads agree; unknown flags raise."""
    import pytest

    from paddle_tpu import flags

    assert fluid.get_flags("check_nan_inf")["FLAGS_check_nan_inf"] is False
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        assert fluid.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"] is True
        assert os.environ["FLAGS_check_nan_inf"] == "1"  # point-of-use sync
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})
    with pytest.raises(KeyError):
        fluid.get_flags("no_such_flag")
    assert "XLA_PYTHON_CLIENT_MEM_FRACTION" in flags.flag_doc(
        "fraction_of_gpu_memory_to_use")
    # typed coercion from env strings
    os.environ["FLAGS_rpc_retry_times"] = "5"
    try:
        assert fluid.get_flags("rpc_retry_times")["FLAGS_rpc_retry_times"] == 5
    finally:
        del os.environ["FLAGS_rpc_retry_times"]


def test_contrib_tail_surface():
    """contrib modules (reference: contrib/ memory_usage_calc,
    op_frequence, model_stat, extend_optimizer, quantize, reader,
    layers, utils, decoder)."""
    import pytest

    from paddle_tpu import framework, reader as R

    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [8])
        out = fluid.layers.fc(fluid.layers.fc(x, 16, act="relu"), 4)
    lo, hi = fluid.contrib.memory_usage(prog, batch_size=32)
    assert 0 < lo < hi
    singles, pairs = fluid.contrib.op_freq_statistic(prog)
    assert singles["mul"] == 2 and pairs
    n, _ = fluid.contrib.summary(prog)
    assert n == 8 * 16 + 16 + 16 * 4 + 4

    # AdamW: with zero grads the decoupled decay shrinks params by
    # exactly lr*coeff*param
    from paddle_tpu.contrib.extend_optimizer import (
        extend_with_decoupled_weight_decay,
    )

    AdamW = extend_with_decoupled_weight_decay(fluid.optimizer.AdamOptimizer)
    p2, s2 = framework.Program(), framework.Program()
    p2.random_seed = s2.random_seed = 3
    with framework.program_guard(p2, s2):
        x = fluid.layers.data("x", [6])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1, bias_attr=False,
                               param_attr=fluid.ParamAttr(name="aw_w"))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        AdamW(weight_decay=0.1, learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(s2)
        w0 = np.asarray(sc.get("aw_w")).copy()
        exe.run(p2, feed={"x": np.zeros((4, 6), "float32"),
                          "y": np.zeros((4, 1), "float32")},
                fetch_list=[loss])
        w1 = np.asarray(sc.get("aw_w"))
    np.testing.assert_allclose(w1, w0 - 0.01 * 0.1 * w0, atol=1e-5)

    # reader decorators
    def rdr():
        for i in range(6):
            yield i

    assert list(R.xmap_readers(lambda v: v * 2, rdr, 2, 4, order=True)()) \
        == [0, 2, 4, 6, 8, 10]
    assert sorted(R.multiprocess_reader([rdr, rdr])()) == sorted(list(rdr()) * 2)

    # implemented in r5 (full tests: tests/test_contrib_decoder.py,
    # tests/test_amp_quant_inference.py::test_qat_freeze_*): here just
    # the import surface + loud argument validation
    assert callable(fluid.contrib.decoder.BeamSearchDecoder)
    with pytest.raises(ValueError, match="out_state"):
        fluid.contrib.decoder.StateCell(inputs={}, states={}, out_state="h")
    with pytest.raises(ValueError, match="no weight fake-quant"):
        # freezing a program that was never QAT-rewritten is a loud error
        fluid.contrib.quantize.QuantizeTranspiler().freeze_program(
            p2, scope=sc)


def test_contrib_trainer_inferencer_roundtrip(tmp_path):
    """The high-level Trainer/Inferencer API (reference:
    contrib/trainer.py:169 + contrib/inferencer.py:31): train with
    Begin/End Epoch/Step events, test(), save_params, then an
    Inferencer rebuilt from infer_func loads the params and predicts
    the trained function."""
    import numpy as np

    from paddle_tpu.contrib.trainer import (
        BeginEpochEvent, BeginStepEvent, EndEpochEvent, EndStepEvent,
        Inferencer, Trainer,
    )

    def net():
        x = fluid.layers.data("x", [4])
        pred = fluid.layers.fc(x, 1, param_attr=fluid.ParamAttr(name="tw"),
                               bias_attr=fluid.ParamAttr(name="tb"))
        return pred

    def train_func():
        pred = net()
        y = fluid.layers.data("y", [1])
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        return [loss]

    def optimizer_func():
        return fluid.optimizer.SGDOptimizer(0.1)

    rng = np.random.RandomState(0)

    def reader():
        for _ in range(8):
            xv = rng.uniform(-1, 1, (16, 4)).astype("float32")
            yv = xv.sum(1, keepdims=True).astype("float32") * 0.5
            yield (xv, yv)

    events = []
    losses = []

    def handler(ev):
        events.append(type(ev).__name__)
        if isinstance(ev, EndStepEvent):
            losses.append(float(np.asarray(ev.metrics[0])))

    trainer = Trainer(train_func, optimizer_func)
    trainer.train(num_epochs=2, event_handler=handler, reader=reader,
                  feed_order=["x", "y"])
    assert events[0] == "BeginEpochEvent" and events[-1] == "EndEpochEvent"
    assert events.count("BeginEpochEvent") == 2
    assert losses[-1] < losses[0]
    (test_loss,) = trainer.test(reader=reader, feed_order=["x", "y"])
    assert test_loss < losses[0]
    trainer.save_params(str(tmp_path / "params"))

    inf = Inferencer(net, str(tmp_path / "params"))
    xb = rng.uniform(-1, 1, (4, 4)).astype("float32")
    (got,) = inf.infer({"x": xb})
    np.testing.assert_allclose(
        np.asarray(got), xb.sum(1, keepdims=True) * 0.5,
        rtol=0.4, atol=0.25)  # trained approximation

    # stop() breaks the loop
    t2 = Trainer(train_func, optimizer_func)
    seen = []

    def stopper(ev):
        if isinstance(ev, BeginStepEvent):
            seen.append(ev.step)
            if ev.step >= 1:
                t2.stop()

    t2.train(num_epochs=5, event_handler=stopper, reader=reader,
             feed_order=["x", "y"])
    assert max(seen) <= 2


def test_contrib_trainer_checkpoint_rotation(tmp_path):
    """CheckpointConfig honors epoch_interval and rotates to
    max_num_checkpoints numbered snapshots (review r5); a feed_order/
    batch length mismatch errors immediately."""
    import pytest

    from paddle_tpu.contrib.trainer import CheckpointConfig, Trainer

    def train_func():
        x = fluid.layers.data("x", [3])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        return [fluid.layers.mean(fluid.layers.square_error_cost(pred, y))]

    rng = np.random.RandomState(1)

    def reader():
        for _ in range(3):
            xv = rng.uniform(-1, 1, (8, 3)).astype("float32")
            yield (xv, xv.sum(1, keepdims=True).astype("float32"))

    ckdir = str(tmp_path / "ck")
    t = Trainer(train_func, lambda: fluid.optimizer.SGDOptimizer(0.1),
                checkpoint_config=CheckpointConfig(
                    ckdir, max_num_checkpoints=2, epoch_interval=1,
                    step_interval=10 ** 9))
    t.train(num_epochs=4, event_handler=lambda ev: None, reader=reader,
            feed_order=["x", "y"])
    kept = sorted(os.listdir(ckdir))
    # 4 epoch saves, rotation keeps the last 2
    assert kept == ["checkpoint_2", "checkpoint_3"], kept

    def bad_reader():
        yield (np.zeros((4, 3), "float32"),)

    with pytest.raises(ValueError, match="feed_order has 2 names"):
        t.train(num_epochs=1, event_handler=lambda ev: None,
                reader=bad_reader, feed_order=["x", "y"])


def test_configure_compile_cache_subprocess_contract(tmp_path):
    """paddle_tpu.compile_cache.configure — the one definition of where
    the persistent XLA cache lives.  JAX_COMPILATION_CACHE_DIR set: jax
    keeps its cache there and the program sets NOTHING in code (jax's
    own env-backed config already holds it).  Unset: the cache goes to
    <checkout>/.jax_cache, exported for children.  Checked in fresh
    subprocesses so the session's own cache config is never disturbed."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = (
        "import os, sys, json\n"
        "sys.path.insert(0, %r)\n"
        "import jax\n"
        "from paddle_tpu import compile_cache\n"
        "calls = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), real(k, v))\n"
        "got = compile_cache.configure()\n"
        "again = compile_cache.configure()\n"
        "print(json.dumps({'ret': got, 'again': again, 'set_in_code': calls,\n"
        "  'env': os.environ.get('JAX_COMPILATION_CACHE_DIR'),\n"
        "  'cfg': jax.config.jax_compilation_cache_dir}))\n" % repo
    )

    def run(env_override):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        env.update(env_override)
        out = subprocess.run(
            [sys.executable, "-c", prog],
            env=env, capture_output=True, text=True, timeout=120, check=True)
        return json.loads(out.stdout.strip().splitlines()[-1])

    # env set -> that directory, and nothing set in code
    placed = str(tmp_path / "placed")
    got = run({"JAX_COMPILATION_CACHE_DIR": placed})
    assert got == {"ret": placed, "again": placed, "set_in_code": [],
                   "env": placed, "cfg": placed}
    # env unset -> <checkout>/.jax_cache through both channels (config
    # for this process, env for its children); a second call is a no-op
    want = os.path.join(repo, ".jax_cache")
    got = run({})
    assert got == {"ret": want, "again": want,
                   "set_in_code": ["jax_compilation_cache_dir"],
                   "env": want, "cfg": want}


def test_fleet_top_once_renders_a_live_fleet():
    """``tools/fleet_top.py --once`` against a REAL 2-child stub fleet's
    federated admin tier: one frame on stdout, exit code 0 — the
    operator console's CI smoke (PR 17)."""
    import sys
    import threading
    import time

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import fleet_top

    from paddle_tpu.monitor import slo as slo_mod
    from paddle_tpu.serving import wire
    from paddle_tpu.serving.server import InferenceServer

    class _Stub:
        def get_input_names(self):
            return ["x"]

        def get_output_names(self):
            return ["y"]

        def input_specs(self):
            return {"x": ((8,), np.dtype("float32"))}

        def jit_cache_stats(self):
            return {"entries": 0, "hits": 0, "misses": 0}

        def run_padded(self, feed, n_valid=None):
            return [np.asarray(feed["x"][:n_valid]).sum(
                axis=1, keepdims=True)]

    sps = []
    for i in range(2):
        srv = InferenceServer(_Stub(), max_batch_size=8,
                              batch_timeout_ms=1, name="top-%d" % i)
        sp = wire.ServingProcess(srv)
        sp.start()
        sps.append(sp)
    fleet = wire.FleetBalancer(
        [sp.address for sp in sps], name="topfleet",
        health_interval_s=0.2, admin_port=0, scrape_interval_s=0.1)
    eng = slo_mod.install(
        [slo_mod.availability("top-avail", good="wire_requests_total",
                              bad="wire_backend_retired_total",
                              target=0.999)],
        interval_s=0.05, window_scale=0.001)
    try:
        rng = np.random.RandomState(0)
        for _ in range(5):
            fleet.infer({"x": rng.rand(2, 8).astype("float32")})
        deadline = time.monotonic() + 5
        while eng._ticks == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        fleet.scrape_once()
        host, port = fleet.admin_address

        out = _io.StringIO()
        real = sys.stdout
        sys.stdout = out
        try:
            rc = fleet_top.main(
                ["%s:%d" % (host, port), "--once", "--no-color"])
        finally:
            sys.stdout = real
        frame = out.getvalue()
        assert rc == 0
        assert "topfleet" in frame and "BACKEND" in frame
        assert "2/2 alive" in frame
        assert "top-avail" in frame  # the SLO table rendered
        # a dead admin address exits 1, not a traceback
        assert fleet_top.main(
            ["127.0.0.1:1", "--once", "--no-color"]) == 1
    finally:
        slo_mod.uninstall()
        fleet.stop()
        for sp in sps:
            sp.stop()


def test_an_executor_finalized_inside_a_scrape_stops_nothing():
    """A collection may start on a thread that is summing the executors'
    counters under their lock (an allocation in the sum tips it), and a
    dead ``Executor``'s finalizer then runs on THAT thread: it must not
    wait for the lock (it did until PR 58, and stopped every later
    scrape and every ``Executor()`` of the process — the one tier-1
    failure of PR 57's run, 300 s in ``Executor.__init__`` of the test
    below).  The dead executor's counts are folded in once."""
    import gc
    import threading

    from paddle_tpu import executor as ex

    exe = fluid.Executor(fluid.CPUPlace())
    exe._bump("runs", 3)
    before = ex._sum_exec_stats("runs")
    done = threading.Event()

    box = [exe]
    del exe

    def finalized_under_the_lock():
        with ex._exec_stats_lock:
            box.clear()
            gc.collect()
        done.set()

    threading.Thread(target=finalized_under_the_lock, daemon=True).start()
    assert done.wait(WAIT), "the finalizer waits for a lock its thread holds"
    assert ex._sum_exec_stats("runs") == before
    fluid.Executor(fluid.CPUPlace())    # and the next one is not stopped
    assert ex._sum_exec_stats("runs") == before


def test_train_top_once_renders_a_live_training_run(tmp_path):
    """``tools/train_top.py --once`` against a REAL trainer's admin
    tier (phase bars, throughput, watchdog, step table), plus the
    offline ``--replay`` mode over the run's step log — the training
    console's CI smoke (PR 20)."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import train_top

    from paddle_tpu import framework

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 27
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [6])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    rng = np.random.RandomState(9)
    feeds = [
        {"x": rng.randn(4, 6).astype("float32"),
         "y": rng.randn(4, 1).astype("float32")}
        for _ in range(6)
    ]
    log = str(tmp_path / "steps.jsonl")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(
            program=prog, dataset=feeds, scope=scope, fetch_list=[loss],
            phase_ledger=True, watchdog=True, train_log=log)
    addr = exe.start_train_admin(port=0)
    try:
        out = _io.StringIO()
        real = sys.stdout
        sys.stdout = out
        try:
            rc = train_top.main(
                ["%s:%d" % addr, "--once", "--no-color"])
        finally:
            sys.stdout = real
        frame = out.getvalue()
        assert rc == 0
        assert "PHASE" in frame and "device_execute" in frame
        assert "WATCHDOG" in frame and "throughput" in frame
        assert "STEP" in frame  # the per-step table rendered

        # offline replay of the same run's step log, no server needed
        out = _io.StringIO()
        sys.stdout = out
        try:
            rc = train_top.main(["--replay", log, "--no-color"])
        finally:
            sys.stdout = real
        replay = out.getvalue()
        assert rc == 0
        assert "PHASE" in replay and "steps 6" in replay

        # a dead admin address exits 1, not a traceback
        assert train_top.main(
            ["127.0.0.1:1", "--once", "--no-color"]) == 1
    finally:
        exe.stop_train_admin()
