"""Child process for the SIGKILL-and-resume chaos drill.

Trains a tiny linear-regression program with checkpointing enabled and
prints one parseable ``batch <step>: {'loss': ...}`` line per step (the
executor's own debug stream).  Batches are a deterministic function of
the GLOBAL step index, so a resumed run regenerates exactly the batches
the killed run would have consumed — loss-trajectory continuity is then
a straight per-step comparison.

``--sharded``: the same drill through the SHARDED training path — the
model trains with Adam on an fsdp mesh via
``paddle_tpu.sharding.train`` rules (``--mesh N`` picks the axis size,
default 2), so the checkpoints under test are SHARD-wise (per-shard
files, no host gather) and resume must re-place every shard (moments
included) loss-exactly.  A resume on a DIFFERENT ``--mesh`` than the
killed run exercises the cross-mesh shard-exchange restore.

``--mesh-tables``: the drill through the mesh-resident SPARSE path —
an ``embedding(is_distributed=True)`` model bound via
``bind_mesh_tables`` (adagrad, so row moments checkpoint too); the
final ``ROWS <table> <sum> <abssum>`` line lets the driver pin
row-value parity against an uninterrupted run.

Driven by tests/chaos/test_chaos_training.py and
tests/chaos/test_chaos_sparse.py; not a test module.
"""
import argparse
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--sharded" in sys.argv or "--mesh-tables" in sys.argv:
    # the fsdp/mp mesh needs virtual CPU devices; must land in the env
    # before jax initializes its backend (imports below stay lazy) —
    # the same flag tests/conftest.py sets for in-process tests
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import framework  # noqa: E402

W_TRUE = np.array([[0.5], [-1.0], [2.0], [0.25]], np.float32)


def build_model(sharded=False, mesh=2):
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 17
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        if sharded:
            # Adam, not SGD: the sharded drill must checkpoint/restore
            # real optimizer moments shard-wise
            opt = fluid.optimizer.AdamOptimizer(0.05)
        else:
            opt = fluid.optimizer.SGDOptimizer(0.05)
        opt.minimize(loss)
    if not sharded:
        return prog, startup, loss
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import sharding
    from paddle_tpu.sharding.rules import PartitionRules

    compiled = sharding.sharded_train_program(
        prog, PartitionRules([(r".", P("fsdp"))], name="child/fsdp"),
        optimizer=opt, mesh_axes={"fsdp": int(mesh)})
    return compiled, startup, loss


MT_TABLE = "mt_tbl"
MT_VOCAB = 48
MT_DIM = 4


def build_mesh_table_model(mesh=2):
    """embedding(is_distributed=True) bound to a mesh-resident table
    (adagrad: the drill checkpoints/restores row MOMENTS too)."""
    from paddle_tpu import sharding
    from paddle_tpu.parallel import mesh as mesh_lib

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 29
    with framework.program_guard(prog, startup):
        ids = fluid.layers.data("ids", [1], dtype="int64")
        y = fluid.layers.data("y", [1])
        emb = fluid.layers.embedding(
            ids, [MT_VOCAB, MT_DIM], is_sparse=True, is_distributed=True,
            param_attr=fluid.ParamAttr(name=MT_TABLE))
        pred = fluid.layers.fc(emb, 1, name="head")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    compiled = fluid.CompiledProgram(prog).with_mesh(
        mesh_lib.make_mesh({"mp": int(mesh)}))
    runtime = sharding.bind_mesh_tables(
        compiled, optimizer="adagrad", lr=0.1, initializer="zeros")
    return compiled, startup, loss, runtime


def batches(n_steps, step_delay, mesh_tables=False):
    for i in range(n_steps):
        rng = np.random.RandomState(1000 + i)  # keyed by GLOBAL step
        if mesh_tables:
            feed = {
                "ids": rng.randint(0, MT_VOCAB, (8, 1)).astype("int64"),
                "y": rng.randn(8, 1).astype("float32"),
            }
        else:
            x = rng.uniform(-1, 1, (8, 4)).astype("float32")
            feed = {"x": x,
                    "y": (x @ W_TRUE
                          + 0.05 * rng.standard_normal((8, 1))).astype(
                              "float32")}
        if step_delay:
            time.sleep(step_delay)
        yield feed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--step-delay", type=float, default=0.0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--mesh", type=int, default=2)
    ap.add_argument("--mesh-tables", action="store_true")
    args = ap.parse_args()

    runtime = None
    if args.mesh_tables:
        prog, startup, loss, runtime = build_mesh_table_model(args.mesh)
    else:
        prog, startup, loss = build_model(sharded=args.sharded,
                                          mesh=args.mesh)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(
            program=prog,
            dataset=batches(args.steps, args.step_delay,
                            mesh_tables=args.mesh_tables),
            scope=scope,
            fetch_list=[loss], fetch_info=["loss"],
            debug=True, print_period=1,
            checkpoint_dir=args.run_dir,
            checkpoint_every=args.ckpt_every,
            resume_from=args.run_dir if args.resume else None,
            checkpoint_async=args.async_ckpt,
        )
        if args.resume:
            print("RESUMED_FROM %s" % exe.last_resume_step, flush=True)
    if runtime is not None:
        # row-value parity hook: the driver compares these against an
        # uninterrupted golden run's line
        rows = runtime.rows(MT_TABLE, np.arange(MT_VOCAB, dtype=np.int64))
        print("ROWS %s %.8e %.8e" % (
            MT_TABLE, float(rows.sum()), float(np.abs(rows).sum())),
            flush=True)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
