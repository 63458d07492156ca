"""The comparison that decides ``correct`` for ``bert_base`` has to fail
what it exists to catch.  At the configuration's full width, on the CPU,
with the program's own initial weights: the reference with every matrix
rounded to int8, and the reference with the padding mask moved by one
position, are both held to the bounds COMMITTED in the config's
``check.tensors`` and must land outside them; the unharmed forward must
land inside.  (On the chip the program's bf16 products add their own
gap beneath the same bounds: PERF.md section 4.)"""
import os

import numpy as np
import pytest

from benchmark.lib import harness, traffic

CONF = os.path.join(harness.BENCH, "configs", "bert_base.json")


@pytest.fixture(scope="module")
def setting():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    cfg = harness.load_config(CONF, rehearse=False)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    ref = harness.load_py(os.path.join(harness.ROOT, cfg["reference"]),
                          "reference_bert_base")
    mix = dict(traffic.load_mix("pretrain_s128"), steps_per_chunk=1,
               batch=int(cfg["check"]["sequences"]),
               min_len_share=float(cfg["check"]["min_len_share"]))
    batch = {k: jnp.asarray(v[0]) for k, v in traffic.train_batches(
        mix, 2 ** 31 + 7, int(cfg["vocab_size"])).items()}
    _, startup, test_prog, _ = fam.build(cfg, int(mix["seq_len"]))
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        weights = {p.name: jnp.asarray(scope.get(p.name))
                   for p in test_prog.all_parameters()}
    fwd = jax.jit(lambda w, b: ref.forward(
        w, b, int(cfg["num_hidden_layers"]),
        int(cfg["num_attention_heads"]), float(cfg["layer_norm_eps"])))
    want = {k: np.asarray(v) for k, v in fwd(weights, batch).items()}
    return cfg, ref, fwd, weights, batch, want


def verdict(cfg, ref, got, want):
    """As families/bert_pretrain.check_against_reference decides."""
    ok, found = True, {}
    for name, bounds in cfg["check"]["tensors"].items():
        nums = dict(zip(("rel_rms", "worst_gap_share"),
                        ref.gaps(got[name], want[name])))
        for key, allowed in bounds.items():
            found[name + "." + key] = (nums[key], allowed)
            ok = ok and nums[key] <= allowed
    return ok, found


def int8_rounded(a):
    import jax.numpy as jnp

    if a.ndim < 2:
        return a
    scale = jnp.abs(a).max() / 127.0
    return jnp.round(a / scale) * scale


def test_the_unharmed_forward_passes(setting):
    cfg, ref, fwd, weights, batch, want = setting
    ok, found = verdict(cfg, ref, want, want)
    assert ok, found
    assert batch["mask"].min() == 0.0  # the sample has padding to mask


def test_int8_rounded_weights_fail(setting):
    cfg, ref, fwd, weights, batch, want = setting
    got = fwd({k: int8_rounded(v) for k, v in weights.items()}, batch)
    ok, found = verdict(cfg, ref, got, want)
    assert not ok, found
    # by the steady number, in both large tensors, not by a lucky maximum
    for name in ("encoder_out", "mlm_logits"):
        value, allowed = found[name + ".rel_rms"]
        assert value > 1.2 * allowed, (name, value, allowed)


def test_a_corrupted_mask_fails(setting):
    import jax.numpy as jnp

    cfg, ref, fwd, weights, batch, want = setting
    moved = dict(batch, mask=jnp.roll(batch["mask"], 1, axis=1))
    ok, found = verdict(cfg, ref, fwd(weights, moved), want)
    assert not ok, found
    ignored = dict(batch, mask=jnp.ones_like(batch["mask"]))
    ok, found = verdict(cfg, ref, fwd(weights, ignored), want)
    assert not ok, found


def test_the_program_agrees_with_the_reference_on_the_cpu(setting):
    """The test clone (fp32 on the CPU) against the reference at the
    full width: far inside the chip's bounds, so what the chip adds is
    its arithmetic and nothing else."""
    import jax
    import paddle_tpu as fluid

    cfg, ref, fwd, weights, batch, want = setting
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    _, startup, test_prog, total = fam.build(cfg, batch["src"].shape[1])
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        names = fam.check_tensors(test_prog)
        block = test_prog.global_block()
        out = exe.run(test_prog, feed=dict(batch), return_numpy=False,
                      fetch_list=[block.var(names[k]) for k in sorted(names)])
        mine = {p.name: jax.numpy.asarray(scope.get(p.name))
                for p in test_prog.all_parameters()}
    got = {k: np.asarray(v).reshape(want[k].shape)
           for k, v in zip(sorted(names), out)}
    theirs = {k: np.asarray(v) for k, v in fwd(mine, batch).items()}
    for name in got:
        rel_rms, worst = ref.gaps(got[name], theirs[name])
        assert rel_rms < 1e-4 and worst < 1e-4, (name, rel_rms, worst)
