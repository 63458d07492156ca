"""End-to-end model tests — the reference's "book" test style
(python/paddle/fluid/tests/book/): build a real model, train a few steps
on synthetic data, assert the loss decreases.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, models


def _train_steps(build_fn, feeds_fn, steps=4, lr=0.01, opt=None, seed=3):
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = seed
    startup.random_seed = seed
    with framework.program_guard(prog, startup):
        loss = build_fn()
        (opt or fluid.optimizer.AdamOptimizer(learning_rate=lr)).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(seed)
    feed = feeds_fn(rng)  # one fixed batch: the model must be able to memorize it
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(steps):
            (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(l)))
    return losses


def test_lenet_mnist_trains():
    def build():
        img = fluid.layers.data("img", [1, 28, 28])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, acc, _ = models.lenet5(img, lbl)
        return avg_loss

    def feeds(rng):
        return {
            "img": rng.uniform(-1, 1, (16, 1, 28, 28)).astype("float32"),
            "lbl": rng.randint(0, 10, (16, 1)).astype("int64"),
        }

    losses = _train_steps(build, feeds, steps=6, lr=0.001)
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_resnet18_tiny_trains():
    def build():
        img = fluid.layers.data("img", [3, 32, 32])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, acc, _ = models.resnet.resnet18(img, lbl, class_num=10)
        return avg_loss

    def feeds(rng):
        return {
            "img": rng.uniform(-1, 1, (8, 3, 32, 32)).astype("float32"),
            "lbl": rng.randint(0, 10, (8, 1)).astype("int64"),
        }

    losses = _train_steps(build, feeds, steps=4, lr=0.001)
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_transformer_lm_trains():
    V, S = 100, 16

    def build():
        src = fluid.layers.data("src", [S], dtype="int64")
        tgt = fluid.layers.data("tgt", [S, 1], dtype="int64")
        avg_loss, _ = models.transformer.transformer_lm(
            src, tgt, vocab_size=V, d_model=32, n_layer=2, n_head=4,
            d_inner=64, seq_len=S, max_pos=S,
        )
        return avg_loss

    def feeds(rng):
        toks = rng.randint(0, V, (4, S + 1))
        return {
            "src": toks[:, :-1].astype("int64"),
            "tgt": toks[:, 1:, None].astype("int64"),
        }

    losses = _train_steps(build, feeds, steps=5, lr=0.01)
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_bert_encoder_shapes():
    S = 16

    def build():
        src = fluid.layers.data("src", [S], dtype="int64")
        mask = fluid.layers.data("mask", [S], dtype="float32")
        seq = models.transformer.bert_encoder(
            src, input_mask=mask, vocab_size=50, d_model=32, n_layer=2,
            n_head=4, d_inner=64, max_pos=S, seq_len=S,
        )
        pooled = fluid.layers.reduce_mean(seq, dim=[1])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        logits = fluid.layers.fc(pooled, size=2, act="softmax")
        return fluid.layers.mean(fluid.layers.cross_entropy(logits, lbl))

    def feeds(rng):
        return {
            "src": rng.randint(0, 50, (4, S)).astype("int64"),
            "mask": np.ones((4, S), dtype="float32"),
            "lbl": rng.randint(0, 2, (4, 1)).astype("int64"),
        }

    losses = _train_steps(build, feeds, steps=4)
    assert losses[-1] < losses[0], losses


def test_word2vec_trains():
    V = 50

    def build():
        ws = [fluid.layers.data("w%d" % i, [1], dtype="int64") for i in range(4)]
        nxt = fluid.layers.data("next", [1], dtype="int64")
        avg_loss, _ = models.word2vec.word2vec_ngram(ws, nxt, dict_size=V, embed_size=8, hidden_size=32)
        return avg_loss

    def feeds(rng):
        d = {"w%d" % i: rng.randint(0, V, (16, 1)).astype("int64") for i in range(4)}
        d["next"] = rng.randint(0, V, (16, 1)).astype("int64")
        return d

    losses = _train_steps(build, feeds, steps=6, lr=0.05)
    assert losses[-1] < losses[0], losses


def test_deepfm_trains():
    F, NF = 8, 200

    def build():
        ids = fluid.layers.data("ids", [F, 1], dtype="int64")
        vals = fluid.layers.data("vals", [F], dtype="float32")
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, _ = models.deepfm_ctr(
            ids, vals, lbl, num_features=NF, num_fields=F, embed_dim=4, deep_layers=(16, 16)
        )
        return avg_loss

    def feeds(rng):
        return {
            "ids": rng.randint(0, NF, (32, F, 1)).astype("int64"),
            "vals": rng.uniform(0, 1, (32, F)).astype("float32"),
            "lbl": rng.randint(0, 2, (32, 1)).astype("int64"),
        }

    losses = _train_steps(build, feeds, steps=6, lr=0.05)
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_bert_pretrain_trains():
    """MLM+NSP pretraining objective trains on a tiny config (flagship
    BASELINE config 3; heads follow the original BERT recipe)."""
    V, D, L, H, DI, S, B, M = 50, 16, 2, 2, 32, 12, 4, 3
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 17
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [S], dtype="int64")
        sent = fluid.layers.data("sent", [S], dtype="int64")
        mask = fluid.layers.data("mask", [S])
        mpos = fluid.layers.data("mpos", [1], dtype="int64")
        mlab = fluid.layers.data("mlab", [1], dtype="int64")
        nlab = fluid.layers.data("nlab", [1], dtype="int64")
        total, mlm_loss, nsp_acc = models.bert_pretrain(
            src, sent, mask, mpos, mlab, nlab,
            vocab_size=V, d_model=D, n_layer=L, n_head=H, d_inner=DI,
            seq_len=S, dropout_rate=0.0,
        )
        fluid.optimizer.AdamOptimizer(5e-3).minimize(total)

    rng = np.random.RandomState(0)
    feed = {
        "src": rng.randint(0, V, (B, S)).astype("int64"),
        "sent": rng.randint(0, 2, (B, S)).astype("int64"),
        "mask": np.ones((B, S), "float32"),
        "mpos": (np.arange(B)[:, None] * S + rng.randint(0, S, (B, M))).reshape(-1, 1).astype("int64"),
        "mlab": rng.randint(0, V, (B * M, 1)).astype("int64"),
        "nlab": rng.randint(0, 2, (B, 1)).astype("int64"),
    }
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(25):
            (l,) = exe.run(prog, feed=feed, fetch_list=[total])
            losses.append(float(np.asarray(l)))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


# ---------------------------------------------------------------------------
# which attention a Program gets (models/transformer.py::_fuses_attention)
# ---------------------------------------------------------------------------
def _bert_loss_and_grads(dropout_rate, monkeypatch=None, four_op=False):
    """A tiny padded BERT pretraining program: (op types, loss, grads by
    parameter name) after one forward + backward on the CPU."""
    from paddle_tpu.backward import append_backward

    V, D, L, H, DI, S, B, M = 50, 16, 2, 2, 32, 12, 4, 3
    if four_op:
        monkeypatch.setattr(models.transformer, "_fuses_attention",
                            lambda rate: False)
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 17
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
            seq_len=S, dropout_rate=dropout_rate, is_test=True)
        pairs = append_backward(total)
    types = [op.type for op in prog.global_block().ops]
    rng = np.random.RandomState(0)
    lens = np.array([S, S - 5, 7, S - 1])  # padded QUERY rows in 3 of 4
    feed = {
        "src": rng.randint(0, V, (B, S)).astype("int64"),
        "sent": rng.randint(0, 2, (B, S)).astype("int64"),
        "mask": (np.arange(S)[None, :] < lens[:, None]).astype("float32"),
        "mpos": (np.arange(B)[:, None] * S
                 + rng.randint(0, S, (B, M))).reshape(-1, 1).astype("int64"),
        "mlab": rng.randint(0, V, (B * M, 1)).astype("int64"),
        "nlab": rng.randint(0, 2, (B, 1)).astype("int64"),
    }
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(prog, feed=feed,
                      fetch_list=[total] + [g for _, g in pairs])
    grads = {p.name: np.asarray(g) for (p, _), g in zip(pairs, out[1:])}
    return types, float(np.asarray(out[0])), grads


def test_bert_without_dropout_builds_one_fused_attention_op_a_layer(
        monkeypatch):
    """dropout 0: one ``fused_attention`` and one ``fused_attention_grad``
    per layer, no ``softmax`` op; its loss and every first-step gradient
    agree with the four-op build of the same weights (float32 on the
    CPU, sums in another order: 1e-5 of the largest gradient)."""
    types, loss, grads = _bert_loss_and_grads(0.0)
    assert types.count("fused_attention") == 2
    assert types.count("fused_attention_grad") == 2
    assert "softmax" not in types and "softmax_grad" not in types
    types4, loss4, grads4 = _bert_loss_and_grads(0.0, monkeypatch,
                                                 four_op=True)
    assert "fused_attention" not in types4 and types4.count("softmax") == 2
    assert abs(loss - loss4) <= 1e-5 * abs(loss4)
    assert sorted(grads) == sorted(grads4)
    for name, g in grads.items():
        np.testing.assert_allclose(
            g, grads4[name], rtol=0,
            atol=1e-5 * max(1e-3, np.abs(grads4[name]).max()), err_msg=name)


def test_bert_with_attention_dropout_keeps_the_four_op_branch():
    types, _, _ = _bert_loss_and_grads(0.1)
    assert "fused_attention" not in types
    assert types.count("softmax") == 2 and types.count("matmul") >= 4


def test_seq2seq_attention_is_unchanged():
    """Cross-attention (S_q != S_k) and the materialized padding and
    causal biases it builds itself stay on the four-op branch, dropout
    or not."""
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [10], dtype="int64")
        tgt = fluid.layers.data("tgt", [6], dtype="int64")
        src_mask = fluid.layers.data("src_mask", [10])
        models.seq2seq.transformer_nmt(
            src, tgt, src_mask=src_mask, src_vocab=40, tgt_vocab=40,
            d_model=16, n_layer=1, n_head=2, d_inner=32, src_len=10,
            tgt_len=6, dropout_rate=0.0)
    types = [op.type for op in prog.global_block().ops]
    assert "fused_attention" not in types
    assert types.count("softmax") == 3  # encoder, decoder self, cross


def test_transformer_lm_without_dropout_is_causal_fused():
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [16], dtype="int64")
        models.transformer_lm(src, None, vocab_size=30, d_model=16,
                              n_layer=2, n_head=2, d_inner=32, seq_len=16)
    ops = [op for op in prog.global_block().ops
           if op.type == "fused_attention"]
    assert len(ops) == 2 and all(op.attrs["causal"] for op in ops)
    assert not any(op.type == "softmax" for op in prog.global_block().ops)
