"""Plain reference for ``bert_base``: BERT's pretraining loss (masked LM
+ next sentence) as one float32 ``jax.numpy`` forward at matmul
precision "highest".  Nothing from ``paddle_tpu``; weights come in under
the names the program gives its parameters (``bert_word_emb``,
``bert_enc_<i>_att_q_w`` ...), which is all the two share.

Follows Devlin et al. 2018 / google-research/bert ``modeling.py`` and
``run_pretraining.py``: embeddings (word + position + segment) ->
LayerNorm -> 12 post-LN blocks (exact GELU) -> MLM head (dense + GELU +
LayerNorm, projection tied to the word embedding, plus a bias) over the
gathered masked positions, and the pooler (dense + tanh on [CLS]) ->
2-way classifier.  The loss is the mean masked-LM cross-entropy plus the
mean next-sentence cross-entropy.  Departure, as the config lists it:
LayerNorm epsilon 1e-5.

What is compared (``check.tensors`` in the config): tensors of the
program's forward (``clone(for_test=True)``, the scope's own weights
after the window) on 8 seeded sequences of unequal length, padded and
masked: the encoder's output ``[B, S, D]`` and the masked-LM logits
``[B*M, V]``.  For each, two numbers against this reference:

* ``rel_rms``: rms(program - reference) / std(reference);
* ``worst_gap_share``: max |program - reference| over the reference's
  range (max - min), as the GPT check's logit-gap share.

Tolerance and its reason.  The clone multiplies its fp32 variables as
the TPU's compiler sees fit; this reference runs at "highest".  That
difference IS the gap, and the chip reads it at 0.0012-0.0016
(encoder) and 0.0006-0.0007 (logits) ``rel_rms``: the config's
``check.why`` has the readings.  ``rel_rms`` averages millions of
elements, so its bounds are 3 x the largest reading; the worst gap is a
maximum and gets 4 x.  What must fail does: one pass of bf16 products
(0.007 / 0.0086 by a CPU emulation), int8-rounded weights and a mask
that hides the wrong keys (``tests/test_reference_check.py`` holds the
last two to the committed numbers, at full width).  A scalar loss cannot
do this: on uniform random ids and labels it sits at chance, ln V +
ln 2, whatever the encoder computes (int8 weights moved it 1e-5 ...
1.5e-3 relative).  The loss is still printed beside the check.  The
next-sentence logits (``forward`` returns them) carry no bound: 16
numbers whose range shrinks as training goes on.

Not covered: the bf16-AMP backward and the optimizer.  The clone is the
forward before ``decorate`` and ``minimize``; the training step is held
to a finite loss only (PERF.md, Open questions: a gradient check).
"""
import jax
import jax.numpy as jnp


def _ln(w, x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w[p + "_scale"] + w[p + "_bias"]


def _fc(w, x, p):
    return x @ w[p + "_w"] + w[p + "_b"]


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def forward(w, batch, n_layer, n_head, eps=1e-5, name="bert"):
    """``batch``: src/sent [B, S] int, mask [B, S] float (1 = token,
    0 = padding), mpos [B*M, 1] flattened positions into [B*S].
    Returns ``encoder_out`` [B, S, D], ``mlm_logits`` [B*M, V] and
    ``nsp_logits`` [B, 2]."""
    with jax.default_matmul_precision("highest"):
        src, sent, mask = batch["src"], batch["sent"], batch["mask"]
        b, s = src.shape
        x = (w[name + "_word_emb"][src] + w[name + "_pos_emb"][:s][None]
             + w[name + "_sent_emb"][sent])
        x = _ln(w, x, name + "_emb_ln", eps)
        d = x.shape[-1]
        dh = d // n_head
        bias = ((mask - 1.0) * 1e9)[:, None, None, :]
        for i in range(n_layer):
            p = "%s_enc_%d" % (name, i)
            split = lambda t: t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)
            q = split(_fc(w, x, p + "_att_q"))
            k = split(_fc(w, x, p + "_att_k"))
            v = split(_fc(w, x, p + "_att_v"))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh)) + bias
            ctx = jnp.einsum("bhqk,bhkd->bhqd",
                             jax.nn.softmax(scores, axis=-1), v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, d)
            x = _ln(w, x + _fc(w, ctx, p + "_att_out"), p + "_ln1", eps)
            h = jax.nn.gelu(_fc(w, x, p + "_ffn_fc0"), approximate=False)
            x = _ln(w, x + _fc(w, h, p + "_ffn_fc1"), p + "_ln2", eps)
        picked = x.reshape(b * s, d)[batch["mpos"].reshape(-1)]
        t = jax.nn.gelu(_fc(w, picked, name + "_mlm_trans"),
                        approximate=False)
        t = _ln(w, t, name + "_mlm_ln", eps)
        mlm_logits = t @ w[name + "_word_emb"].T + w[name + "_mlm_out_b"]
        pooled = jnp.tanh(_fc(w, x[:, 0], name + "_pool"))
        return {"encoder_out": x, "mlm_logits": mlm_logits,
                "nsp_logits": _fc(w, pooled, name + "_nsp")}


def loss(w, batch, n_layer, n_head, eps=1e-5, name="bert"):
    """The scalar pretraining loss of ``forward``'s logits against
    ``batch``'s mlab [B*M, 1] and nlab [B, 1]: mean masked-LM
    cross-entropy plus mean next-sentence cross-entropy."""
    out = forward(w, batch, n_layer, n_head, eps, name)
    return (jnp.mean(_xent(out["mlm_logits"], batch["mlab"].reshape(-1)))
            + jnp.mean(_xent(out["nsp_logits"], batch["nlab"].reshape(-1))))


def gaps(got, want):
    """(rel_rms, worst_gap_share) of one tensor against its reference,
    as the docstring defines them; numpy arrays in, floats out."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = got - want
    rel_rms = float(np.sqrt(np.mean(diff ** 2)) / np.std(want))
    worst = float(np.abs(diff).max() / (want.max() - want.min()))
    return rel_rms, worst
