"""Transformer family: BERT-style encoder and a decoder-only LM.

Reference model family: the reference ships transformer NMT as a dist test
model (python/paddle/fluid/tests/unittests/dist_transformer.py) built from
the same primitives used here (layers/nn.py fc/matmul/softmax/layer_norm).
This is the flagship for the multi-chip shardings: parameters get stable
names (``enc_<i>_...``) so `paddle_tpu.parallel` sharding rules can map
attention/FFN weights onto the ``tp`` axis (Megatron-style column/row
parallel) and activations onto ``sp``/``dp`` — see
parallel/auto_shard.py.

TPU notes: everything is static-shape [batch, seq_len]; variable-length
text uses bucketed padding + the input mask (the LoDTensor analog — see
SURVEY.md §5 long-context notes).
"""
from __future__ import annotations

import numpy as np

from paddle_tpu import ParamAttr, layers

__all__ = ["multi_head_attention", "encoder_layer", "bert_encoder", "bert_pretrain", "transformer_lm"]


def _fc3(x, size, name, num_flatten_dims=2, act=None):
    return layers.fc(
        x,
        size=size,
        num_flatten_dims=num_flatten_dims,
        param_attr=ParamAttr(name=name + "_w"),
        bias_attr=ParamAttr(name=name + "_b"),
        act=act,
    )


def _fuses_attention(dropout_rate: float) -> bool:
    """The one decision: attention with no dropout inside it is ONE
    ``fused_attention`` op (its lowering is the op's to choose); with
    dropout on the probabilities it stays matmul, add, softmax, dropout,
    matmul."""
    return not dropout_rate


def multi_head_attention(
    q_in,
    kv_in,
    d_model: int,
    n_head: int,
    dropout_rate: float = 0.1,
    attn_bias=None,
    is_test: bool = False,
    name: str = "att",
    mask=None,
    causal: bool = False,
):
    """Scaled-dot-product multi-head attention over [N, S, d_model].

    q/k/v projections, then over [N, H, S, D] either

    * ONE ``fused_attention`` op — whenever no dropout sits inside
      attention, it is self-attention and no materialized ``attn_bias``
      is handed in: padding goes in as ``mask`` [N, S] (1 = token) and
      causality as ``causal=``; or
    * four ops — batched matmul, the additive ``attn_bias`` ([S, S]
      causal or [N, 1, 1, S] padding, broadcast into the logits),
      softmax (+ dropout), matmul: attention dropout, cross-attention, or
      a bias only the caller can build.

    then the output projection.
    """
    d_head = d_model // n_head
    q = _fc3(q_in, d_model, name + "_q")
    k = _fc3(kv_in, d_model, name + "_k")
    v = _fc3(kv_in, d_model, name + "_v")

    def split_heads(x):
        # [N, S, d_model] -> [N, H, S, D]
        x = layers.reshape(x, shape=[0, 0, n_head, d_head])
        return layers.transpose(x, perm=[0, 2, 1, 3])

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    if _fuses_attention(dropout_rate) and attn_bias is None and q_in is kv_in:
        from paddle_tpu.layer_helper import LayerHelper

        helper = LayerHelper(name + "_fused")
        ctx = helper.create_variable_for_type_inference(q.dtype)
        lse = helper.create_variable_for_type_inference("float32")
        lse.stop_gradient = True
        ins = {"Q": [q], "K": [k], "V": [v]}
        if mask is not None:
            ins["Mask"] = [mask]
        helper.append_op(
            type="fused_attention", inputs=ins,
            outputs={"Out": [ctx], "Lse": [lse]},
            attrs={"causal": bool(causal),
                   "scale": 1.0 / float(np.sqrt(d_head))},
        )
    else:
        if mask is not None or causal:
            raise ValueError(
                "mask=/causal= describe the masking to the fused op; the "
                "four-op path (dropout inside attention, cross-attention) "
                "takes a materialized attn_bias (silently ignoring them "
                "would drop the masking)"
            )
        scores = layers.matmul(q, k, transpose_y=True, alpha=1.0 / float(np.sqrt(d_head)))
        if attn_bias is not None:
            scores = scores + attn_bias
        weights = layers.softmax(scores)
        if dropout_rate:
            weights = layers.dropout(weights, dropout_prob=dropout_rate, is_test=is_test)
        ctx = layers.matmul(weights, v)  # [N, H, S, D]
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[0, 0, d_model])
    return _fc3(ctx, d_model, name + "_out")


def positionwise_ffn(x, d_model, d_inner, name, act="gelu", is_test=False, dropout_rate=0.1):
    hidden = _fc3(x, d_inner, name + "_fc0", act=act)
    if dropout_rate:
        hidden = layers.dropout(hidden, dropout_prob=dropout_rate, is_test=is_test)
    return _fc3(hidden, d_model, name + "_fc1")


def encoder_layer(
    x,
    d_model,
    n_head,
    d_inner,
    attn_bias=None,
    dropout_rate: float = 0.1,
    is_test: bool = False,
    name: str = "enc_0",
    mask=None,
    causal: bool = False,
):
    """Post-LN transformer block (attention + FFN, residuals)."""
    att = multi_head_attention(
        x, x, d_model, n_head, dropout_rate, attn_bias, is_test,
        name=name + "_att", mask=mask, causal=causal,
    )
    if dropout_rate:
        att = layers.dropout(att, dropout_prob=dropout_rate, is_test=is_test)
    x = layers.layer_norm(
        x + att,
        begin_norm_axis=2,
        param_attr=ParamAttr(name=name + "_ln1_scale"),
        bias_attr=ParamAttr(name=name + "_ln1_bias"),
    )
    ffn = positionwise_ffn(x, d_model, d_inner, name + "_ffn", is_test=is_test, dropout_rate=dropout_rate)
    if dropout_rate:
        ffn = layers.dropout(ffn, dropout_prob=dropout_rate, is_test=is_test)
    return layers.layer_norm(
        x + ffn,
        begin_norm_axis=2,
        param_attr=ParamAttr(name=name + "_ln2_scale"),
        bias_attr=ParamAttr(name=name + "_ln2_bias"),
    )


def _causal_bias(seq_len: int, dtype="float32"):
    """[S, S] additive bias: 0 on/below diagonal, -1e9 above."""
    r = layers.range(0, seq_len, 1, "int32")
    rows = layers.reshape(r, shape=[seq_len, 1])
    cols = layers.reshape(r, shape=[1, seq_len])
    allowed = layers.cast(layers.less_equal(cols, rows), dtype)
    return (allowed - 1.0) * 1e9


def _embeddings(ids, vocab_size, d_model, max_pos, seq_len, name, extra_ids=None, extra_vocab=0):
    emb = layers.embedding(
        ids, size=[vocab_size, d_model], param_attr=ParamAttr(name=name + "_word_emb")
    )
    pos = layers.range(0, seq_len, 1, "int64")
    pos = layers.reshape(pos, shape=[1, seq_len])
    pos_emb = layers.embedding(
        pos, size=[max_pos, d_model], param_attr=ParamAttr(name=name + "_pos_emb")
    )
    out = emb + pos_emb
    if extra_ids is not None:
        out = out + layers.embedding(
            extra_ids, size=[extra_vocab, d_model], param_attr=ParamAttr(name=name + "_sent_emb")
        )
    return out


def bert_encoder(
    src_ids,
    input_mask=None,
    sent_ids=None,
    vocab_size: int = 30522,
    d_model: int = 768,
    n_layer: int = 12,
    n_head: int = 12,
    d_inner: int = 3072,
    max_pos: int = 512,
    seq_len: int = 128,
    dropout_rate: float = 0.1,
    is_test: bool = False,
    name: str = "bert",
):
    """BERT-base encoder; returns the [N, S, d_model] sequence output.

    ``input_mask``: float [N, S] (1 = token, 0 = pad): the ``Mask`` input
    of each layer's fused_attention op, or, with dropout inside
    attention, one materialized additive bias for the four-op build.
    """
    x = _embeddings(src_ids, vocab_size, d_model, max_pos, seq_len, name, sent_ids, 2)
    x = layers.layer_norm(
        x,
        begin_norm_axis=2,
        param_attr=ParamAttr(name=name + "_emb_ln_scale"),
        bias_attr=ParamAttr(name=name + "_emb_ln_bias"),
    )
    if dropout_rate:
        x = layers.dropout(x, dropout_prob=dropout_rate, is_test=is_test)
    fused = _fuses_attention(dropout_rate)
    attn_bias = None
    if input_mask is not None and not fused:
        m = layers.reshape(input_mask, shape=[-1, 1, 1, seq_len])
        attn_bias = layers.scale(m, scale=1e9, bias=-1e9)  # (m-1)*1e9
    for i in range(n_layer):
        x = encoder_layer(
            x, d_model, n_head, d_inner, attn_bias, dropout_rate, is_test,
            name="%s_enc_%d" % (name, i),
            mask=input_mask if fused else None,
        )
    return x


def transformer_lm(
    src_ids,
    labels,
    vocab_size: int = 32000,
    d_model: int = 512,
    n_layer: int = 6,
    n_head: int = 8,
    d_inner: int = 2048,
    seq_len: int = 256,
    max_pos: int = 2048,
    dropout_rate: float = 0.0,
    is_test: bool = False,
    name: str = "lm",
):
    """Decoder-only causal LM; returns (avg_loss, logits).

    src_ids/labels: int64 [N, S] / [N, S, 1].

    With ``dropout_rate=0`` (the default) causality goes in as the fused
    op's ``causal=`` attr instead of a materialized [S, S] bias — the
    build the sequence-parallel (sp) serving layout needs, since only
    the fused op can dispatch to ring attention (no S^2 tensor may exist
    for the seq axis to shard).
    """
    x = _embeddings(src_ids, vocab_size, d_model, max_pos, seq_len, name)
    fused = _fuses_attention(dropout_rate)
    causal = None if fused else _causal_bias(seq_len, x.dtype)
    for i in range(n_layer):
        x = encoder_layer(
            x, d_model, n_head, d_inner, causal, dropout_rate, is_test,
            name="%s_dec_%d" % (name, i), causal=fused,
        )
    logits = _fc3(x, vocab_size, name + "_head")
    if labels is None:  # inference/decoding program: logits only
        return None, logits
    loss = layers.softmax_with_cross_entropy(logits, labels)
    avg_loss = layers.mean(loss)
    return avg_loss, logits


def bert_pretrain(
    src_ids,
    sent_ids,
    input_mask,
    mask_pos,
    mask_labels,
    nsp_labels,
    vocab_size: int = 30522,
    d_model: int = 768,
    n_layer: int = 12,
    n_head: int = 12,
    d_inner: int = 3072,
    max_pos: int = 512,
    seq_len: int = 128,
    dropout_rate: float = 0.1,
    is_test: bool = False,
    name: str = "bert",
):
    """BERT pretraining objective: masked-LM + next-sentence prediction
    (BASELINE.json flagship config 3; reference model family:
    ERNIE/BERT-on-fluid pretraining — the fluid repo itself ships only
    the encoder blocks, so heads follow the original BERT recipe).

    src_ids/sent_ids: int64 [N, S]; input_mask: float [N, S];
    mask_pos: int64 [N*M, 1] FLATTENED positions into [N*S];
    mask_labels: int64 [N*M, 1]; nsp_labels: int64 [N, 1].
    Returns (total_loss, mlm_loss, nsp_acc).
    """
    enc = bert_encoder(
        src_ids, input_mask, sent_ids, vocab_size, d_model, n_layer, n_head,
        d_inner, max_pos, seq_len, dropout_rate, is_test, name,
    )  # [N, S, D]

    # ---- masked LM head over gathered positions
    flat = layers.reshape(enc, shape=[-1, d_model])          # [N*S, D]
    picked = layers.gather(flat, layers.reshape(mask_pos, shape=[-1]))  # [N*M, D]
    trans = _fc3(picked, d_model, name + "_mlm_trans", num_flatten_dims=1, act="gelu")
    trans = layers.layer_norm(
        trans, begin_norm_axis=1,
        param_attr=ParamAttr(name=name + "_mlm_ln_scale"),
        bias_attr=ParamAttr(name=name + "_mlm_ln_bias"),
    )
    # output projection TIED to the word embedding (original BERT recipe)
    word_emb = enc.block.program.global_block().var(name + "_word_emb")
    mlm_logits = layers.matmul(trans, word_emb, transpose_y=True)  # [N*M, V]
    mlm_bias = layers.create_parameter([vocab_size], "float32",
                                       name=name + "_mlm_out_b", is_bias=True)
    mlm_logits = mlm_logits + mlm_bias
    mlm_loss = layers.mean(layers.softmax_with_cross_entropy(mlm_logits, mask_labels))

    # ---- next-sentence head on the [CLS] (first) token
    first = layers.slice(enc, axes=[1], starts=[0], ends=[1])   # [N, 1, D]
    pooled = _fc3(layers.reshape(first, shape=[-1, d_model]), d_model,
                  name + "_pool", num_flatten_dims=1, act="tanh")
    nsp_logits = _fc3(pooled, 2, name + "_nsp", num_flatten_dims=1)
    nsp_loss = layers.mean(layers.softmax_with_cross_entropy(nsp_logits, nsp_labels))
    nsp_acc = layers.accuracy(nsp_logits, nsp_labels)

    total = mlm_loss + nsp_loss
    return total, mlm_loss, nsp_acc
