"""Plain reference for ``gpt1_117m``: the full causal forward of a
GPT-1-shaped decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no batching tricks, nothing from ``paddle_tpu``.

Follows Radford et al. 2018 (post-LN blocks: x = LN(x + attn(x)),
x = LN(x + ffn(x)); learned positions; no final LayerNorm), with the
departures ``gpt1_117m.json`` lists: an untied output head with a bias
and the exact GELU.  Weights come in under the names the served program
uses (``lm_word_emb``, ``lm_dec_<i>_att_q_w`` ...): that naming is the
only thing shared with the system under test.

Tolerance (``check.logit_gap_share`` in the config, as
``chip_smoke.LOGIT_GAP_SHARE``): the served step multiplies fp32 weights
at the TPU's default precision (bf16 products) in all 12 layers; this
forward runs at "highest".  With random weights the top two of 40478
logits sit closer than that rounding, so the argmax flips and tokens
cannot be compared; logits can.  Each served token's reference logit
must lie within 2% of that position's logit range (max - min) of the
position's maximum.  About two in 40478 random logits are that close to
the top, so the check still pins every token to the top handful, and a
wrong mask, position, cache row or layer order fails it at once.
"""
import jax
import jax.numpy as jnp


def _ln(w, x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w[p + "_scale"] + w[p + "_bias"]


def _fc(w, x, p):
    return x @ w[p + "_w"] + w[p + "_b"]


def forward(w, tokens, n_layer, n_head, eps=1e-5, name="lm"):
    """tokens [B, S] int32 -> logits [B, S, V]; position s attends to
    positions <= s."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        x = w[name + "_word_emb"][tokens] + w[name + "_pos_emb"][:s][None]
        d = x.shape[-1]
        dh = d // n_head
        causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
        for i in range(n_layer):
            p = "%s_dec_%d" % (name, i)
            split = lambda t: t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)
            q = split(_fc(w, x, p + "_att_q"))
            k = split(_fc(w, x, p + "_att_k"))
            v = split(_fc(w, x, p + "_att_v"))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh))
            scores = jnp.where(causal, scores, -1e9)
            ctx = jnp.einsum("bhqk,bhkd->bhqd",
                             jax.nn.softmax(scores, axis=-1), v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, d)
            x = _ln(w, x + _fc(w, ctx, p + "_att_out"), p + "_ln1", eps)
            h = jax.nn.gelu(_fc(w, x, p + "_ffn_fc0"), approximate=False)
            x = _ln(w, x + _fc(w, h, p + "_ffn_fc1"), p + "_ln2", eps)
        return _fc(w, x, name + "_head")
