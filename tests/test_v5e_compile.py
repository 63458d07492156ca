"""The kernels at the benchmark's widths, compiled for a described v5e chip.

No chip is attached: nothing runs, the chip's compiler accepts or
refuses.  One file loads that compiler (libtpu, in the test's own
process; skipped where the topology cannot be described), so the
compile cases of ``paddle_tpu/decode_attention.py``,
``fused_attention.py``, ``grouped_matmul.py``, ``hybrid_ssm.py`` and the
gpt1 chunk share its module-scoped ``one_chip`` here, in a file of
their own: under ``--dist loadfile`` a file is one worker's job, and the
kernel tests of ``tests/test_decode_attention.py`` (interpret mode, 167
cases) are another's.
"""
import contextlib

import numpy as np
import pytest

from test_decode_attention import _two_grouped_layers, _two_sparse_layers

from paddle_tpu import decode_attention as da


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # an executable for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@contextlib.contextmanager
def _chunk_tool():
    """``(tools/decode_chunk_text.py, the checkout's root)``.  The tool
    answers "tpu" for the backend it compiles for and puts the checkout
    on the path: both undone when the block ends."""
    import importlib.util
    import os
    import sys

    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "decode_chunk_text", os.path.join(root, "tools",
                                          "decode_chunk_text.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", jax.default_backend)
        patch.setattr(sys, "path", list(sys.path))
        yield tool, root


def _compiled_chunk(config, layers, counters, kind="chunk"):
    """The slot pool's ``chunk`` of ``config`` (its one rung pair, the
    published widths, the first ``layers`` of its cut; ``kind``: another
    of the tool's programs) compiled ONCE: ``(text, memory analysis,
    counted)`` — ``counted`` what the trace added to ``counters``
    (``{key: a counter's child}``) and, under ``"relayouts"``, the tool's
    counts of copies of a matrix or a cache leaf (a step, once a call)."""
    with _chunk_tool() as (tool, root):
        before = {k: c.value for k, c in counters.items()}
        lowered = tool.lowered_chunk(root, config, kind, layers)
        compiled = lowered.compile()
        text = compiled.as_text()
        counted = {k: c.value - before[k] for k, c in counters.items()}
        counted["relayouts"] = tool.relayouts(lowered, text)
    return text, compiled.memory_analysis(), counted


def test_kernel_compiles_for_v5e_at_gpt1_widths(one_chip):
    """320 slots x 512 positions x 768 (12 heads), blocks of 128: the
    kernel lowers, and both cache leaves are aliased in place (no
    temporary of a leaf's size)."""
    import jax
    import jax.numpy as jnp

    s, t, d, h = 320, 512, 768, 12
    block = da.kv_read_block(t)
    assert da.kernel_supported(t, d, h)

    def f(q, kn, vn, kc, vc, ts):
        return da.ragged_decode_attention(
            q, kn, vn, kc, vc, ts, da.decode_work_items(ts, t, block),
            n_head=h, scale=0.125, block=block)

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(f, donate_argnums=(3, 4)).lower(
        sd((s, d)), sd((s, d)), sd((s, d)), sd((s, t, d)), sd((s, t, d)),
        sd((s,), jnp.int32)).compile()
    assert "ragged_decode_attention" in compiled.as_text()
    mem = compiled.memory_analysis()
    leaf = s * t * d * 4
    assert mem.alias_size_in_bytes >= 2 * leaf
    assert mem.temp_size_in_bytes < leaf // 8


def test_block_kernel_compiles_for_v5e_at_minicpm_sala_widths(one_chip):
    """64 slots x 32768 positions x 2 K/V heads of 128, 16 query heads a
    K/V head, 98 named blocks of 64 of which the first and the window's
    33 are declared runs: the kernel lowers, reads the leaves where they
    lie (no temporary of a leaf's size), and its buffers stay inside the
    VMEM a kernel gets without asking (it asks for none)."""
    import jax
    import jax.numpy as jnp

    s, t, g, d, rep, block, b = 64, 32768, 2, 128, 16, 64, 98

    def f(q, kc, vc, ts, blocks, valid):
        return da.block_sparse_decode_attention(
            q, kc, vc, ts, blocks, valid, n_head=g * rep, n_kv_head=g,
            scale=0.088, block=block, shared_runs=((0, 1), (1, 33)))

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(f).lower(
        sd((s, g * rep * d)), sd((s, t, g * d), jnp.bfloat16),
        sd((s, t, g * d), jnp.bfloat16), sd((s,), jnp.int32),
        sd((s, g, b), jnp.int32), sd((s, g, b), jnp.bool_))
    # the kernel asked for no more VMEM than the compiler's default, and
    # the chip's compiler accepts it so
    assert "scoped_memory_configs" not in lowered.as_text()
    compiled = lowered.compile()
    assert "block_sparse_decode_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        s * t * g * d * 2) // 8


def test_grouped_kernel_compiles_for_v5e_at_smallthinker_widths(one_chip):
    """40 slots x 16,384 positions x 4 K/V heads of 128, 7 query heads a
    K/V head, bf16: two layers' append-and-read lower to ONE kernel
    called twice, both layers' leaves are appended in place and handed
    to the kernel as they lie (the benchmark's shape needle finds the
    call; no temporary of a leaf's size)."""
    import re

    import jax

    f, args, _ = _two_grouped_layers(one_chip)
    lowered = jax.jit(f, donate_argnums=(3, 4, 5, 6)).trace(*args).lower()
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"call @_grouped\b", text)) == 2
    compiled = lowered.compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "grouped_decode_attention" in line and "custom-call(" in line]
    assert len(calls) == 2 and all("bf16[40,16384,512]" in c for c in calls)
    leaf = 40 * 16384 * 512 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * leaf
    assert mem.temp_size_in_bytes < leaf // 8


def test_grouped_kernel_compiles_for_v5e_at_k_exaone_widths(one_chip):
    """128 slots x 4,096 positions x 8 K/V heads of 128, 8 query heads a
    K/V head, bf16, TWO fresh rows a slot (``k_exaone_236b_a23b``'s
    global layer and its module in one self-drafting round): the two
    append-and-reads lower to ONE kernel called twice, the leaves are
    appended in place and handed to the kernel as they lie — no copy of
    a rung-sized operand in the compiled text, no temporary of a leaf's
    size — and the unit is the rule's (four heads: 64 rows x 512
    lanes)."""
    import re

    import jax

    f, args, _ = _two_grouped_layers(one_chip, "k_exaone")
    assert args[0].shape == (128, 2, 64 * 128)
    lowered = jax.jit(f, donate_argnums=(3, 4, 5, 6)).trace(*args).lower()
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"call @_grouped\b", text)) == 2
    compiled = lowered.compile()
    lines = compiled.as_text().splitlines()
    calls = [line for line in lines
             if "grouped_decode_attention" in line and "custom-call(" in line]
    assert len(calls) == 2 and all("bf16[128,4096,1024]" in c for c in calls)
    assert all("bf16[128,2,64,512]" in c for c in calls)    # q: units, R, L
    assert not [line for line in lines
                if " copy(" in line and "[128,4096," in line]
    leaf = 128 * 4096 * 1024 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * leaf
    assert mem.temp_size_in_bytes < leaf // 8


def test_grouped_kernel_compiles_for_v5e_at_olmo_hybrid_widths(one_chip):
    """80 slots x 1,024 positions x 30 K/V heads of 128, ONE query head
    each, bf16 (two of ``olmo_hybrid_7b``'s full layers): the two
    append-and-reads lower to ONE kernel called twice over the leaves as
    they lie, the 30 heads one unit of 32 rows handed over ``[heads,
    Dh]`` (not as rows 3,840 lanes wide), in blocks of 256 — and the
    chip's compiler grants the VMEM the kernel asks for, which stays
    under a third of the chip's (the wide-row operand and blocks of 512
    asked for 90 MB)."""
    import re

    import jax

    f, args, _ = _two_grouped_layers(one_chip, "olmo")
    assert args[0].shape == (80, 30 * 128)
    assert da.step_read_sizes(1024, 3840, "bfloat16", n_head=30,
                              n_kv_head=30, backend="tpu") == (256, 32)
    lowered = jax.jit(f, donate_argnums=(3, 4, 5, 6)).trace(*args).lower()
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"call @_grouped\b", text)) == 2
    asked = [int(n) for n in re.findall(
        r'scoped_memory_configs[^\]]*?size[^0-9]*([0-9]+)', text)]
    assert asked and max(asked) < 40 << 20, asked
    compiled = lowered.compile()        # raises where the chip would
    lines = compiled.as_text().splitlines()
    calls = [line for line in lines
             if "grouped_decode_attention" in line and "custom-call(" in line]
    assert len(calls) == 2 and all("bf16[80,1024,3840]" in c for c in calls)
    assert all("bf16[80,1,32,128]" in c for c in calls)     # q: units, R, Dh
    assert not [line for line in lines
                if " copy(" in line and "[80,1024," in line]
    leaf = 80 * 1024 * 3840 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * leaf
    assert mem.temp_size_in_bytes < leaf // 8


def test_block_kernel_is_lowered_once_for_two_layers_on_v5e(one_chip):
    """Lowered for the chip, two sparse layers are two calls of ONE
    function that holds the ONE kernel of the module."""
    import re

    import jax

    f, args, _ = _two_sparse_layers(one_chip)
    text = jax.jit(f).trace(*args).lower().as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"func\.func private @_block_sparse\b", text)) == 1
    assert len(re.findall(r"call @_block_sparse\b", text)) == 2


def test_hybrid_ssm_layer_compiles_for_v5e_at_falcon_h1_widths(one_chip):
    """80 slots x 1024 positions, one block's Mamba-2 mixer and
    grouped-query attention at Falcon-H1-34B's widths (bf16 weights and
    K/V, fp32 state): the SSM, conv and both K/V leaves are aliased in
    place, no temporary is the size of the 335 MB SSM leaf (the update is
    one pass), and the update carries its scope's name for the trace."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from paddle_tpu import hybrid_ssm as hs

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "falcon_h1_34b.json")) as fh:
        cfg = json.load(fh)
    d = hs.dims(cfg)
    s, t, p = 80, 1024, "lm_l0_"

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = {k: sd(shp, jnp.bfloat16 if len(shp) == 2
               and not k.endswith("conv_w") else jnp.float32)
         for k, shp in hs.param_shapes(cfg).items() if k.startswith(p)}

    def f(w, ssm, conv, kc, vc, x, ts):
        mix, ssm, conv = hs.mamba2_step(x, w, p, ssm, conv, ts, d)
        q = hs.linear(x, w[p + "attn_q"])
        k = hs.linear(x, w[p + "attn_k"])
        ctx, kv = da.grouped_masked_decode_attention(
            q, k, hs.linear(x, w[p + "attn_v"]), {"k": kc, "v": vc}, ts,
            n_head=d.n_head, n_kv_head=d.n_kv_head, scale=0.088)
        return mix, ctx, ssm, conv, kv["k"], kv["v"]

    ssm = (s, d.ssm_heads, d.ssm_head_dim, d.d_state)
    compiled = jax.jit(f, donate_argnums=(1, 2, 3, 4)).lower(
        w, sd(ssm), sd((s, d.d_conv - 1, d.d_xbc)),
        sd((s, t, d.d_kv), jnp.bfloat16), sd((s, t, d.d_kv), jnp.bfloat16),
        sd((s, d.d_model)), sd((s,), jnp.int32)).compile()
    assert hs.SSM_UPDATE_SCOPE in compiled.as_text()
    mem = compiled.memory_analysis()
    ssm_leaf = 4 * s * d.ssm_heads * d.ssm_head_dim * d.d_state
    kv_leaf = 2 * s * t * d.d_kv
    assert mem.alias_size_in_bytes >= ssm_leaf + 2 * kv_leaf
    assert mem.temp_size_in_bytes < ssm_leaf // 2


def test_gpt1_chunk_compiles_for_v5e_with_no_cast_of_a_weight(one_chip):
    """The slot pool's ``chunk`` of ``gpt1_117m`` (its one rung pair, the
    published widths, two layers) as ``tools/decode_chunk_text.py``
    builds it for a TPU: the builder holds bf16 copies of the matrices
    it multiplies, so the chip's compiler leaves no instruction that
    casts a whole weight-shaped matrix to bf16 (13 before the copies:
    six a layer and the head, run again on every call), and the ragged
    kernel is still the attention."""
    with _chunk_tool() as (tool, root):
        lowered = tool.lowered_chunk(root, "gpt1_117m")
        text = lowered.compile().as_text()
    assert "ragged_decode_attention" in text
    assert tool.weight_casts(lowered, text) == 0


@pytest.mark.parametrize("shape", [(32, 12, 512, 64), (128, 12, 128, 64)],
                         ids=["pretrain_s512", "pretrain_s128"])
def test_fused_attention_kernels_compile_for_v5e_at_bert_widths(one_chip,
                                                                shape):
    """The training attention pair (paddle_tpu/fused_attention.py, kept
    here because one file loads the chip's compiler) in the model's own
    layout, forward + backward: both kernels lower, the model's head
    transposes cancel against the op's (the kernels read the
    projections' ``[N, S, 768]`` as it is: no ``[N, 12, S, 64]`` copy),
    and nothing score-shaped exists."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import fused_attention as fa

    n, h, s, d = shape
    assert fa.attention_lowering("tpu", s, s, h, d, jnp.bfloat16) == "kernel"

    def heads(x):
        return x.reshape(n, s, h, d).transpose(0, 2, 1, 3)

    def merge(x):
        return x.transpose(0, 2, 1, 3).reshape(n, s, h * d)

    def f(xq, xk, xv, mask, dctx):
        q, k, v = heads(xq), heads(xk), heads(xv)
        out, lse = fa.kernel_attention(q, k, v, mask, False, 0.125)
        grads = fa.kernel_attention_grad(q, k, v, mask, out, lse,
                                         heads(dctx), False, 0.125)
        return (merge(out),) + tuple(merge(g) for g in grads)

    x = jax.ShapeDtypeStruct((n, s, h * d), jnp.bfloat16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((n, s), jnp.float32, sharding=one_chip)
    text = jax.jit(f).lower(x, x, x, mask, x).compile().as_text()
    assert "fused_attention_fwd" in text and "fused_attention_bwd" in text
    assert "[%d,%d,%d,%d]" % (n, h, s, s) not in text
    assert "[%d,%d,%d,%d]" % (n, h, s, d) not in text


def test_grouped_matmul_compiles_for_v5e_at_lfm2_widths(one_chip):
    """1024 sorted (row, choice) pairs against 64 experts' stacked
    matrices, both products of the gated FFN: the kernel lowers, and no
    expert matrix is copied (no temporary of a stacked matrix's size)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import grouped_matmul as gm

    m, d, f, e = 1024, 2048, 1536, 64

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def ffn(rows, w13, w2, sizes):
        p = gm.plan(sizes, m)
        gu = gm.kernel_grouped_matmul(rows, w13, p)
        act = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        return gm.kernel_grouped_matmul(act.astype(w2.dtype), w2, p)

    assert gm.lowering("tpu", sd((m, d)), sd((e, d, 2 * f))) == "kernel"
    assert gm.lowering("tpu", sd((m, f)), sd((e, f, d))) == "kernel"
    compiled = jax.jit(ffn).lower(
        sd((m, d)), sd((e, d, 2 * f)), sd((e, f, d)),
        sd((e,), jnp.int32)).compile()
    assert compiled.as_text().count(gm.KERNEL_NAME) >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < (
        e * f * d * 2) // 4


@pytest.mark.parametrize("s,t,g,rep,dh,copied", [
    (256, 2048, 8, 4, 64, 1), (80, 1024, 30, 1, 128, 2)],
    ids=["lfm2_64_wide_heads", "olmo_hybrid_one_head_a_kv_head"])
def test_lane_masked_attention_makes_no_copy_of_a_rung_on_v5e(
        one_chip, s, t, g, rep, dh, copied):
    """Why ``make_decode_attention`` sends two groupings through the form
    that reads bf16 leaves as they lie, which compiles with temporaries
    far under a leaf's size.  256 slots x 2048 positions x 8 K/V heads of
    64, 4 query heads a K/V head (``lfm2_24b_a2b``): the per-head view
    of the same leaves re-tiles them, a copy of each.  80 x 1024 x 30
    heads of 128, ONE query head a K/V head (``olmo_hybrid_7b``'s full
    layers): with one query row a K/V head the compiler takes the score
    product off the matrix unit and first re-lays each leaf out in
    float32, a temporary TWICE a leaf's size a leaf (in the compiled
    ``chunk``, PR 50: six 1.26 GB copies a step, 3.0 GB of temporaries;
    0.5 GB with the lane form)."""
    import jax
    import jax.numpy as jnp

    leaf = s * t * g * dh * 2

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def temp(form):
        def f(q, kn, vn, k, v, ts):
            return form(q, kn, vn, {"k": k, "v": v}, ts, n_head=g * rep,
                        n_kv_head=g, scale=0.125)
        return jax.jit(f, donate_argnums=(3, 4)).lower(
            sd((s, g * rep * dh)), sd((s, g * dh)), sd((s, g * dh)),
            sd((s, t, g * dh), jnp.bfloat16), sd((s, t, g * dh), jnp.bfloat16),
            sd((s,), jnp.int32)).compile().memory_analysis(
            ).temp_size_in_bytes

    assert temp(da.lane_masked_decode_attention) < leaf // 4
    assert temp(da.grouped_masked_decode_attention) >= copied * leaf


def _reads_of(text, shape):
    """The instructions of a compiled program that take a value of
    ``shape`` as an operand and do work on it: not the plumbing that
    only hands it on (parameters, tuples and their elements, bitcasts,
    the loop itself), and not the inside of a fusion (the fusion is the
    instruction)."""
    import re

    plumbing = {"parameter", "tuple", "get-tuple-element", "bitcast",
                "while", "conditional", "call", "opt-barrier"}
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    rows, inside = [], None
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        made = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*)", line)
        if head:
            inside = head.group(1)
        elif made and inside not in fused:
            name, rest = made.groups()
            depth = end = 0     # the type: one word, or a tuple in brackets
            while depth or not rest[end].isspace():
                depth += {"(": 1, ")": -1}.get(rest[end], 0)
                end += 1
            op, _, operands = rest[end + 1:].partition("(")
            rows.append((name, rest[:end], op, operands, line.strip()))
    holders = {name for name, typ, _, _, _ in rows if typ.startswith(shape)}
    return [line for _, _, op, operands, line in rows if op not in plumbing
            and holders & set(re.findall(r"%[\w.\-]+", operands))]


def _olmo_delta_layer(one_chip):
    """Compiled: 80 slots of one gated delta-rule layer at
    Olmo-Hybrid-7B's widths (30 heads of 96 key and 192 value lanes,
    bf16 weights, fp32 state), both leaves donated, compiled for the
    described chip in the form the backend in force gives."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from paddle_tpu import delta_hybrid_lm as dh

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "olmo_hybrid_7b.json")) as fh:
        cfg = json.load(fh)
    d = dh.dims(cfg)
    assert d.state_shape == (15, 96, 384)
    s, p = 80, "lm_l0_"

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = {k: sd(shp, jnp.float32 if k.endswith(dh.FLOAT32_PARAMS)
               else jnp.bfloat16)
         for k, shp in dh.param_shapes(cfg).items()
         if k.startswith(p + "lin_")}

    def f(w, state, conv, x, ts):
        return dh.delta_layer_step(x, w, p, state, conv, ts, d)

    compiled = jax.jit(f, donate_argnums=(1, 2)).lower(
        w, sd((s,) + d.state_shape), sd((s, d.conv_len - 1, d.d_qkv)),
        sd((s, d.d_model)), sd((s,), jnp.int32)).compile()
    assert dh.DELTA_UPDATE_SCOPE in compiled.as_text()
    assert dh.SHORT_CONV_SCOPE in compiled.as_text()
    # whatever implements the rule: both leaves aliased in place, no
    # temporary the size of the 177 MB state, the arguments the weights,
    # the leaves and the row to the byte (``[80, 30, 96, 192]`` would hold
    # a third more: the tiled layout pads 192 lanes to 256)
    mem = compiled.memory_analysis()
    state_leaf = 4 * s * d.lin_heads * d.dk * d.dv
    conv_leaf = 4 * s * (d.conv_len - 1) * d.d_qkv
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in w.values())
    assert mem.alias_size_in_bytes >= state_leaf + conv_leaf
    assert mem.argument_size_in_bytes < (weights + state_leaf + conv_leaf
                                         + (2 << 20))
    assert mem.temp_size_in_bytes < state_leaf // 2
    return compiled


def test_delta_rule_layer_compiles_for_v5e_at_olmo_hybrid_widths(one_chip):
    """The layer as the CPU's backend builds it (the XLA form): the state
    leaf is declared two heads a row (384 lanes = 3 tiles), so the tiled
    layout pads nothing of it, both leaves are aliased in place, no
    temporary is the size of the state, the rule carries its scope's name
    for the trace — and it is TWO passes over the leaf, neither
    materialised."""
    compiled = _olmo_delta_layer(one_chip)
    reads = _reads_of(compiled.as_text(), "f32[80,15,96,384]")
    assert len(reads) == 2 and all(" fusion(" in r for r in reads), reads


def test_delta_rule_layer_takes_the_kernel_on_v5e_at_olmo_hybrid_widths(
        one_chip, monkeypatch):
    """The same layer built for a TPU: the rule is ONE custom call whose
    operands hold the state leaf as declared (``f32[80,15,96,384]``: what
    the benchmark's readers find it by), NOTHING else reads a tensor of
    that shape — the state is read once — both leaves are still aliased
    in place, no temporary is the size of the state, and the arguments
    are still unpadded to the byte."""
    import jax

    from paddle_tpu import delta_hybrid_lm as dh

    # no chip is attached: the answer is given for the backend compiled for
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    count = lambda path: dh.LOWERED.labels(path=path).value
    before = count("kernel"), count("xla")
    compiled = _olmo_delta_layer(one_chip)
    assert (count("kernel") - before[0], count("xla") - before[1]) == (1, 0)
    reads = _reads_of(compiled.as_text(), "f32[80,15,96,384]")
    assert len(reads) == 1, reads
    assert "tpu_custom_call" in reads[0] and dh.KERNEL_NAME in reads[0]


@pytest.fixture(scope="module")
def olmo_chunk(one_chip):
    """The slot pool's ``chunk`` of ``olmo_hybrid_7b`` (its one rung
    pair, the published widths, the whole 12-layer cut) as
    ``tools/decode_chunk_text.py`` builds it for a TPU, compiled ONCE for
    the tests below: ``(text, memory analysis, counted)`` — ``counted``
    what the trace added to the two lowering counters, by path."""
    from paddle_tpu import delta_hybrid_lm as dh

    return _compiled_chunk("olmo_hybrid_7b", 12, {
        (name, path): c.labels(path=path)
        for name, c in (("delta", dh.LOWERED), ("full", da.UNGROUPED_LOWERED))
        for path in ("kernel", "xla")})


def test_olmo_hybrid_chunk_holds_nine_kernel_calls_a_step_on_v5e(olmo_chunk):
    """Nine calls of the delta rule's kernel a step, one a linear layer,
    each over a state leaf as declared, no second read of a state leaf,
    every leaf aliased in place, and the counter says ``kernel`` nine
    times a traced step and ``xla`` never."""
    from paddle_tpu import delta_hybrid_lm as dh

    text, mem, counted = olmo_chunk
    traced = counted["delta", "kernel"]
    assert traced >= 9 and traced % 9 == 0 and not counted["delta", "xla"]
    reads = _reads_of(text, "f32[80,15,96,384]")
    assert len(reads) == 9, reads
    assert all("tpu_custom_call" in r and dh.KERNEL_NAME in r for r in reads)
    # nine states, nine conv windows and the three full layers' K and V
    pool = 9 * 4 * 80 * (15 * 96 * 384 + 3 * 11520) + 6 * 2 * 80 * 1024 * 3840
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 4 * 80 * 15 * 96 * 384 * 4


def test_olmo_hybrid_chunk_reads_the_full_layers_by_the_kernel_on_v5e(
        olmo_chunk):
    """The same chunk's three full layers (ONE query head a K/V head
    over bf16 leaves): THREE calls of the attention kernel a step, each
    over both ``bf16[80,1024,3840]`` leaves as they lie; nothing
    float32 of a leaf's shape anywhere in the program (the per-head XLA
    form copied each leaf to one, the lane form held scores over the
    whole rung), what else touches a leaf is its append; every K/V leaf
    is still aliased in place; the counter says ``kernel`` three times a
    traced step and ``xla`` never."""
    text, mem, counted = olmo_chunk
    traced = counted["full", "kernel"]
    assert traced >= 3 and traced % 3 == 0 and not counted["full", "xla"]
    calls = [line for line in text.splitlines()
             if "grouped_decode_attention" in line and "custom-call(" in line]
    assert len(calls) == 3, len(calls)
    assert all(c.count("bf16[80,1024,3840]") >= 2 for c in calls)
    assert "f32[80,1024,3840]" not in text and "f32[80,30,1024]" not in text
    reads = _reads_of(text, "bf16[80,1024,3840]")
    others = [r for r in reads if "grouped_decode_attention" not in r]
    assert len(reads) - len(others) == 3
    assert len(others) == 6 and all(      # the append: a row a leaf
        " scatter(" in r or " fusion(" in r or "dynamic-update-slice(" in r
        for r in others), others
    assert mem.alias_size_in_bytes >= 6 * 80 * 1024 * 3840 * 2


@pytest.fixture(scope="module")
def solar_chunk(one_chip):
    """The slot pool's ``chunk`` of ``solar_open2_250b`` (its one rung
    pair, the published widths, the whole 4-layer cut: G, K, K, K, 40
    held experts of 320) as ``tools/decode_chunk_text.py`` builds it for
    a TPU, compiled ONCE: ``(text, memory analysis, counted)``."""
    from paddle_tpu import delta_hybrid_lm as dh

    return _compiled_chunk("solar_open2_250b", 4, {
        **{("path", p): dh.LOWERED.labels(path=p) for p in ("kernel", "xla")},
        **{("decay", k): dh.DECAY.labels(decay=k)
           for k in ("head", "channel")}})


def test_solar_open2_chunk_takes_the_kernel_with_a_decay_a_channel_on_v5e(
        solar_chunk):
    """Three calls of the delta rule's kernel a step, one a K layer, each
    over a state leaf ``f32[256,64,128,128]`` as declared, with the decay
    laid as a third column beside k and q (``f32[64,2,3,128,128]``); no
    other instruction reads a state leaf, none copies one or a rung-long
    K/V leaf; every leaf is aliased in place; the G layer's read is ONE
    call of the grouped attention kernel over both bf16 leaves; the
    experts are the grouped product; and the whole cut fits the chip."""
    from paddle_tpu import delta_hybrid_lm as dh
    from paddle_tpu import grouped_matmul as gm

    text, mem, counted = solar_chunk
    traced = counted["path", "kernel"]
    assert traced >= 3 and traced % 3 == 0 and not counted["path", "xla"]
    assert counted["decay", "channel"] == traced
    assert not counted["decay", "head"]
    reads = _reads_of(text, "f32[256,64,128,128]")
    assert len(reads) == 3, reads
    assert all("tpu_custom_call" in r and dh.KERNEL_NAME in r
               and "f32[64,2,3,128,128]" in r for r in reads)
    copies = [line for line in text.splitlines() if " copy(" in line]
    assert not any(shape in line for line in copies for shape in (
        "[256,64,128,128]", "[256,2048,1024]", "[256,3,24576]"))
    calls = [line for line in text.splitlines()
             if "grouped_decode_attention" in line and "custom-call(" in line]
    assert len(calls) == 1 and calls[0].count("bf16[256,2048,1024]") >= 2
    assert "f32[256,2048,1024]" not in text
    assert sum(gm.KERNEL_NAME in line and "custom-call(" in line
               for line in text.splitlines()) == 2 * 4
    # three states, three conv windows, the G layer's K and V
    pool = 3 * 4 * 256 * (64 * 128 * 128 + 3 * 24576) + 2 * 2 * 256 * 2048 * 1024
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 0.5e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes) < 13.5e9


def test_k_row_ring_read_copies_no_ring_and_no_weight_on_v5e(one_chip):
    """The self-drafting round of ``k_exaone_236b_a23b`` at its published
    widths and the cell's own depth (128 slots, rings of 128 rows, 64
    query heads over 8 K/V heads of 128, ``d_model`` 6144, K = 2; the
    whole 5-layer cut — four window layers around the global one — and
    the module): each window block reads both ``bf16[128,128,1024]`` ring
    leaves AS THEY LIE through ONE ``ring_rows_decode_attention`` custom
    call that takes the two leaves themselves as operands — the K rows
    beside the query heads of their K/V head, the old ring and the fresh
    rows scored apart — and nothing else but the appends (and the
    compiler's own fetch of a leaf ahead of its call) reads a ring leaf;
    the compiled round holds no copy of a ring leaf or of an
    ``attn_q`` (``[6144,8192]``) or ``attn_k`` (``[6144,1024]``) matrix
    either way round (the tool's count, from the program's own shapes)
    and no run of ring + K = 130 keys in any tensor.  Before PR 59 the
    same program held two copies a window layer of the ring leaves, the
    130-row run re-laid by heads, and a transposed copy of both matrices
    in every layer fed by a block before it: 16."""
    import re

    text, _, counted = _compiled_chunk("k_exaone_236b_a23b", 5, {
        "rows": da.RING_LOWERED.labels(form="rows"),
        "ring": da.ROWS_LOWERED.labels(leaf="ring")}, kind="spec_chunk")
    assert counted["rows"] == counted["ring"] == 4      # one a window layer
    assert counted["relayouts"] == (0, 0)     # a step, once a call
    assert not re.search(r"\[(?:\d+,)*130(?:,\d+)*\]", text)
    reads = _reads_of(text, "bf16[128,128,1024]")
    calls = [r for r in reads if "ring_rows_decode_attention" in r]
    assert len(calls) == 4 and all(
        "tpu_custom_call" in c and c.count("bf16[128,128,1024]") >= 2
        for c in calls), calls
    # the compiler may fetch a leaf ahead of its call (slices of it)
    others = [r for r in reads if r not in calls and " slice-start(" not in r]
    assert len(others) == 8 and all(    # the append: a row a leaf
        " scatter(" in r or " fusion(" in r or "dynamic-update-slice(" in r
        for r in others), others


def test_lfm2_chunk_reads_its_64_lane_heads_by_the_kernel_on_v5e(one_chip):
    """The slot pool's ``chunk`` of ``lfm2_24b_a2b`` at its published
    widths (its one rung pair; two layers: the dense conv layer and the
    first attention layer with its 64 experts): the attention's read is
    ONE call of the grouped kernel a step over both ``bf16[256,2048,512]``
    leaves as they lie — 32 query heads over 8 K/V heads of 64 lanes,
    ONE unit of 64 rows x 512 lanes, a lane tile of context two heads —
    no ``f32[256,32,2048]`` scores over the whole rung (the lane-masked
    form's), no copy of a leaf, and the counter says ``kernel``."""
    text, mem, counted = _compiled_chunk("lfm2_24b_a2b", 2, {
        path: da.GROUPED_LOWERED.labels(path=path)
        for path in ("kernel", "xla")})
    assert counted["kernel"] >= 1 and not counted["xla"]
    lines = text.splitlines()
    calls = [line for line in lines
             if "grouped_decode_attention" in line and "custom-call(" in line]
    assert len(calls) == 1 and calls[0].count("bf16[256,2048,512]") >= 2
    assert "bf16[256,1,64,512]" in calls[0]         # q: units, R, L
    assert "f32[256,4,8,128]" in calls[0]           # two heads a lane tile
    assert "f32[256,32,2048]" not in text and "f32[256,2048,512]" not in text
    assert not [line for line in lines
                if " copy(" in line and "[256,2048,512]" in line]
    leaf = 256 * 2048 * 512 * 2
    assert mem.alias_size_in_bytes >= 2 * leaf
    assert mem.temp_size_in_bytes < leaf // 4


@pytest.mark.parametrize("lanes,copied", [(640, False), (576, True)],
                         ids=["whole_tiles", "as_wide_as_the_row"])
def test_a_latent_leaf_of_whole_lane_tiles_is_not_copied_on_v5e(
        one_chip, lanes, copied):
    """Why ``latent_leaves`` rounds a row up to whole 128-lane tiles.  24
    slots x 32768 positions of a 512 + 64 lane latent row
    (``deepseek_v3_2``), eight steps of append, index scoring, top-2048
    and selected read in one loop, as the pool's ``chunk`` runs them: a
    leaf DECLARED 576 lanes wide (4.5 tiles) is re-laid sequence-minor
    inside the loop and copied whole there and back (five 0.96 GB copies
    in the cell's compiled chunk, which then did not fit the chip: PR
    54); at 640 lanes the step's temporaries are a fraction of a leaf and
    the index scoring's ``[24, 64, 32768]`` products never reach HBM.
    Since PR 55 the selection is a threshold and a compaction: no
    ``sort`` of the rung is left in the loop (there were 75.7 MB of
    temporaries with ``lax.top_k``'s, most of them the sort's operands),
    and the read's gather is told its list is sorted."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import latent_sparse_lm as ls

    s, t, h, row_w, top_k = 24, 32768, 128, 576, 2048

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(q, row, ki, qi, wi, latent, index_k, ts):
        def body(i, carry):
            kv = da.append_latent_rows(
                {"latent": carry[0], "index_k": carry[1]}, row, ki, ts + i)
            scores = ls.index_scores(qi, wi, kv["index_k"])
            sel, valid = ls.select_positions(scores, ts + i, top_k)
            u = da.selected_latent_attention(q, kv, ts + i, sel, valid,
                                             d_value=512, scale=0.135)
            return kv["latent"], kv["index_k"], carry[2] + u
        return jax.lax.fori_loop(0, 8, body, (
            latent, index_k, jnp.zeros((s, h, 512), jnp.float32)))

    compiled = jax.jit(f, donate_argnums=(5, 6)).lower(
        sd((s, h, row_w)), sd((s, row_w)), sd((s, 128)), sd((s, 64, 128)),
        sd((s, 64)), sd((s, t, lanes), jnp.bfloat16),
        sd((s, t, 128), jnp.bfloat16), sd((s,), jnp.int32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    leaf = s * t * lanes * 2
    assert (temp >= leaf) == copied
    text = compiled.as_text()
    assert " sort(" not in text
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert gathers and all("indices_are_sorted=true" in g for g in gathers)
    if not copied:
        assert temp < leaf // 4
        assert temp <= 75_700_000       # what the sorting form held
        # the per-head index products stay inside their fusion
        assert not _reads_of(text, "f32[24,64,32768]")


@pytest.mark.parametrize("kind,layers", [("spec_chunk", 5), ("prefill", 5)])
def test_dense_latent_round_and_prefill_fit_v5e_and_copy_no_leaf(
        one_chip, kind, layers):
    """The self-drafting round and the chunked prefill of
    ``openpangu_ultra_moe_718b`` at its published widths and the cell's
    own depth (32 slots x 16384 positions, 128 heads over one 640-lane
    row a position, ``d_model`` 7680, K = 2; the whole 5-layer cut and
    the module): both fit 16 GB beside the pool and the snapshots, no
    ``copy`` bears a latent leaf's shape and no tensor holds a rung-wide
    score (``[32,2,128,16384]`` / ``[32,256,16384]``, 537 MB a layer).
    The round's dense read is the Pallas kernel's, once a leaf
    (``dense_kernel`` counted ``layers + 1`` times, ``dense_xla`` never):
    ONE ``dense_latent_attention`` custom call a leaf that takes the
    ``bf16[32,16384,640]`` leaf as it lies and the queries
    ``bf16[32,256,640]`` and returns ``f32[32,256,512]`` — the two shapes
    the benchmark's readers find the read by
    (``benchmark/families/pooled_latent_mtp_lm.py``:
    ``dense_latent_shapes``), so ``dense_latent_roofline.serve`` and
    ``latent_attention_time_share.serve`` go on reading — and no XLA
    instruction holds a key block's scores any more.  The chunked prefill
    is expanded and takes neither lowering."""
    text, mem, counted = _compiled_chunk("openpangu_ultra_moe_718b", layers, {
        "kernel": da.LATENT_LOWERED.labels(path="dense_kernel"),
        "dense": da.LATENT_LOWERED.labels(path="dense_xla"),
        "selected": da.LATENT_LOWERED.labels(path="xla")}, kind=kind)
    lines = text.splitlines()
    copies = [line for line in lines if " copy(" in line]
    assert not any("[32,16384,640]" in line for line in copies), copies
    assert "[32,16384,640]{1," not in text      # never sequence-minor
    for scores in ("[32,2,128,16384]", "[32,256,16384]", "[32,128,16384]",
                   "[512,128,16384]"):
        assert scores not in text, scores
    snapshots = 8 * 6 * 16384 * 640 * 2
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes + snapshots) < 15.0e9
    pool = 32 * 6 * 16384 * 640 * 2
    assert mem.alias_size_in_bytes >= pool
    assert not counted["selected"] and not counted["dense"]
    calls = [line for line in lines
             if "dense_latent_attention" in line and "custom-call(" in line]
    if kind == "spec_chunk":
        assert counted["kernel"] == layers + 1  # every layer and the module
        assert len(calls) == layers + 1
        for call in calls:
            assert "tpu_custom_call" in call
            assert " = f32[32,256,512]" in call             # the context
            assert "bf16[32,256,640]" in call               # the queries
            assert "bf16[32,16384,640]" in call             # the leaf
        # a key block's slice of every slot's leaf was the XLA walk's
        assert "[32,512,640]" not in text
        assert mem.temp_size_in_bytes < 0.1e9
    else:
        assert not counted["kernel"] and not calls  # expanded, by key blocks
        assert mem.temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("config,kind,rows,products,matrices", [
    ("openpangu_ultra_moe_718b", "spec_chunk", 64, {24576: 6},
     ((1536, 24576), (16384, 7680), (18432, 7680))),
    ("deepseek_v3_2", "chunk", 24, {24576: 5, 8192: 5},
     ((1536, 24576), (1536, 8192)))],
    ids=["openpangu_round", "deepseek_chunk"])
def test_a_latent_projection_is_multiplied_as_its_matrix_is_stored_on_v5e(
        one_chip, config, kind, rows, products, matrices):
    """The self-drafting round of ``openpangu_ultra_moe_718b`` and the
    ``chunk`` of ``deepseek_v3_2`` at their published widths and the
    cells' own depth (the whole 5-layer cuts, the module): the queries'
    up projection ``attn_q_b`` ``[1536,24576]`` — and the indexer's
    ``index_q`` ``[1536,8192]`` from the same low rank — is ONE plain
    product a layer over the matrix as stored, complete before the
    reshape by heads sees it (the barrier in
    ``latent_sparse_lm.latent_inputs`` / ``index_inputs``), and the
    compiled program holds no ``copy`` of a weight-shaped matrix either
    way round, a step or once a call (the tool's count, from the
    program's own shapes).  Before PR 64 the product was laid by heads
    for the per-head ``attn_uk`` product behind it and read its matrix
    the other way round: six 75.5 MB copies a round there (0.65 ms of
    20.9), ten hoisted out of the step loop here (0.5 GB of temporaries
    in a cell whose peak read 100.5% of the chip)."""
    import re

    text, mem, counted = _compiled_chunk(config, 5, {}, kind=kind)
    assert counted["relayouts"] == (0, 0)     # a step, once a call
    copies = [line for line in text.splitlines() if " copy(" in line]
    for a, b in matrices:
        for shape in ("[%d,%d]" % (a, b), "[%d,%d]" % (b, a)):
            assert not any(shape in line for line in copies), shape
    for width, n in products.items():
        assert len(re.findall(
            r"= f32\[%d,%d\]\{1,0[^}]*\} convolution\(" % (rows, width),
            text)) == n, width
    assert mem.temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("kind", ["chunk", "prefill"])
def test_kda_latent_chunk_and_prefill_fit_v5e_and_copy_no_leaf(one_chip,
                                                               kind):
    """The ``chunk`` and the chunked prefill of ``kimi_linear_48b_a3b`` at
    its published widths and the cell's own depth (96 slots x 32768
    positions; the whole 8-layer cut: K with the dense FFN, K, K, M, K, K,
    K, M; 16 held experts of 256): both fit 16 GB beside the pool and the
    eight whole-row snapshots, every leaf is aliased in place, no ``copy``
    bears a latent or a state leaf's shape and no tensor holds a rung-wide
    score a head.  A step is SIX calls of the delta rule's kernel, each
    the only reader of its ``f32[96,32,128,128]`` leaf with the decay a
    third column, and TWO ``dense_latent_attention`` custom calls at K =
    1, each taking its ``bf16[96,32768,640]`` leaf as it lies and the
    queries ``bf16[96,32,640]`` and returning ``f32[96,32,512]`` (the
    shapes ``benchmark/families/pooled_kda_latent_lm.py`` finds the read
    by).  The prefill takes neither kernel: SIX chunk forms (a decay a
    channel; ``InvertDiagBlocksLowerTriangular``: the forward
    substitution), their pairwise decays ``f32[64,64,32,128]`` inside
    fusions, and the latent prefill expanded by key blocks."""
    from paddle_tpu import delta_hybrid_lm as dh
    from paddle_tpu import grouped_matmul as gm

    text, mem, counted = _compiled_chunk("kimi_linear_48b_a3b", 8, {
        **{("path", p): dh.LOWERED.labels(path=p)
           for p in ("kernel", "xla", "chunk")},
        **{("decay", k): dh.DECAY.labels(decay=k)
           for k in ("head", "channel")},
        "dense_kernel": da.LATENT_LOWERED.labels(path="dense_kernel"),
        "dense_xla": da.LATENT_LOWERED.labels(path="dense_xla")}, kind=kind)
    lines = text.splitlines()
    copies = [line for line in lines if " copy(" in line]
    assert not any(shape in line for line in copies for shape in (
        "[96,32768,640]", "[96,32,128,128]", "[96,3,12288]")), copies
    assert "[96,32768,640]{1," not in text      # never sequence-minor
    for scores in ("[96,32,32768]", "[512,32,32768]", "[32,512,32768]"):
        assert scores not in text, scores
    slot = 2 * 32768 * 640 * 2 + 6 * 4 * (32 * 128 * 128 + 3 * 12288)
    assert mem.alias_size_in_bytes >= 96 * slot
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes + 8 * slot) < 13.5e9
    assert mem.temp_size_in_bytes < 0.3e9
    assert not counted["decay", "head"] and not counted["path", "xla"]
    assert not counted["dense_xla"]
    kernels = [line for line in lines if "tpu_custom_call" in line
               and "custom-call(" in line]
    delta = [line for line in kernels if dh.KERNEL_NAME in line]
    dense = [line for line in kernels if "dense_latent_attention" in line]
    # two grouped products a sparse layer; the prefill makes no logits, so
    # its last layer's FFN feeds nothing and is not in the program
    assert sum(gm.KERNEL_NAME in line for line in kernels) == 2 * (
        7 if kind == "chunk" else 6)
    if kind == "chunk":
        assert counted["path", "kernel"] == counted["decay", "channel"] == 6
        assert not counted["path", "chunk"]
        assert counted["dense_kernel"] == 2
        assert len(delta) == 6 and len(dense) == 2
        assert [r.strip() for r in _reads_of(
            text, "f32[96,32,128,128]")] == [r.strip() for r in delta]
        assert all("f32[32,1,3,128,128]" in line for line in delta)
        for call in dense:
            assert " = f32[96,32,512]" in call              # the context
            assert "bf16[96,32,640]" in call                # the queries
            assert "bf16[96,32768,640]" in call             # the leaf
    else:
        assert counted["path", "chunk"] == counted["decay", "channel"] == 6
        assert not counted["path", "kernel"] and not counted["dense_kernel"]
        assert not delta and not dense
        assert text.count("InvertDiagBlocksLowerTriangular") >= 6
        assert "f32[64,64,32,128]" in text


@pytest.mark.parametrize("config,kind", [
    ("k_exaone_236b_a23b", "spec_chunk"), ("k_exaone_236b_a23b", "chunk"),
    ("openpangu_ultra_moe_718b", "spec_chunk"),
    ("openpangu_ultra_moe_718b", "chunk")])
def test_a_chunk_returns_the_view_beside_a_state_aliased_whole_on_v5e(
        one_chip, config, kind):
    """The scheduler's view (``kv_pool.with_view``: ``tokens``, ``pos``,
    ``active``, ``finished``, ``n_gen`` and the builder's
    ``expert_stats``) leaves the ``chunk`` and the self-drafting round of
    both cells whose tick is one round as ONE output of its own — a
    packed int32 vector the program writes, alive after the state is
    donated to the next dispatch — at the cells' own depth (the whole
    5-layer cuts): every leaf of the state is still aliased to its
    argument, in place, and no ``copy`` bears the shape of a cache leaf
    (the vector is all the parent's text lacks:
    ``tools/decode_chunk_text.py`` at both checkouts)."""
    import re

    import jax

    with _chunk_tool() as (tool, root):
        lowered = tool.lowered_chunk(root, config, kind, 5)
        compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    state = lowered.args_info[0][2]
    leaves = jax.tree.leaves(state)
    header = text.splitlines()[0]
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", header)
    assert aliased.group(1).count("-alias)") == len(leaves)
    state_bytes = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                      for a in leaves)
    assert mem.alias_size_in_bytes >= state_bytes
    (entry,) = [line for line in text.splitlines()
                if line.startswith("ENTRY ")]
    outputs = entry.rsplit(") -> (", 1)[1]
    assert len(re.findall(r"\w+\[[\d,]*\]", outputs)) == len(leaves) + 1
    # what is not aliased: the view (tokens above all) and little else
    s, t = state["tokens"].shape
    assert 0 < mem.output_size_in_bytes - mem.alias_size_in_bytes < (
        2 * s * t * 4)
    stats = state["cache"]["expert_stats"].shape
    assert "s32[%d]" % (s * t + 4 * s + stats[0] * stats[1]) in outputs
    # (a ``copy-start`` of a leaf is the compiler's fetch ahead of a call)
    copies = [line for line in text.splitlines() if " copy(" in line]
    for leaf in jax.tree.leaves(state["cache"]):
        if len(leaf.shape) >= 3:
            shape = "[%s]" % ",".join(map(str, leaf.shape))
            assert not any(shape in line for line in copies), shape
