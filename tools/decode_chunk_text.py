#!/usr/bin/env python
"""The compiled ``chunk`` program of a decode cell, as text that two
checkouts can be compared by.

A change that must not move a decode cell (a refactor of the step, the
cache format, the slot pool) shows it before any chip run: the pool's
``chunk`` executable — ``decoding.make_slot_decode_fns`` over the cell's
step at its ONE rung pair, at the configuration's published widths and
two layers (the layers are a Python loop) — is compiled here, without a
chip, for a described v5e, and written with everything that only names a
source position taken out:

* the file / function / location / stack-frame tables and every
  ``metadata={...}``;
* the Mosaic kernel's serialized body, replaced by its MLIR printed
  without debug info (the bytecode embeds the call stack's lines);
* instruction numbering (``%ge.25`` against ``%ge.21``): every ``%name``
  is renamed by order of first appearance.

    python tools/decode_chunk_text.py gpt1_117m /tmp/a.txt
    python tools/decode_chunk_text.py gpt1_117m /tmp/b.txt --repo ../parent
    cmp /tmp/a.txt /tmp/b.txt

``--repo`` imports ``paddle_tpu`` and reads ``benchmark/configs`` from
another checkout (this file need not exist there).  Nothing runs: equal
text says the two checkouts hand the chip the same program, not how fast
it is.

The last line also counts the instructions that cast a whole
weight-shaped matrix to bf16: work a ``chunk`` call repeats when its
step does not hold its weights in the dtype of their products.
``gpt1_117m`` (two layers): 13 before PR 35 (six a layer and the head),
0 since — its builder makes those copies once, at build, which is why
the tool builds THAT step outside the program and hoists what it closes
over, as the pool does; ``falcon_h1_34b`` and ``minicpm_sala`` multiply
weights as stored: 0, and their text was equal at PR 35 and its parent.

It also counts the ``copy`` instructions whose operand is shaped like
one of the program's matrices (either way round) or like a leaf of its
cache (:func:`relayouts`), so that a comparison of two texts NAMES a
relayout: those the program repeats every step (inside its step loop, or
anywhere where it has none), and apart those the compiler hoisted out of
the loop (once a call).  ``k_exaone_236b_a23b --kind spec_chunk
--layers 5``: 16 a step at PR 59's parent (both ring leaves of each of
four window layers, and ``attn_q`` ``[6144,8192]`` and ``attn_k``
``[6144,1024]`` transposed in each of the four layers fed by a block
before them) -> 0 since.  ``openpangu_ultra_moe_718b --kind spec_chunk
--layers 5``: 6 a step at PR 64's parent (``attn_q_b`` ``[1536,24576]``
re-laid for a product laid by heads, once a block) -> 0 since;
``deepseek_v3_2 --layers 5``: 10 once a call (``attn_q_b`` and
``index_q`` ``[1536,8192]``, a layer each) -> 0.  ``falcon_h1_34b``: 0 a
step, 2 once a call;
``gpt1_117m --kind seat_prefill``: 2 a step (inside the scanned body of
its blocks), 1 once a call.
"""
import argparse
import base64
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"

_TABLES = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}


def _kernel_asm(match):
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        mod = ir.Module.parse(base64.b64decode(match.group(1)))
        asm = mod.operation.get_asm(enable_debug_info=False)
    return '"body":%s' % json.dumps(asm)


def canonical(hlo_text: str) -> str:
    lines, skipping = [], False
    for line in hlo_text.splitlines():
        if line.strip() in _TABLES:
            skipping = True
        elif skipping:
            skipping = bool(line.strip())
        else:
            lines.append(re.sub(r",? ?metadata=\{[^}]*\}", "", line))
    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _kernel_asm,
                  "\n".join(lines))
    names = {}
    return re.sub(
        r"%[A-Za-z_][\w.\-]*",
        lambda m: names.setdefault(m.group(0), "%%v%d" % len(names)), text)


def lowered_chunk(repo: str, config: str, kind: str = "chunk",
                  layers: int = 2):
    sys.path.insert(0, repo)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu import decoding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the step asks the default backend which attention to build; no
    # chip is attached here, so the answer is given for it
    jax.default_backend = lambda: "tpu"

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    with open(os.path.join(repo, "benchmark", "configs",
                           config + ".json")) as fh:
        cfg = json.load(fh)
    sv = cfg["serving"]
    (slots,), (seq_len,) = sv["slot_ladder"], sv["len_ladder"]
    if cfg["family"] == "pooled_sparse_linear_lm":
        from paddle_tpu import sparse_linear_lm

        # one layer of each kind first: the first ``layers`` entries
        cfg["mixer_types"] = cfg["mixer_types"][:layers]
        cfg["num_hidden_layers"] = len(cfg["mixer_types"])
        weights = {n: sd(shp, jnp.bfloat16 if len(shp) == 2
                         else jnp.float32)
                   for n, shp in sparse_linear_lm.param_shapes(cfg).items()}

        def build(w):
            return decoding.make_sparse_linear_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"],
                state_dtype=cfg["assumed"]["lightning_state_dtype"],
                prefill_tokens=sv["prefill_tokens"])[:2]
    elif cfg["family"] == "pooled_routed_conv_lm":
        from paddle_tpu import routed_experts

        # the first ``layers`` of the cut (2: the dense conv layer and an
        # attention layer with its experts; 9: the whole cut)
        cfg["layer_types"] = cfg["layer_types"][:layers]
        cfg["num_hidden_layers"] = len(cfg["layer_types"])
        # as the family makes them: matrices bf16, the rest fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            routed_experts.FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in routed_experts.param_shapes(cfg).items()}

        def build(w):
            return decoding.make_routed_conv_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"])
    elif cfg["family"] == "pooled_windowed_routed_lm":
        from paddle_tpu import windowed_routed_lm

        # the first ``layers`` of the cut (2: a global and a window
        # layer, each with its experts; 8: the whole cut)
        for key in ("sliding_window_layout", "rope_layout"):
            cfg[key] = cfg[key][:layers]
        cfg["num_hidden_layers"] = layers
        # as the family makes them: matrices bf16, norms and routers fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            windowed_routed_lm.FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in windowed_routed_lm.param_shapes(cfg).items()}

        def build(w):
            return decoding.make_windowed_routed_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"],
                prefill_tokens=sv["prefill_tokens"])[:2]
    elif cfg["family"] == "pooled_mtp_routed_lm":
        from paddle_tpu import mtp_routed_lm

        # the first ``layers`` of the cut (2: the dense window layer and
        # a sparse window layer; 5: the whole cut) and the module
        for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
            cfg[key] = cfg[key][:layers]
        cfg["num_hidden_layers"] = layers
        held = tuple(cfg["experts_held"])
        # as the family makes them: matrices bf16, norms, routers and
        # biases fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            mtp_routed_lm.FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in mtp_routed_lm.param_shapes(cfg, held=held).items()}

        def build(w):
            return decoding.make_mtp_routed_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"], held=held,
                prefill_tokens=sv["prefill_tokens"])[:2]
    elif cfg["family"] == "pooled_latent_sparse_lm":
        from paddle_tpu import latent_sparse_lm

        # the first ``layers`` of the cut (2: the dense layer and a sparse
        # one, each latent attention behind its indexer; 5: the whole cut)
        cfg["num_hidden_layers"] = layers
        held = tuple(cfg["experts_held"])
        # as the family makes them: matrices bf16, norms, the indexer's
        # LayerNorm, routers and biases fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            latent_sparse_lm.FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in latent_sparse_lm.param_shapes(
                cfg, held=held).items()}

        def build(w):
            return decoding.make_latent_sparse_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"], held=held,
                prefill_tokens=sv["prefill_tokens"])[:2]
    elif cfg["family"] == "pooled_latent_mtp_lm":
        from paddle_tpu import latent_mtp_lm

        # the first ``layers`` of the cut (2: the dense layer and a sparse
        # one, each latent attention read densely; 5: the whole cut) and
        # the module
        cfg["num_hidden_layers"] = layers
        held = tuple(cfg["experts_held"])
        # as the family makes them: matrices bf16, norms and routers fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            latent_mtp_lm.FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in latent_mtp_lm.param_shapes(cfg, held=held).items()}

        def build(w):
            return decoding.make_latent_mtp_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"], held=held,
                prefill_tokens=sv["prefill_tokens"])[:2]
    elif cfg["family"] == "pooled_kda_latent_lm":
        from paddle_tpu import kda_latent_lm

        # the first ``layers`` of the cut (4: the dense K layer, two K
        # layers and an M layer; 8: the whole cut, two periods); the two
        # layer lists are 1-indexed
        lin = cfg["linear_attn_config"]
        for key in ("kda_layers", "full_attn_layers"):
            lin[key] = [i for i in lin[key] if i <= layers]
        cfg["num_hidden_layers"] = layers
        held = tuple(cfg["experts_held"])
        # as the family makes them: matrices bf16, vectors, the conv
        # kernel, the router and its bias fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            kda_latent_lm.FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in kda_latent_lm.param_shapes(cfg, held=held).items()}

        def build(w):
            return decoding.make_kda_latent_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"], held=held,
                prefill_tokens=sv["prefill_tokens"])[:2]
    elif cfg["family"] == "pooled_delta_hybrid_lm":
        from paddle_tpu import delta_hybrid_lm

        # the first ``layers`` of the cut (4: one period of three linear
        # layers and a full one; 12: the whole cut)
        cfg["layer_types"] = cfg["layer_types"][:layers]
        cfg["num_hidden_layers"] = len(cfg["layer_types"])
        # as the family makes them: matrices bf16, the rest fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            delta_hybrid_lm.FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in delta_hybrid_lm.param_shapes(cfg).items()}

        def build(w):
            return decoding.make_delta_hybrid_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"])
    elif cfg["family"] == "pooled_kda_routed_lm":
        from paddle_tpu import delta_hybrid_lm

        # the first ``layers`` of the cut (2: the G layer and a K layer,
        # each with its experts; 4: the whole cut, one period)
        cfg["num_hidden_layers"] = layers
        held = tuple(cfg["experts_held"])
        # as the family makes them: matrices bf16, vectors, the conv
        # kernel, the router and its bias fp32
        weights = {n: sd(shp, jnp.float32 if n.endswith(
            delta_hybrid_lm.KDA_FLOAT32_PARAMS) else jnp.bfloat16)
            for n, shp in delta_hybrid_lm.kda_param_shapes(
                cfg, held=held).items()}

        def build(w):
            return decoding.make_kda_routed_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"], held=held)
    elif cfg["family"] == "pooled_hybrid_ssm_lm":
        from paddle_tpu import hybrid_ssm

        cfg["num_hidden_layers"] = layers
        # as the family makes them: matrices bf16, vectors and the conv
        # kernel fp32
        weights = {n: sd(shp, jnp.bfloat16 if len(shp) == 2
                         and not n.endswith("conv_w") else jnp.float32)
                   for n, shp in hybrid_ssm.param_shapes(cfg).items()}

        def build(w):
            return decoding.make_hybrid_ssm_lm_pooled_step_fn(
                w, cfg, kv_dtype=sv["kv_dtype"],
                ssm_state_dtype=cfg["assumed"]["ssm_state_dtype"])
    else:
        import numpy as np

        dims = (cfg["vocab_size"], cfg["n_embd"], layers, cfg["n_head"],
                cfg["assumed"]["n_inner"])
        # this builder may work at BUILD (one bf16 copy of every matrix
        # it multiplies), so it is built outside the program, over
        # weights that exist (here, on the CPU), and the program takes
        # what the step closes over as arguments (``hoisted`` below)
        parts = decoding.make_transformer_lm_pooled_step_fn(
            decoding.random_transformer_lm_state(
                np.random.RandomState(0), *dims, cfg["n_positions"]),
            *dims, kv_dtype=sv["kv_dtype"])
        weights = {}

        def build(w):
            return parts

    def viewed(fn, w):
        # the scheduler's view as outputs of their own beside the state,
        # as the pool compiles a ``chunk`` / ``spec_chunk`` since PR 62
        # (``kv_pool.with_view``; a checkout from before it: the state)
        from paddle_tpu.serving import kv_pool

        with_view = getattr(kv_pool, "with_view", None)
        return fn if with_view is None else with_view(
            fn, decoding.spec_of(build(w)[1]).expert_stats)

    def chunk(w, state):
        step_fn, _ = build(w)
        return viewed(decoding.make_slot_decode_fns(
            step_fn, int(cfg["vocab_size"]), sv["steps_per_tick"])[0],
                      w)(state)

    def prefill(w, state):
        # one slot's next chunk of prompt tokens, as the pool runs it
        # (KVSlotPool._prefill_fn) for a builder that declares one
        fn = decoding.spec_of(build(w)[1]).prefill_fn
        c = fn.chunk_tokens
        return dict(state, cache=fn(
            state["cache"], jnp.int32(3),
            jax.lax.dynamic_slice(
                state["tokens"], (3, c),
                (1, c + getattr(fn, "lookahead", 0)))[0],
            state["pos"][3], jnp.int32(c)))

    def spec_chunk(w, state):
        # one self-drafting round, as the pool runs it for a builder
        # that declares its verify and its module (k_exaone_236b_a23b)
        from paddle_tpu.serving.speculative import make_self_draft

        return viewed(pool_of(w, speculative=make_self_draft(
            build(w)[1]))._spec_chunk_fn, w)(state)

    def self_drafts(w):
        return decoding.spec_of(build(w)[1]).verify_fn is not None

    def admit_prefix(w, state, mask, prompt, prompt_len, total_len, kv,
                     prefix_len, *spec_flag):
        # one request seated over a snapshot, as the pool runs it for a
        # builder with a chunked prefill: under a self-drafting round
        # where the builder declares one (openpangu_ultra_moe_718b),
        # plain where it does not (kimi_linear_48b_a3b: the snapshot
        # carries recurrent leaves)
        from paddle_tpu.serving.speculative import make_self_draft

        kw = ({"speculative": make_self_draft(build(w)[1])}
              if self_drafts(w) else {})
        return pool_of(w, prefix=True, **kw)._admit_prefix_fn(
            state, mask, prompt, prompt_len, total_len, kv, prefix_len,
            *spec_flag)

    def snapshot(w, state, slot):
        # a slot's whole row of every leaf, copied, as the pool keeps a
        # prefix over a builder with a chunked prefill
        return pool_of(w, prefix=True)._snapshot_fn(state, slot)

    def seat_prefill(w, state, packed):
        # a turn's seats seated and fed their prompts, as the pool runs
        # it (KVSlotPool._seat_prefill_fn) for a builder that declares a
        # batched prefill (gpt1_117m)
        return pool_of(w)._seat_prefill_fn(state, packed)

    def pool_of(w, **kw):
        from paddle_tpu.serving.kv_pool import KVSlotPool

        return KVSlotPool(
            *build(w)[:2], eos_id=int(cfg["vocab_size"]), max_slots=slots,
            max_seq_len=seq_len, slot_ladder=(slots,), len_ladder=(seq_len,),
            steps=sv["steps_per_tick"], kv_dtype=sv["kv_dtype"], **kw)

    i32, flag = jnp.int32, jnp.bool_
    state = {
        "cache": jax.tree.map(
            lambda l: sd(l.shape, l.dtype),
            jax.eval_shape(lambda w: build(w)[1](slots, seq_len), weights)),
        "tokens": sd((slots, seq_len), i32), "pos": sd((slots,), i32),
        "prompt_len": sd((slots,), i32), "total_len": sd((slots,), i32),
        "active": sd((slots,), flag), "finished": sd((slots,), flag),
        "n_gen": sd((slots,), i32)}
    drafting = []
    jax.eval_shape(lambda w: drafting.append(self_drafts(w)) or 0, weights)
    drafting = drafting[0]
    if kind == "spec_chunk" or (kind == "admit_prefix" and drafting):
        state.update(spec=sd((slots,), flag), draft=sd((slots,), i32),
                     proposals=sd((slots, seq_len), i32))
    # what the program closes over — the step's own weights where it
    # was built outside, the numpy constants of every step — is hoisted
    # to arguments, as the pool does (``KVSlotPool._lower``), instead of
    # being baked into the text
    more = ([sd((pool_of(weights)._packed_seats_size(slots, seq_len),), i32)]
            if kind == "seat_prefill" else [])
    if kind == "admit_prefix":
        # the snapshot: a slot's whole row of every leaf that has one
        decl = []
        jax.eval_shape(lambda w: decl.extend(jax.tree.leaves(
            decoding.spec_of(build(w)[1]).leaves)) or 0, weights)
        more = [sd((slots,), flag), sd((seq_len,), i32), sd((), i32),
                sd((), i32),
                [sd(l.shape[1:], l.dtype) if d.slot else sd((1,))
                 for l, d in zip(jax.tree.leaves(state["cache"]), decl)],
                sd((), i32)] + ([sd((), flag)] if drafting else [])
    if kind == "snapshot":
        more = [sd((), i32)]
    closed, out = jax.make_jaxpr(
        {"chunk": chunk, "prefill": prefill, "seat_prefill": seat_prefill,
         "spec_chunk": spec_chunk, "admit_prefix": admit_prefix,
         "snapshot": snapshot}[kind],
        return_shape=True)(weights, state, *more)

    def hoisted(consts, w, st, *more):
        return jax.tree.unflatten(jax.tree.structure(out), jax.core.eval_jaxpr(
            closed.jaxpr, consts, *jax.tree.leaves((w, st, more))))

    # a snapshot copies: the state it reads stays the pool's
    return jax.jit(hoisted, donate_argnums=(
        () if kind == "snapshot" else (2,))).lower(
        [sd(c.shape, c.dtype) for c in closed.consts], weights, state, *more)


def weight_casts(lowered, text: str) -> int:
    """How many instructions of the compiled program cast to bf16 a
    whole matrix shaped like one of the program's weight arguments
    (``bf16[a,b] convert(...)``): what a step repeats on every call
    when it does not hold its weights in the dtype of their products."""
    import jax

    shapes = {tuple(a.shape) for a in jax.tree.leaves(
        lowered.args_info[0][:2]) if len(a.shape) == 2}  # consts, weights
    return sum((int(a), int(b)) in shapes for a, b in re.findall(
        r"= bf16\[(\d+),(\d+)\]\S* convert\(", text))


def relayouts(lowered, text: str):
    """``(a step, once a call)``: how many ``copy`` instructions of the
    compiled program re-lay a whole operand shaped like one of the
    program's matrices (either way round: a transposed copy bears the
    dimensions swapped) or like a leaf of its cache — what a product or
    a view costs that asks for another layout than the one the operand
    is stored in.  ``a step``: inside the program's step loop (the body
    of a ``while`` that carries the cache, and what it calls), or
    anywhere in a program that has none — repeated every step; ``once a call``: hoisted out of the
    loop by the compiler.  A ``copy`` inside a fusion is a relayout on
    the fly and is counted too; ``temp_size_in_bytes`` says whether it
    was materialised."""
    import jax

    consts, weights, state = lowered.args_info[0][:3]
    shaped = {tuple(sorted(a.shape)) for a in jax.tree.leaves(
        (consts, weights)) if len(a.shape) >= 2}
    shaped |= {tuple(sorted(a.shape)) for a in jax.tree.leaves(
        state["cache"]) if len(a.shape) >= 3}
    copies, calls, inside = {}, {}, None    # by computation
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
            copies[inside], calls[inside] = 0, set()
        elif inside:
            calls[inside].update(re.findall(r"%[\w.\-]+", line))
            made = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
            copies[inside] += bool(made) and tuple(sorted(
                int(n) for n in made.group(1).split(","))) in shaped
    # the step loop: a ``while`` that carries the cache
    carried = ["[%s]" % ",".join(map(str, a.shape))
               for a in jax.tree.leaves(state["cache"]) if len(a.shape) >= 3]
    looped = {body for typ, body in re.findall(
        r"= (.*?) while\(.*?body=(%[\w.\-]+)", text)
        if any(c in typ for c in carried)}
    reach = list(looped)
    for name in reach:      # what the loop's body calls, and so on
        new = (calls[name] & copies.keys()) - looped
        looped |= new
        reach.extend(new)
    step = sum(n for name, n in copies.items()
               if name in looped or not looped)
    return step, sum(copies.values()) - step


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a name under benchmark/configs, "
                    "e.g. gpt1_117m or falcon_h1_34b")
    ap.add_argument("out")
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--kind", default="chunk",
                    choices=("chunk", "prefill", "seat_prefill",
                             "spec_chunk", "admit_prefix", "snapshot"),
                    help="prefill: the chunked-prefill program of a "
                    "builder that has one (minicpm_sala, "
                    "smallthinker_21b_a3b, deepseek_v3_2); seat_prefill: "
                    "the "
                    "seat-and-prefill program of one with a batched "
                    "prefill (gpt1_117m); spec_chunk: the self-drafting "
                    "round of a builder with a multi-token-prediction "
                    "module (k_exaone_236b_a23b, "
                    "openpangu_ultra_moe_718b); admit_prefix: a request "
                    "seated over a snapshot, under such a round where "
                    "the builder declares one (openpangu_ultra_moe_718b; "
                    "plain: kimi_linear_48b_a3b); snapshot: a slot's whole "
                    "row copied (kimi_linear_48b_a3b)")
    ap.add_argument("--layers", type=int, default=2,
                    help="layers compiled (8: the whole minicpm_sala or "
                    "smallthinker_21b_a3b cut, 5: k_exaone_236b_a23b's, "
                    "deepseek_v3_2's or openpangu_ultra_moe_718b's, 12: olmo_hybrid_7b's, 4: "
                    "solar_open2_250b's, 8 again: kimi_linear_48b_a3b's, "
                    "to see that "
                    "the real program fits the chip)")
    args = ap.parse_args()
    lowered = lowered_chunk(os.path.abspath(args.repo), args.config,
                            args.kind, args.layers)
    compiled = lowered.compile()
    print(compiled.memory_analysis())
    text = canonical(compiled.as_text())
    with open(args.out, "w") as fh:
        fh.write(text)
    kernels = [name for name in (
        "ragged_decode_attention", "grouped_decode_attention",
        "block_sparse_decode_attention", "ring_rows_decode_attention",
        "grouped_matmul",
        "gated_delta_update") if name in text]
    print("%s: %d bytes, %s, %d whole-matrix casts to bf16, %d copies of a "
          "matrix or a cache leaf a step (%d more once a call)" % (
              (args.out, len(text),
               " + ".join(kernels) + " kernel" if kernels else "no kernel",
               weight_casts(lowered, text)) + relayouts(lowered, text)))


if __name__ == "__main__":
    main()
