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
        weights = {k: sd(a.shape) for k, a in
                   decoding.random_transformer_lm_state(
                       np.random.RandomState(0), *dims,
                       cfg["n_positions"]).items()}

        def build(w):
            return decoding.make_transformer_lm_pooled_step_fn(
                w, *dims, kv_dtype=sv["kv_dtype"])

    def chunk(w, state):
        step_fn, _ = build(w)
        return decoding.make_slot_decode_fns(
            step_fn, int(cfg["vocab_size"]), sv["steps_per_tick"])[0](state)

    def prefill(w, state):
        # one slot's next chunk of prompt tokens, as the pool runs it
        # (KVSlotPool._prefill_fn) for a builder that declares one
        fn = build(w)[1].prefill_fn
        c = fn.chunk_tokens
        return dict(state, cache=fn(
            state["cache"], jnp.int32(3),
            jax.lax.dynamic_slice(state["tokens"], (3, c), (1, c))[0],
            state["pos"][3], jnp.int32(c)))

    i32, flag = jnp.int32, jnp.bool_
    state = {
        "cache": jax.tree.map(
            lambda l: sd(l.shape, l.dtype),
            jax.eval_shape(lambda w: build(w)[1](slots, seq_len), weights)),
        "tokens": sd((slots, seq_len), i32), "pos": sd((slots,), i32),
        "prompt_len": sd((slots,), i32), "total_len": sd((slots,), i32),
        "active": sd((slots,), flag), "finished": sd((slots,), flag),
        "n_gen": sd((slots,), i32)}
    return jax.jit({"chunk": chunk, "prefill": prefill}[kind],
                   donate_argnums=(1,)).lower(weights, state)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a name under benchmark/configs, "
                    "e.g. gpt1_117m or falcon_h1_34b")
    ap.add_argument("out")
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--kind", default="chunk", choices=("chunk", "prefill"),
                    help="prefill: the chunked-prefill program of a "
                    "builder that has one (minicpm_sala)")
    ap.add_argument("--layers", type=int, default=2,
                    help="layers compiled (8: the whole minicpm_sala cut, "
                    "to see that the real program fits the chip)")
    args = ap.parse_args()
    compiled = lowered_chunk(os.path.abspath(args.repo), args.config,
                             args.kind, args.layers).compile()
    print(compiled.memory_analysis())
    text = canonical(compiled.as_text())
    with open(args.out, "w") as fh:
        fh.write(text)
    print("%s: %d bytes, %s" % (
        args.out, len(text), "ragged_decode_attention kernel"
        if "ragged_decode_attention" in text else "no kernel"))


if __name__ == "__main__":
    main()
