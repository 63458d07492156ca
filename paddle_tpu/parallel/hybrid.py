"""Hybrid 5D-parallel transformer engine: dp / pp / tp / sp / ep.

This is the TPU-native replacement for the reference's whole distributed
runtime zoo — ParallelExecutor NCCL data-parallel (parallel_executor.cc),
PipelineTrainer/SectionWorker pipeline stages (framework/section_worker.cc,
optimizer.py:2665 PipelineOptimizer), and the sharded-table model
parallelism (distributed_lookup_table) — expressed as ONE jitted training
step under `jax.shard_map` over a 5-axis mesh:

  * dp — batch sharding; gradient psum over ``dp`` (the NCCL allreduce).
  * pp — GPipe microbatch pipeline: each rank owns ``n_layers/pp`` blocks;
    activations stream stage-to-stage via `lax.ppermute` inside a
    `lax.scan` (the SectionWorker queue loop, but compiled; bubbles and
    all).  Backward flows through the transposed ppermute automatically.
  * tp — Megatron-style tensor parallel: qkv/ffn weights column-sharded,
    out/second-ffn row-sharded, psum at row-parallel outputs.
  * sp — sequence parallel: activations sharded over the sequence dim;
    attention computes local query rows against all-gathered K/V
    (ring attention is the drop-in upgrade — parallel/ring_attention.py).
  * ep — expert parallel: MoE expert weights sharded over ``ep``; each
    rank computes its local experts, combined by psum.

Everything — forward, backward, optimizer update — is one XLA module per
step; collectives ride ICI in mesh-axis order.

Numerics are validated against a single-device reference implementation
(`reference_loss`) in tests/test_hybrid_parallel.py, in the loss-parity
style of the reference's dist tests (test_dist_base.py:432).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from paddle_tpu.parallel import mesh as mesh_lib

__all__ = ["HybridConfig", "init_params", "make_train_step", "reference_loss", "factorize_mesh"]


class HybridConfig(NamedTuple):
    vocab_size: int = 1000
    d_model: int = 64
    n_head: int = 4
    d_ff: int = 128
    n_layers: int = 4
    n_experts: int = 4
    seq_len: int = 32
    batch: int = 8          # global batch
    microbatches: int = 2   # per dp-shard microbatch count (GPipe M)
    dp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    lr: float = 0.1
    ring_attention: bool = True  # sp>1: ring attention vs all-gather KV

    @property
    def layers_per_stage(self) -> int:
        assert self.n_layers % self.pp == 0
        return self.n_layers // self.pp

    def mesh_axes(self) -> Dict[str, int]:
        return {"dp": self.dp, "pp": self.pp, "tp": self.tp, "sp": self.sp, "ep": self.ep}


def factorize_mesh(n_devices: int) -> Dict[str, int]:
    """Deterministically factor a device count onto the 5 axes.

    Order of filling: pp, tp, dp, sp, ep — pipeline+tensor first (the
    common v5e intra-host layout), then data, then sequence/expert.
    """
    sizes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
    order = ["pp", "tp", "dp", "sp", "ep"]
    n = n_devices
    i = 0
    while n > 1:
        for p in (2, 3, 5, 7, 11, 13):
            if n % p == 0:
                sizes[order[i % len(order)]] *= p
                n //= p
                break
        else:  # prime > 13: give it all to dp
            sizes["dp"] *= n
            n = 1
        i += 1
    return sizes


# ---------------------------------------------------------------------------
# Parameters.  Stage-stacked: leading dim pp, second dim layers-per-stage.
# ---------------------------------------------------------------------------
def _param_specs(cfg: HybridConfig):
    """name -> PartitionSpec dims (None = replicated on that dim)."""
    from jax.sharding import PartitionSpec as P

    return {
        "word_emb": P(),
        "pos_emb": P(),
        "head": P(None, "tp"),
        "ln1_scale": P("pp"),
        "ln1_bias": P("pp"),
        "ln2_scale": P("pp"),
        "ln2_bias": P("pp"),
        "wq": P("pp", None, None, "tp"),
        "wk": P("pp", None, None, "tp"),
        "wv": P("pp", None, None, "tp"),
        "wo": P("pp", None, "tp", None),
        "gate_w": P("pp"),
        "moe_w0": P("pp", None, "ep", None, "tp"),
        "moe_w1": P("pp", None, "ep", "tp", None),
    }


def init_params(cfg: HybridConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.RandomState(seed)
    S, L = cfg.pp, cfg.layers_per_stage
    D, F, E, V = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.vocab_size

    def rand(*shape, scale=None):
        scale = scale if scale is not None else (1.0 / np.sqrt(shape[-2] if len(shape) >= 2 else D))
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    return {
        "word_emb": rand(V, D, scale=0.02),
        "pos_emb": rand(cfg.seq_len, D, scale=0.02),
        "head": rand(D, V),
        "ln1_scale": np.ones((S, L, D), np.float32),
        "ln1_bias": np.zeros((S, L, D), np.float32),
        "ln2_scale": np.ones((S, L, D), np.float32),
        "ln2_bias": np.zeros((S, L, D), np.float32),
        "wq": rand(S, L, D, D),
        "wk": rand(S, L, D, D),
        "wv": rand(S, L, D, D),
        "wo": rand(S, L, D, D),
        "gate_w": rand(S, L, D, E),
        "moe_w0": rand(S, L, E, D, F),
        "moe_w1": rand(S, L, E, F, D, scale=1.0 / np.sqrt(F)),
    }


# ---------------------------------------------------------------------------
# Model math (shared by the sharded engine and the reference impl).
# ---------------------------------------------------------------------------
def _layer_norm(x, scale, bias, eps=1e-5):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _attention_math(q, k, v, bias, n_head_local, d_head):
    """q: [b, Tq, Hl*Dh]; k/v: [b, Tk, Hl*Dh]; bias: [Tq, Tk]."""
    import jax.numpy as jnp

    b, tq, _ = q.shape
    tk = k.shape[1]

    def heads(x, t):
        return x.reshape(b, t, n_head_local, d_head).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(q, tq), heads(k, tk), heads(v, tk)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d_head)
    scores = scores + bias
    w = _softmax(scores)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", w, vh)
    return ctx.transpose(0, 2, 1, 3).reshape(b, tq, n_head_local * d_head)


def _softmax(x):
    import jax.nn

    return jax.nn.softmax(x, axis=-1)


def _moe_math(x, gate_logits_local, w0_local, w1_local):
    """x: [b, t, D]; gate_logits_local: [b, t, e_loc] (already softmaxed
    slice); w0_local: [e_loc, D, F_loc]; w1_local: [e_loc, F_loc, D]."""
    import jax
    import jax.numpy as jnp

    h = jnp.einsum("btd,edf->btef", x, w0_local)
    h = jax.nn.gelu(h)
    y = jnp.einsum("btef,efd->bted", h, w1_local)
    return jnp.einsum("bted,bte->btd", y, gate_logits_local)


# ---------------------------------------------------------------------------
# Single-device reference (for loss parity tests)
# ---------------------------------------------------------------------------
def reference_loss(params: Dict[str, Any], tokens, labels, cfg: HybridConfig):
    """Pure single-device forward loss, same math as the sharded engine."""
    import jax
    import jax.numpy as jnp

    D, H = cfg.d_model, cfg.n_head
    d_head = D // H
    T = cfg.seq_len
    x = params["word_emb"][tokens] + params["pos_emb"][None, :, :]
    causal = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None], 0.0, -1e9)
    for s in range(cfg.pp):
        for l in range(cfg.layers_per_stage):
            h = _layer_norm(x, params["ln1_scale"][s, l], params["ln1_bias"][s, l])
            q, k, v = h @ params["wq"][s, l], h @ params["wk"][s, l], h @ params["wv"][s, l]
            att = _attention_math(q, k, v, causal, H, d_head)
            x = x + att @ params["wo"][s, l]
            h = _layer_norm(x, params["ln2_scale"][s, l], params["ln2_bias"][s, l])
            gates = jax.nn.softmax(h @ params["gate_w"][s, l], axis=-1)
            x = x + _moe_math(h, gates, params["moe_w0"][s, l], params["moe_w1"][s, l])
    logits = x @ params["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# The sharded engine
# ---------------------------------------------------------------------------
def _optimizer_plan(optimizer):
    """Map a fluid optimizer object onto its registered op kernel
    (reference: each Optimizer's _append_optimize_op emits the same op).

    Returns (op_type, attrs, moment_slots, pow_slots, lr, l2_decay).
    moment_slots are per-param aux tensors shaped like the param (sharded
    with the param's spec); pow_slots are per-param scalars (replicated).
    """
    if optimizer is None:
        return ("sgd", {}, [], {}, None, 0.0)
    decay = 0.0
    reg = getattr(optimizer, "regularization", None)
    if reg is not None:
        if type(reg).__name__ != "L2DecayRegularizer":
            raise ValueError(
                "hybrid engine: only L2 decay regularization is supported "
                "(got %s)" % type(reg).__name__
            )
        decay = float(reg._coeff)
    lr = optimizer._learning_rate
    if not isinstance(lr, (int, float)):
        raise ValueError(
            "hybrid engine: optimizer must have a float learning rate "
            "(LR schedules run program-side)"
        )
    # exact-class whitelist (ADVICE r4): a wrapper/subclass like
    # DGCMomentumOptimizer or LarsMomentumOptimizer carries extra update
    # semantics a substring match would silently drop — those must raise
    # and route through the Program path instead
    from paddle_tpu import optimizer as opt_mod

    cls = type(optimizer)
    if cls is opt_mod.AdamOptimizer:
        return (
            "adam",
            {"beta1": optimizer._beta1, "beta2": optimizer._beta2,
             "epsilon": optimizer._epsilon},
            ["Moment1", "Moment2"],
            {"Beta1Pow": optimizer._beta1, "Beta2Pow": optimizer._beta2},
            float(lr), decay,
        )
    if cls is opt_mod.MomentumOptimizer:
        return (
            "momentum",
            {"mu": optimizer._momentum,
             "use_nesterov": optimizer._use_nesterov},
            ["Velocity"], {}, float(lr), decay,
        )
    if cls is opt_mod.SGDOptimizer:
        return ("sgd", {}, [], {}, float(lr), decay)
    raise ValueError(
        "hybrid engine supports exactly SGDOptimizer/MomentumOptimizer/"
        "AdamOptimizer (got %s — subclasses and wrappers carry extra "
        "update semantics); route other optimizers through the Program "
        "path" % cls.__name__
    )


def init_opt_state(cfg: HybridConfig, params, optimizer):
    """Optimizer aux state for ``make_train_step(..., optimizer=)``:
    '<param>@<Slot>' -> zeros_like(param) moments and scalar beta pows
    (the reference's per-param accumulators, optimizer.py
    _add_accumulator)."""
    _, _, moment_slots, pow_slots, _, _ = _optimizer_plan(optimizer)
    aux = {}
    for n, p in params.items():
        for slot in moment_slots:
            aux["%s@%s" % (n, slot)] = np.zeros_like(p)
        for slot, v0 in pow_slots.items():
            aux["%s@%s" % (n, slot)] = np.full((1,), v0, np.float32)
    return aux


def make_train_step(cfg: HybridConfig, mesh=None, optimizer=None):
    """Build the single jitted XLA module implementing the full
    5D-parallel training step (fwd + bwd + optimizer update).

    ``optimizer=None``: plain SGD at ``cfg.lr``;
    ``step(params, tokens, labels) -> (loss, new_params)``.

    ``optimizer=`` a fluid SGD/Momentum/Adam optimizer object (with
    optional L2 regularization): the update replays the optimizer's
    REGISTERED op kernel per parameter — the same kernels the Program
    path runs (parallel/pipeline_program.py does the same for pipeline
    sections) — and the step signature becomes
    ``step(params, aux, tokens, labels) -> (loss, new_params, new_aux)``
    with ``aux`` from :func:`init_opt_state`.  Moments shard with their
    parameter's spec; beta pows replicate.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        mesh = mesh_lib.make_mesh(cfg.mesh_axes())
    specs = _param_specs(cfg)
    opt_op, opt_attrs, moment_slots, pow_slots, opt_lr, l2_decay = _optimizer_plan(optimizer)
    aux_spec_of = {}
    for n in specs:
        for slot in moment_slots:
            aux_spec_of["%s@%s" % (n, slot)] = specs[n]
        for slot in pow_slots:
            aux_spec_of["%s@%s" % (n, slot)] = P()

    D, H, T, V, E, F = cfg.d_model, cfg.n_head, cfg.seq_len, cfg.vocab_size, cfg.n_experts, cfg.d_ff
    assert H % cfg.tp == 0 and D % cfg.tp == 0 and F % cfg.tp == 0
    assert T % cfg.sp == 0 and E % cfg.ep == 0 and cfg.batch % cfg.dp == 0
    h_loc, t_loc, e_loc = H // cfg.tp, T // cfg.sp, E // cfg.ep
    d_head = D // H
    b_loc = cfg.batch // cfg.dp
    M = cfg.microbatches
    assert b_loc % M == 0
    mb = b_loc // M
    S = cfg.pp
    n_steps = M + S - 1

    ALL_AXES = ("dp", "pp", "tp", "sp", "ep")

    def lift_all(x):
        """Cast x to varying over every mesh axis it isn't already
        varying on, so downstream vma state is uniform regardless of
        axis sizes."""
        missing = tuple(a for a in ALL_AXES if a not in jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    # ---------------- per-stage block (runs under shard_map) -------------
    def stage_fn(sp_idx, tp_idx, ep_idx, stage_params, x):
        """x: [mb, t_loc, D] local activation; applies this stage's layers."""
        q_off = sp_idx * t_loc
        rows = jnp.arange(t_loc) + q_off
        cols = jnp.arange(T)
        causal = jnp.where(cols[None, :] <= rows[:, None], 0.0, -1e9)

        for l in range(cfg.layers_per_stage):
            p = {k: v[l] for k, v in stage_params.items()}
            h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
            # tp column-parallel qkv: local [D, D/tp] slices
            q = h @ p["wq"]
            k = h @ p["wk"]
            v = h @ p["wv"]
            if cfg.sp > 1 and cfg.ring_attention:
                # ring attention: K/V blocks rotate over the sp ring with
                # online-softmax accumulation (parallel/ring_attention.py)
                from paddle_tpu.parallel.ring_attention import ring_attention

                b = q.shape[0]

                def heads(z):
                    return z.reshape(b, t_loc, h_loc, d_head).transpose(0, 2, 1, 3)

                ctx = ring_attention(heads(q), heads(k), heads(v), "sp", causal=True)
                att = ctx.transpose(0, 2, 1, 3).reshape(b, t_loc, h_loc * d_head)
            else:
                # sp: all-gather K/V sequence shards -> full-length keys
                if cfg.sp > 1:
                    k = jax.lax.all_gather(k, "sp", axis=1, tiled=True)
                    v = jax.lax.all_gather(v, "sp", axis=1, tiled=True)
                att = _attention_math(q, k, v, causal, h_loc, d_head)
            # tp row-parallel output projection + psum over tp
            o = att @ p["wo"]
            o = jax.lax.psum(o, "tp")
            x = x + o

            h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
            gates = jax.nn.softmax(h @ p["gate_w"], axis=-1)  # full E
            g_loc = jax.lax.dynamic_slice_in_dim(gates, ep_idx * e_loc, e_loc, axis=-1)
            y = _moe_math(h, g_loc, p["moe_w0"], p["moe_w1"])
            y = jax.lax.psum(y, ("ep", "tp"))
            x = x + y
        return x

    STAGE_KEYS = (
        "ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
        "wq", "wk", "wv", "wo", "gate_w", "moe_w0", "moe_w1",
    )

    # ---------------- full local step (inside shard_map) ------------------
    def local_loss(params, tokens, labels):
        stage = jax.lax.axis_index("pp")
        sp_idx = jax.lax.axis_index("sp")
        tp_idx = jax.lax.axis_index("tp")
        ep_idx = jax.lax.axis_index("ep")

        # slice my sequence shard of tokens/labels: [b_loc, t_loc]
        tok = jax.lax.dynamic_slice_in_dim(tokens, sp_idx * t_loc, t_loc, axis=1)
        lab = jax.lax.dynamic_slice_in_dim(labels, sp_idx * t_loc, t_loc, axis=1)
        pos = jax.lax.dynamic_slice_in_dim(params["pos_emb"], sp_idx * t_loc, t_loc, axis=0)[None]
        x = params["word_emb"][tok] + pos  # [b_loc, t_loc, D]
        x = lift_all(x)

        # microbatches [M, mb, t_loc, D]
        xs = x.reshape(M, mb, t_loc, D)
        stage_params = {k: params[k][0] for k in STAGE_KEYS}  # local stage (pp-sharded dim0)

        if S == 1:
            final = stage_fn(sp_idx, tp_idx, ep_idx, stage_params, x)
        else:
            def body(carry, t):
                buf = carry
                x_t = xs[jnp.clip(t, 0, M - 1)]
                inp = jnp.where(stage == 0, x_t, buf)
                out = stage_fn(sp_idx, tp_idx, ep_idx, stage_params, inp)
                sent = jax.lax.ppermute(out, "pp", [(i, (i + 1) % S) for i in range(S)])
                y = jnp.where(stage == S - 1, out, 0.0)
                return sent, y

            init = lift_all(jnp.zeros((mb, t_loc, D), x.dtype))
            _, ys = jax.lax.scan(body, init, jnp.arange(n_steps))
            final = ys[S - 1 :].reshape(b_loc, t_loc, D)  # valid on last stage

        # head: tp column-parallel logits -> gather over tp
        logits_loc = final @ params["head"]  # [b_loc, t_loc, V/tp]
        if cfg.tp > 1:
            logits = jax.lax.all_gather(logits_loc, "tp", axis=-1, tiled=True)
        else:
            logits = logits_loc
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        loss_sum = jnp.sum(nll)
        # only the last pipeline stage's loss is real
        loss_sum = jnp.where(stage == S - 1, loss_sum, 0.0)
        total_tokens = cfg.batch * T
        loss = jax.lax.psum(loss_sum, ("dp", "pp", "sp")) / total_tokens
        # value-identity pmean proves tp/ep invariance to the vma checker
        # (the loss is computed redundantly on those ranks)
        return jax.lax.pmean(loss, ("tp", "ep"))

    def apply_optimizer(params, grads, aux):
        """Replay the registered optimizer kernel per parameter (the same
        kernels Executor programs run; pipeline_program.py's pattern)."""
        from paddle_tpu.core.registry import get_kernel

        kern = get_kernel(opt_op)
        lr_arr = jnp.asarray([opt_lr], jnp.float32)
        new_p, new_aux = {}, dict(aux)
        for n in params:
            g = grads[n]
            if l2_decay:
                g = g + l2_decay * params[n]
            ins = {"Param": [params[n]], "Grad": [g.astype(params[n].dtype)],
                   "LearningRate": [lr_arr]}
            for slot in moment_slots + list(pow_slots):
                ins[slot] = [aux["%s@%s" % (n, slot)]]
            outs = kern(ins, opt_attrs)
            new_p[n] = outs["ParamOut"]
            for slot in moment_slots + list(pow_slots):
                out = outs.get(slot + "Out")
                if out is not None:
                    new_aux["%s@%s" % (n, slot)] = out
        return new_p, new_aux

    def sharded_step(params, aux, tokens, labels):
        # Gradient reduction over each param's replication axes (the
        # reference's NCCL allreduce, details/all_reduce_op_handle.cc) is
        # inserted by shard_map's transpose: under check_vma=True the
        # cotangent of an input that is invariant over an axis is psum'd
        # over that axis automatically.
        loss, grads = jax.value_and_grad(local_loss)(params, tokens, labels)
        if optimizer is None:
            new_params = {n: params[n] - cfg.lr * grads[n] for n in params}
            return loss, new_params, aux
        new_params, new_aux = apply_optimizer(params, grads, aux)
        return loss, new_params, new_aux

    in_specs = (
        {n: specs[n] for n in specs},
        {n: aux_spec_of[n] for n in aux_spec_of},
        P("dp"),
        P("dp"),
    )
    out_specs = (
        P(),
        {n: specs[n] for n in specs},
        {n: aux_spec_of[n] for n in aux_spec_of},
    )

    smapped = jax.shard_map(
        sharded_step,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=True,
    )
    jitted = jax.jit(smapped)

    def place_aux(aux):
        return {
            n: jax.device_put(v, NamedSharding(mesh, aux_spec_of[n]))
            for n, v in aux.items()
        }

    def place(params, tokens, labels):
        params = {
            n: jax.device_put(v, NamedSharding(mesh, specs[n])) for n, v in params.items()
        }
        tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp")))
        labels = jax.device_put(labels, NamedSharding(mesh, P("dp")))
        return params, tokens, labels

    if optimizer is None:
        # legacy signature: step(params, tokens, labels) -> (loss, params)
        def step(params, tokens, labels):
            loss, new_params, _ = jitted(params, {}, tokens, labels)
            return loss, new_params

        return step, place, mesh

    def step(params, aux, tokens, labels):
        return jitted(params, aux, tokens, labels)

    step.place_aux = place_aux
    return step, place, mesh
