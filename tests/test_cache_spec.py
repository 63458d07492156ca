"""A builder's contract with the pool and the server is ONE declaration
(``decoding.CacheSpec``, made by ``decoding.declare``, read by
``decoding.spec_of``): every builder's spec fits the cache it builds,
``declare`` refuses what does not, and the server's position and expert
counters read after a fixed storm what they read before the contract had
a home (recorded at the parent of PR 58, with the sixteen attributes and
the server's own fallback)."""
import numpy as np
import pytest

from conftest import WAIT

from paddle_tpu import decoding
from paddle_tpu.serving.decode import DecodeServer


# ---------------------------------------------------------------------------
# the eleven builders at their toy configs
# ---------------------------------------------------------------------------
LM_RUNG = 256


def _lm(kv_dtype="fp32"):
    from test_decode import LM_DIMS, lm_weights

    # a rung of two read blocks: the ragged kernel's rounding shows
    d = dict(LM_DIMS, max_pos=LM_RUNG)
    built = decoding.make_transformer_lm_pooled_step_fn(
        lm_weights(np.random.RandomState(7), **d), d["vocab"], d["d_model"],
        d["n_layer"], d["n_head"], d["d_inner"], kv_dtype=kv_dtype)
    return built, d["vocab"], LM_RUNG, kv_dtype


def _hybrid_ssm():
    import test_hybrid_ssm as t

    cfg = t.tiny_cfg()
    return decoding.make_hybrid_ssm_lm_pooled_step_fn(
        t.weights(cfg, seed=21), cfg, kv_dtype="fp32"), 97, 32, "fp32"


def _sparse_linear():
    import test_sparse_linear_lm as t
    from paddle_tpu import sparse_linear_lm as sl

    w = sl.random_state(np.random.RandomState(0), t.CFG, std=0.3,
                        sparse_q_norm=3.0)
    return decoding.make_sparse_linear_lm_pooled_step_fn(
        w, t.CFG, kv_dtype="fp32", prefill_tokens=t.C), t.VOCAB, 64, "fp32"


def _routed_conv():
    import test_routed_conv_lm as t

    cfg = t.rehearse_cfg()
    return decoding.make_routed_conv_lm_pooled_step_fn(
        t.weights(cfg, seed=21), cfg, kv_dtype="fp32"), t.V, 32, "fp32"


def _windowed_routed():
    import test_windowed_routed_lm as t

    cfg = t.rehearse_cfg()
    return decoding.make_windowed_routed_lm_pooled_step_fn(
        t.weights(cfg, seed=7), cfg, kv_dtype="fp32",
        prefill_tokens=t.CHUNK), t.V, 64, "fp32"


def _mtp_routed():
    import test_k_exaone_lm as t

    cfg = t.tiny_cfg()
    return decoding.make_mtp_routed_lm_pooled_step_fn(
        t.weights(cfg, seed=4), cfg, kv_dtype="fp32",
        prefill_tokens=t.CHUNK), t.V, 64, "fp32"


def _delta_hybrid():
    import test_delta_hybrid_lm as t

    cfg = t.tiny_cfg()
    return decoding.make_delta_hybrid_lm_pooled_step_fn(
        t.weights(cfg, seed=21), cfg, kv_dtype="fp32"), t.V, 32, "fp32"


def _kda_routed():
    import test_kda_routed_lm as t

    cfg = t.tiny_cfg()
    return t._build(cfg, t.weights(cfg, seed=21)), t.V, 32, "fp32"


def _latent_sparse():
    import test_latent_sparse_lm as t

    cfg = t.rehearse_cfg()
    return decoding.make_latent_sparse_lm_pooled_step_fn(
        t.weights(cfg, seed=7), cfg, kv_dtype="fp32",
        prefill_tokens=t.CHUNK), t.V, 64, "fp32"


def _latent_mtp():
    import test_latent_mtp_lm as t

    cfg = t.tiny_cfg()
    return t._build(cfg, t.weights(cfg, seed=7)), t.V, 64, "fp32"


def _kda_latent(kv_dtype="fp32"):
    import test_kda_latent_lm as t

    cfg = t.tiny_cfg()
    return (t._build(cfg, t.weights(cfg, seed=7), kv_dtype=kv_dtype), t.V,
            64, kv_dtype)


BUILDERS = {
    "transformer_lm": _lm, "hybrid_ssm": _hybrid_ssm,
    "sparse_linear": _sparse_linear, "routed_conv": _routed_conv,
    "windowed_routed": _windowed_routed, "mtp_routed": _mtp_routed,
    "delta_hybrid": _delta_hybrid, "kda_routed": _kda_routed,
    "latent_sparse": _latent_sparse, "latent_mtp": _latent_mtp,
    "kda_latent": _kda_latent}


# ---------------------------------------------------------------------------
# (a) every builder's declaration fits what it builds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_a_builders_spec_fits_the_cache_it_builds(builder):
    import jax

    from paddle_tpu.serving.decode import POSITION_SERIES

    (step, make_cache, *rest), _, rung, _ = BUILDERS[builder]()
    spec = decoding.spec_of(make_cache)
    built = jax.eval_shape(lambda: make_cache(2, rung))
    assert jax.tree.structure(spec.leaves) == jax.tree.structure(built)
    assert all(isinstance(leaf, decoding.Leaf) for leaf in spec.flat)
    for leaf, shape in zip(spec.flat, jax.tree.leaves(built)):
        # a slot's row of a leaf that has one; a ring leaf IS one
        assert (shape.shape[0] == 2) or not leaf.slot or not shape.shape
        if leaf.window is not None:
            assert shape.shape[leaf.seq_axis] == min(rung, leaf.window)
        elif leaf.seq_axis is not None:
            assert shape.shape[leaf.seq_axis] == rung // leaf.stride
    kinds = [read.kind for read in spec.reads]
    assert len(set(kinds)) == len(kinds) and set(kinds) <= set(
        POSITION_SERIES)
    assert all(callable(read.rule) and read.layers >= 1
               for read in spec.reads)
    declared = [fn for fn in (spec.prefill_fn, spec.prefill_rows_fn,
                              spec.verify_fn, spec.mtp_fn,
                              spec.expert_stats) if fn is not None]
    assert all(callable(fn) for fn in declared)
    if spec.prefill_fn is not None:     # the third thing a builder returns
        assert rest == [spec.prefill_fn] and spec.prefill_fn.chunk_tokens > 0
    assert (spec.n_expert > 0) == (spec.expert_stats is not None)
    assert (spec.mtp_fn is None) or (spec.verify_fn is not None)


def test_the_servers_table_counts_every_kind_a_builder_may_declare():
    from paddle_tpu.serving.decode import POSITION_SERIES

    assert sorted(POSITION_SERIES) == sorted(decoding.READ_KINDS)
    names = [series.name for read, live, _ in POSITION_SERIES.values()
             for series in (read, live)]
    assert len(set(names)) == 8 and all(
        n.startswith("serving_decode_") and n.endswith("_total")
        for n in names)


# ---------------------------------------------------------------------------
# (b) what ``declare`` refuses
# ---------------------------------------------------------------------------
def _toy_cache(n_rows, seq_len):
    import jax.numpy as jnp

    return {"r": jnp.zeros((n_rows,), "int32"),
            "z": jnp.zeros((n_rows, seq_len), "float32"),
            "zz": jnp.zeros((n_rows, seq_len // 2), "float32")}


_Leaf, _Read = decoding.Leaf, decoding.PositionRead
_FITS = {"r": _Leaf(), "z": _Leaf(1), "zz": _Leaf(1, stride=2)}


@pytest.mark.parametrize("why,spec,match", [
    ("a leaf too many",
     lambda: decoding.CacheSpec(dict(_FITS, extra=_Leaf(1))),
     "declares 4 leaves, the cache has 3"),
    ("a leaf too few",
     lambda: decoding.CacheSpec({"z": _Leaf(1)}),
     "declares 1 leaves, the cache has 3"),
    ("as many leaves in another tree",
     lambda: decoding.CacheSpec([_Leaf(), _Leaf(1), _Leaf(1)]),
     "a pytree shaped like the cache"),
    ("the old ints",
     lambda: decoding.CacheSpec({"r": -1, "z": 1, "zz": 1}),
     "a decoding.Leaf a leaf"),
    ("a stride of 0",
     lambda: decoding.CacheSpec(dict(_FITS, zz=_Leaf(1, stride=0))),
     "must declare a stride >= 1 for each of the cache's 3 leaves"),
    ("a window below 0",
     lambda: decoding.CacheSpec(dict(_FITS, z=_Leaf(1, window=-4))),
     "must declare a window >= 0"),
    ("a kind of read nothing counts",
     lambda: decoding.CacheSpec(_FITS, reads=[_Read("ring", len)]),
     r"declares the kinds \['ring'\]"),
    ("one kind twice",
     lambda: decoding.CacheSpec(_FITS, reads=[_Read("kv", len)] * 2),
     "one PositionRead a kind"),
])
def test_declare_refuses_what_does_not_fit(why, spec, match):
    def make_cache(n_rows, seq_len):
        return _toy_cache(n_rows, seq_len)

    with pytest.raises(ValueError, match=match):
        decoding.declare(make_cache, spec())
    with pytest.raises(ValueError, match="make_cache declares nothing"):
        decoding.spec_of(make_cache)        # nothing was hung on it
    assert decoding.declare(make_cache, decoding.CacheSpec(
        _FITS)) is make_cache
    assert decoding.spec_of(make_cache).flat == tuple(
        _FITS[k] for k in sorted(_FITS))


def test_a_kind_the_server_does_not_count_is_refused_at_construction():
    """A spec that reached ``make_cache`` some other way than
    ``declare`` (which knows the kinds) is still held to the table."""
    from test_decode import chain_model

    step_fn, make_cache = chain_model()
    decoding.spec_of(make_cache).reads = (_Read("ring", len),)
    with pytest.raises(ValueError, match="declares a 'ring' read"):
        DecodeServer(step_fn, make_cache, eos_id=9, max_seq_len=16,
                     max_slots=2)


# ---------------------------------------------------------------------------
# (c) the counters after a fixed storm
# ---------------------------------------------------------------------------
COUNTED = ("ticks", "kv_positions_read", "kv_positions_live",
           "kv_positions_pool", "sparse_positions_read",
           "sparse_positions_live", "window_positions_read",
           "window_positions_live", "index_positions_scored",
           "latent_positions_selected", "expert_assignments",
           "experts_touched", "expert_peak_load", "expert_layer_steps")


def _lm_int8():
    return _lm("int8")


def _lm_with_draft():
    """The transformer LM in fp32 with a separate draft model: a round's
    verify is a masked read of the whole pool."""
    from test_decode import LM_DIMS, lm_weights

    from paddle_tpu.serving.speculative import make_lm_speculative

    built, vocab, t, kv = _lm()
    d = dict(LM_DIMS, max_pos=t)
    draft = lm_weights(np.random.RandomState(3), vocab=vocab, d_model=8,
                       n_layer=1, n_head=2, d_inner=16, max_pos=t,
                       name="draft")
    spec = make_lm_speculative(
        lm_weights(np.random.RandomState(7), **d), vocab_size=vocab,
        d_model=d["d_model"], n_layer=d["n_layer"], n_head=d["n_head"],
        d_inner=d["d_inner"], draft_state=draft, draft_d_model=8,
        draft_n_layer=1, draft_n_head=2, draft_d_inner=16, k=3)
    return built, vocab, t, kv, spec


def _mtp_self_draft():
    from paddle_tpu.serving.speculative import make_self_draft

    built, vocab, t, kv = _mtp_routed()
    return built, vocab, t, kv, make_self_draft(built[1])


def _latent_mtp_self_draft():
    from paddle_tpu.serving.speculative import make_self_draft

    built, vocab, t, kv = _latent_mtp()
    return built, vocab, t, kv, make_self_draft(built[1])


def _latent_mtp_bf16():
    import test_latent_mtp_lm as t

    cfg = t.tiny_cfg()
    return (t._build(cfg, t.weights(cfg, seed=7), kv_dtype="bf16"), t.V, 64,
            "bf16")


def _latent_mtp_bf16_self_draft():
    from paddle_tpu.serving.speculative import make_self_draft

    built, vocab, t, kv = _latent_mtp_bf16()
    return built, vocab, t, kv, make_self_draft(built[1])


def _kda_latent_bf16():
    return _kda_latent("bf16")


STORMS = dict(BUILDERS, transformer_lm_int8=_lm_int8,
              kda_latent_bf16=_kda_latent_bf16,
              transformer_lm_draft=_lm_with_draft,
              mtp_routed_self_draft=_mtp_self_draft,
              latent_mtp_self_draft=_latent_mtp_self_draft,
              latent_mtp_bf16=_latent_mtp_bf16,
              latent_mtp_bf16_self_draft=_latent_mtp_bf16_self_draft)

#: ``metrics()["decode"]`` after :func:`_storm`, key by key in
#: :data:`COUNTED`'s order, as the parent of PR 58 read them
RECORDED = {
    'delta_hybrid': (16, 3232, 1031, 4608, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    'hybrid_ssm': (16, 3232, 1031, 4608, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    'kda_routed': (16, 3232, 1031, 4608, 0, 0, 0, 0, 0, 0, 342, 297, 205, 192),
    # the tenth builder's, as PR 60 first read them (a plain step is
    # counted the module's leaf too: the rule is the round's)
    'latent_mtp': (22, 8448, 3352, 12672, 0, 0, 0, 0, 13408, 13408, 528, 434, 208, 130),
    'latent_mtp_self_draft': (48, 9216, 3352, 9216, 0, 0, 0, 0, 25000, 25000, 1416, 793, 432, 144),
    # bf16 leaves, as PR 61 first read them: the builder's "kv" rule is
    # the dense read's host mirror (here the XLA form's: the rung of 64 is
    # one key block), counted once a round a slot that ADVANCED — where
    # the float32 pool's round is counted masked over the whole pool
    'latent_mtp_bf16': (22, 8448, 3352, 12672, 0, 0, 0, 0, 13408, 13408, 528, 433, 208, 130),
    'latent_mtp_bf16_self_draft': (48, 7552, 3352, 9216, 0, 0, 0, 0, 25000, 25000, 1416, 794, 432, 144),
    # the eleventh builder's, as PR 63 first read them: ONE latent layer
    # of four (a position is read once a step), three expert layers of
    # which 4 of 16 experts are held; bf16 leaves count the dense read's
    # host mirror (here the XLA form's: the rung of 64 is one key block)
    'kda_latent': (22, 8448, 3352, 12672, 0, 0, 0, 0, 3352, 3352, 179, 164, 142, 195),
    'kda_latent_bf16': (22, 8448, 3352, 12672, 0, 0, 0, 0, 3352, 3352, 179, 164, 142, 195),
    'latent_sparse': (22, 8448, 3352, 12672, 0, 0, 0, 0, 10056, 5532, 1056, 897, 217, 130),
    'mtp_routed': (22, 8448, 3352, 12672, 0, 0, 2064, 13408, 0, 0, 1056, 872, 408, 260),
    'mtp_routed_self_draft': (49, 7616, 3352, 9408, 0, 0, 3760, 25396, 0, 0, 2380, 1215, 823, 245),
    'routed_conv': (16, 3232, 1031, 4608, 0, 0, 0, 0, 0, 0, 1212, 990, 250, 144),
    'sparse_linear': (20, 9472, 3552, 11520, 6784, 7104, 0, 0, 0, 0, 0, 0, 0, 0),
    'transformer_lm': (59, 65344, 50778, 135936, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    'transformer_lm_draft': (230, 176640, 73452, 176640, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    'transformer_lm_int8': (59, 135936, 50778, 135936, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    'windowed_routed': (22, 8448, 3352, 12672, 0, 0, 5532, 10056, 0, 0, 1584, 1253, 470, 260),
}


def _storm(case):
    """Six requests of fixed tokens through three slots of one rung pair
    (three seats reused), all queued before the first turn sees any, so
    that every turn's seats — and with them every counter — are the same
    in every run; speculative where the case has a draft, but for one
    request."""
    from test_decode import turn_held

    (step, make_cache, *_), vocab, t, kv_dtype, *draft = STORMS[case]()
    srv = DecodeServer(
        step, make_cache, eos_id=vocab, max_seq_len=t, max_slots=3,
        slot_ladder=(3,), len_ladder=(t,), steps_per_tick=3,
        queue_capacity=64, target_queue_wait_ms=600000.0, kv_dtype=kv_dtype,
        name="storm-" + case, speculative=draft[0] if draft else None)
    rng = np.random.RandomState(58)
    lengths = [(int(t * a), int(t * b)) for a, b in (
        (0.1, 0.3), (0.45, 0.2), (0.05, 0.55), (0.3, 0.1), (0.6, 0.39),
        (0.2, 0.25))]
    try:
        srv.warmup()
        with turn_held(srv):
            reqs = [srv.submit(
                {"tokens": rng.randint(0, vocab, max(p, 1)).astype(np.int32)},
                max_new_tokens=max(n, 1),
                **({"speculative": i != 1} if draft else {}))
                for i, (p, n) in enumerate(lengths)]
        for r in reqs:
            r.result(WAIT)
        m = srv.metrics()
    finally:
        srv.stop(drain=False, timeout=30.0)
    assert m["recompiles"] == 0
    return [int(m["decode"][key]) for key in COUNTED]


@pytest.mark.parametrize("case", sorted(STORMS))
def test_the_counters_read_after_a_storm_what_the_parent_read(case):
    assert dict(zip(COUNTED, _storm(case))) == dict(
        zip(COUNTED, RECORDED[case]))
