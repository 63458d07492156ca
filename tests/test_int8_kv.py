"""int8 KV-cache slots (ISSUE 18 tentpole b): ``KVSlotPool(kv_dtype=
"int8")`` stores KV leaves as int8 codes with per-slot-per-head fp32
scales riding the state as sibling leaves — quantize-on-write inside
the step fn, dequant-at-attend.

Pinned here:

* greedy decode parity vs the fp32-KV pool (same tokens, the
  acceptance tolerance is EXACT token match over the drill),
* >= 1.8x concurrent sequences at a fixed HBM budget, from the pool's
  own ``kv_rung_bytes`` accounting (ground truth, not estimates),
* prefix caching and speculative decode still compose on the int8
  pool with the zero-recompile contract intact,
* the endpoint manifest round-trips ``kv_dtype`` and ``/healthz`` +
  ``metrics()`` advertise it (the fleet-discovery surface).
"""
import numpy as np
import pytest

from conftest import WAIT

import jax.numpy as jnp

from paddle_tpu.decoding import (
    KV_DTYPES,
    make_transformer_lm_pooled_step_fn,
    normalize_kv_dtype,
    random_transformer_lm_state,
)
from paddle_tpu.serving.decode import (
    DecodeServer,
    load_decode_endpoint,
    save_decode_endpoint,
)
from paddle_tpu.serving.kv_pool import KVSlotPool
from paddle_tpu.serving.speculative import make_lm_speculative

V = 64
LM = dict(vocab=V, d_model=32, n_layer=2, n_head=4, d_inner=64,
          max_pos=64)
EOS = V - 1  # random logits essentially never emit it; caps terminate


@pytest.fixture(scope="module")
def lm_state():
    return random_transformer_lm_state(np.random.RandomState(7), **LM)


def _pooled(state, kv_dtype):
    return make_transformer_lm_pooled_step_fn(
        state, LM["vocab"], LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"], kv_dtype=kv_dtype)


def test_kv_dtype_normalization():
    assert KV_DTYPES == ("fp32", "int8", "bf16")
    assert normalize_kv_dtype("bfloat16") == "bf16"
    assert normalize_kv_dtype(None) == "fp32"
    assert normalize_kv_dtype("float32") == "fp32"
    assert normalize_kv_dtype("int8") == "int8"
    with pytest.raises(ValueError):
        normalize_kv_dtype("fp8")


def test_int8_cache_leaves_and_greedy_parity(lm_state):
    """The int8 cache stores int8 code leaves + fp32 scale siblings,
    and greedy decode tracks the fp32-KV path token-for-token."""
    import jax

    sf32, mc32 = _pooled(lm_state, "fp32")
    sf8, mc8 = _pooled(lm_state, "int8")
    c32, c8 = mc32(2, 24), mc8(2, 24)
    dts = {str(l.dtype) for l in jax.tree_util.tree_leaves(c8)}
    assert "int8" in dts and "float32" in dts
    assert all(str(l.dtype) == "float32"
               for l in jax.tree_util.tree_leaves(c32))

    j32, j8 = jax.jit(sf32), jax.jit(sf8)
    tok32 = tok8 = np.array([3, 5], np.int32)
    for i in range(12):
        ts = np.full(2, i, np.int32)
        l32, c32 = j32(c32, tok32, ts)
        l8, c8 = j8(c8, tok8, ts)
        tok32 = np.argmax(np.asarray(l32), -1).astype(np.int32)
        tok8 = np.argmax(np.asarray(l8), -1).astype(np.int32)
        np.testing.assert_array_equal(tok32, tok8)


def test_pool_bytes_accounting_and_sequences_at_fixed_hbm(lm_state):
    """kv_rung_bytes computes from the STORED dtype: the int8 pool's
    per-slot KV bytes buy >= 1.8x the concurrent sequences of fp32 at
    any fixed HBM budget (acceptance floor; per-head scales cost
    4/d_head extra so the exact ratio is (d_head + 4) / (4 * d_head))."""
    pools = {}
    for dt in ("fp32", "int8"):
        sf, mc = _pooled(lm_state, dt)
        pools[dt] = KVSlotPool(sf, mc, eos_id=EOS, max_slots=4,
                               max_seq_len=32, steps=2, kv_dtype=dt)
        assert pools[dt].kv_dtype == dt
    for s, t in pools["fp32"].rung_pairs():
        b32 = pools["fp32"].kv_rung_bytes(s, t)
        b8 = pools["int8"].kv_rung_bytes(s, t)
        budget = 4 * b32  # fits exactly 4 fp32 rungs' worth of slots
        assert (budget // b8) * s >= 1.8 * (budget // b32) * s, (s, t)
    # live state agrees with the rung arithmetic
    st8 = pools["int8"].alloc(2, 16)
    assert pools["int8"].kv_state_bytes(st8) == \
        pools["int8"].kv_rung_bytes(2, 16)


def test_int8_pool_zero_recompiles_and_resize_carries_scales(lm_state):
    sf8, mc8 = _pooled(lm_state, "int8")
    pool = KVSlotPool(sf8, mc8, eos_id=EOS, max_slots=4, max_seq_len=16,
                      steps=2, kv_dtype="int8")
    pool.warmup()
    recompiles = []
    pool._on_recompile = lambda: recompiles.append(1)
    for s, t in pool.rung_pairs():
        st = pool.alloc(s, t)
        st = pool.admit(st, 0, np.array([2, 3], np.int32), 2, t)
        st = pool.chunk(st)
        st = pool.release(st, [0])
    assert pool.jit_cache_stats()["misses"] == 0 and not recompiles
    # resize up/down round-trips the int8 codes AND their scale leaves
    import jax

    st = pool.alloc(2, 8)
    st = pool.admit(st, 0, np.array([2, 3, 4], np.int32), 3, 8)
    st = pool.chunk(st)
    kv_keys = sorted(k for k in st if k not in ("tokens", "pos", "live",
                                                "cap"))
    leaves0 = [np.asarray(l) for k in kv_keys
               for l in jax.tree_util.tree_leaves(st[k])]
    up = pool.resize(st, 4, 16)
    down = pool.resize(up, 2, 8)
    leaves1 = [np.asarray(l) for k in kv_keys
               for l in jax.tree_util.tree_leaves(down[k])]
    assert len(leaves0) == len(leaves1)
    for a, b in zip(leaves0, leaves1):
        np.testing.assert_array_equal(a, b)


def _greedy_tokens(srv, prompt, n):
    req = srv.submit({"tokens": np.asarray(prompt, np.int32)},
                     max_new_tokens=n)
    return req.result(timeout=WAIT)[0].tolist()


def test_decode_server_int8_parity_and_kv_bytes_gauge(lm_state):
    """End to end: an int8-KV DecodeServer emits the SAME tokens as the
    fp32 one, reports kv_dtype + kv_cache_bytes through metrics(), and
    the gauge drops to 0 when the pool idles."""
    servers = {}
    for dt in ("fp32", "int8"):
        sf, mc = _pooled(lm_state, dt)
        srv = DecodeServer(sf, mc, eos_id=EOS, max_seq_len=32,
                           max_slots=2, len_ladder=[32], steps_per_tick=2,
                           name="kv-%s" % dt, kv_dtype=dt)
        srv.warmup(configure_cache=False)
        servers[dt] = srv
    try:
        out32 = _greedy_tokens(servers["fp32"], [3, 5, 7], 10)
        out8 = _greedy_tokens(servers["int8"], [3, 5, 7], 10)
        assert out32 == out8
        m8 = servers["int8"].metrics()["decode"]
        assert m8["kv_dtype"] == "int8"
        assert servers["int8"].kv_dtype == "int8"
        # pool idles after the request completes -> bytes gauge returns
        # to 0 (it was set while the slot was live)
        import time
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if servers["int8"].metrics()["decode"]["kv_cache_bytes"] == 0:
                break
            time.sleep(0.02)
        assert servers["int8"].metrics()["decode"]["kv_cache_bytes"] == 0
        assert servers["fp32"].metrics()["decode"]["kv_dtype"] == "fp32"
    finally:
        for srv in servers.values():
            srv.stop(drain=False)


def test_int8_prefix_and_speculative_compose(lm_state):
    """Decode tier 2 on the int8 pool: prefix-cached admission and
    draft-then-verify rounds still produce the plain path's tokens with
    zero steady-state recompiles."""
    draft_state = random_transformer_lm_state(
        np.random.RandomState(11), V, 16, 1, 2, 32, LM["max_pos"],
        name="draft")
    spec = make_lm_speculative(
        lm_state, vocab_size=V, d_model=LM["d_model"],
        n_layer=LM["n_layer"], n_head=LM["n_head"],
        d_inner=LM["d_inner"], draft_state=draft_state,
        draft_d_model=16, draft_n_layer=1, draft_n_head=2,
        draft_d_inner=32, k=3, kv_dtype="int8")
    sf8, mc8 = _pooled(lm_state, "int8")
    srv = DecodeServer(sf8, mc8, eos_id=EOS, max_seq_len=32, max_slots=2,
                       len_ladder=[32], steps_per_tick=2,
                       name="kv-int8-t2", kv_dtype="int8",
                       prefix_cache=1 << 20, speculative=spec)
    plain_sf, plain_mc = _pooled(lm_state, "fp32")
    ref = DecodeServer(plain_sf, plain_mc, eos_id=EOS, max_seq_len=32,
                       max_slots=2, len_ladder=[32], steps_per_tick=2,
                       name="kv-ref")
    try:
        srv.warmup(configure_cache=False)
        ref.warmup(configure_cache=False)
        prompt = [2, 9, 4, 6]
        want = _greedy_tokens(ref, prompt, 8)
        misses0 = srv._pool.jit_cache_stats()["misses"]
        # plain, speculative, then shared-prefix re-admission
        assert _greedy_tokens(srv, prompt, 8) == want
        req = srv.submit({"tokens": np.asarray(prompt, np.int32)},
                         max_new_tokens=8, speculative=True)
        assert req.result(timeout=WAIT)[0].tolist() == want
        assert _greedy_tokens(srv, prompt, 8) == want
        assert srv._pool.jit_cache_stats()["misses"] == misses0
        assert srv.metrics().get("recompiles", 0) == 0
    finally:
        srv.stop(drain=False)
        ref.stop(drain=False)


def test_endpoint_round_trip_and_healthz_advertise(tmp_path, lm_state):
    """save/load_decode_endpoint persists kv_dtype; /healthz advertises
    it next to precision/sharded for fleet discovery."""
    from paddle_tpu.serving.wire import RemoteClient
    from paddle_tpu.serving.wire.server import ServingProcess

    d = save_decode_endpoint(
        str(tmp_path / "ep"), lm_state, vocab_size=V,
        d_model=LM["d_model"], n_layer=LM["n_layer"],
        n_head=LM["n_head"], d_inner=LM["d_inner"], eos_id=EOS,
        max_seq_len=32, max_slots=2, kv_dtype="int8")
    srv = load_decode_endpoint(d, name="kv-ep")
    try:
        assert srv.kv_dtype == "int8"
        srv.warmup(configure_cache=False)
        sp = ServingProcess(srv)
        sp.start()
        cli = RemoteClient(sp.address)
        try:
            h = cli.healthz()
            assert h["kv_dtype"] == "int8"
            assert "row_dtype" in h  # advertised (None: no mesh tables)
        finally:
            cli.close()
            sp.stop(drain=False)
            srv = None  # ServingProcess.stop stopped it
    finally:
        if srv is not None:
            srv.stop(drain=False)
    with pytest.raises(ValueError):
        save_decode_endpoint(
            str(tmp_path / "bad"), lm_state, vocab_size=V,
            d_model=LM["d_model"], n_layer=LM["n_layer"],
            n_head=LM["n_head"], d_inner=LM["d_inner"], eos_id=EOS,
            max_seq_len=32, kv_dtype="fp8")


def test_fleet_top_dtype_column():
    """fleet_top renders a per-backend dtype tag composed from the
    federated statusz: precision default + non-fp32 KV / row rungs."""
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import fleet_top

    reg = {"sharding_sparse_row_dtype": {"series": [
        {"labels": {"table": "t", "dtype": "int8"}, "value": 1}]}}
    m = {"precision_dtypes": ["bf16", "fp32"],
         "decode": {"kv_dtype": "int8"}}
    assert fleet_top._dtype_tag(m, reg) == "bf16+kv:int8+row:int8"
    assert fleet_top._dtype_tag({"qps": 1.0}, {}) == "fp32"
    assert fleet_top._dtype_tag({}, {}) == "-"
    statusz = {
        "fleet": "f",
        "balancer": {"backends": {"b0": {"alive": True, "in_flight": 0}}},
        "backends": {"b0": {"statusz": {"metrics": m, "registry": reg},
                            "age_s": 0.1}},
    }
    frame = fleet_top.render_frame(statusz, {}, {}, color=False)
    assert "dtype" in frame and "bf16+kv:int8+" in frame
