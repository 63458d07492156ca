"""What ``KVSlotPool._lower`` hoists out of a step lives on the device.

A numpy array a decode step closes over comes out of the pool's trace
as a HOST-BORN executable argument, and an executable bound to it sends
it to the device again on every call (24 transfers a ``chunk`` of
``gpt1_117m`` on the chip: the kernel's indicator matrices).  The pool
places every such constant once, at lowering.  Three steps that close
over numpy constants on the CPU: a toy, and the rehearsal sizes of the
benchmark's ``falcon_h1_34b`` and ``minicpm_sala`` as their families
build them (``tools/time_pool_dispatch.build_step``)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import decoding, monitor
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 61


def _toy_step():
    """A step over one K leaf whose logits go through two numpy
    matrices (equal bytes: one device copy) and a numpy vector."""
    rng = np.random.RandomState(0)
    embed = jax.device_put(
        jnp.asarray(rng.randn(VOCAB, 8), jnp.float32), jax.devices()[0])
    mix = rng.randn(8, 8).astype(np.float32)
    head = rng.randn(8, VOCAB).astype(np.float32)

    def step(cache, tok, pos):
        x = embed[tok] @ jnp.asarray(mix) + embed[tok] @ jnp.asarray(
            mix.copy())
        rows = jnp.arange(tok.shape[0])
        k = cache["k"].at[rows, jnp.maximum(pos, 0)].set(x)
        live = (jnp.arange(k.shape[1])[None, :] <= pos[:, None])[..., None]
        ctx = (k * live).sum(1)
        return (x + ctx) @ jnp.asarray(head), {"k": k}

    def make_cache(n_rows, seq_len):
        return {"k": jnp.zeros((n_rows, seq_len, 8), jnp.float32)}

    decoding.declare(make_cache, decoding.CacheSpec(
        {"k": decoding.Leaf(1)}))
    return step, make_cache, dict(vocab=VOCAB, slots=4, rungs=[16, 32],
                                  steps=2, kv_dtype="fp32")


def _rehearsal_step(config):
    spec = importlib.util.spec_from_file_location(
        "time_pool_dispatch", os.path.join(
            ROOT, "tools", "time_pool_dispatch.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cfg, _, step, make_cache = tool.build_step(ROOT, config, True)
    sv = cfg["serving"]
    t = int(sv["len_ladder"][-1])
    return step, make_cache, dict(
        vocab=int(cfg["vocab_size"]), slots=int(sv["slot_ladder"][-1]),
        rungs=[t // 2, t], steps=int(sv["steps_per_tick"]),
        kv_dtype=sv["kv_dtype"])


@pytest.fixture(scope="module", params=["toy", "falcon_h1_34b",
                                        "minicpm_sala"])
def built(request):
    """(pool, warmed; its step parts) over two length rungs."""
    step, make_cache, d = (_toy_step() if request.param == "toy"
                           else _rehearsal_step(request.param))
    snapshots = getattr(make_cache, "prefill_fn", None) is not None
    pool = KVSlotPool(step, make_cache, eos_id=d["vocab"],
                      max_slots=d["slots"], max_seq_len=d["rungs"][-1],
                      slot_ladder=[d["slots"]], len_ladder=d["rungs"],
                      steps=d["steps"], kv_dtype=d["kv_dtype"],
                      prefix=snapshots)
    pool.warmup()
    return pool, step, make_cache, d


def _seated(pool, d, t):
    """A device state at length rung ``t`` with two prompts seated."""
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, d["vocab"], n).astype(np.int32)
               for n in (5, 9)]
    state = pool.admit(pool.alloc(d["slots"], t), [0, 2], prompts,
                       [5, 9], [t, t])
    return jax.block_until_ready(state)


def test_every_bound_constant_is_on_the_pools_device(built):
    pool = built[0]
    found = pool.host_born_constants()
    assert set(found) == set(pool._kinds())
    # the steps here DO close over numpy constants: nothing is vacuous
    assert found["chunk"][0] >= 1 and found["chunk"][1] > 0
    if "prefill" in found:
        assert found["prefill"][0] >= 1
    dev = jax.devices()[0]
    for key, exe in pool._exe.items():
        for const in exe.args[0]:
            assert isinstance(const, jax.Array), (key, type(const))
            assert const.devices() == {dev}, key


def test_chunk_sends_the_device_nothing(built):
    """``chunk``'s only argument is the state: with that on the device,
    a call makes no host-to-device transfer at all (the other kinds take
    host arguments — seats, a mask, a slot number — by design)."""
    pool, _, _, d = built
    for t in d["rungs"]:
        state = _seated(pool, d, t)
        with jax.transfer_guard_host_to_device("disallow"):
            state = pool.chunk(state)
            state = pool.chunk(state)
        assert int(np.asarray(state["pos"])[0]) == 2 * d["steps"]


def test_rung_pairs_and_kinds_share_one_device_copy(built):
    """Equal constants are ONE device array per pool, whatever the
    layer, the kind and the rung pair; the count of copies made is what
    the pool reports."""
    pool, _, _, d = built
    s = d["slots"]
    bound = {id(c) for exe in pool._exe.values() for c in exe.args[0]}
    placed = {id(c) for c in pool._placed.values()}
    assert placed <= bound
    assert pool.constants_placed == len(placed) >= 1
    lo, hi = (pool._exe["chunk", s, t].args[0] for t in d["rungs"])
    shared = {id(c) for c in lo} & {id(c) for c in hi} & placed
    # every placed constant of one rung pair's chunk serves the other's
    assert shared == {id(c) for c in lo} & placed != set()
    # fewer copies than host-born constants bound: layers share them
    assert len(placed) <= sum(n for n, _ in
                              pool.host_born_constants().values())


def test_tokens_equal_a_plain_jit_of_the_same_function(built):
    pool, _, _, d = built
    t = d["rungs"][-1]
    state = _seated(pool, d, t)
    plain = jax.jit(pool._chunk_fn)
    want = state
    for _ in range(3):
        state, want = pool.chunk(state), plain(want)
    for name in ("tokens", "pos", "n_gen", "finished"):
        np.testing.assert_array_equal(np.asarray(state[name]),
                                      np.asarray(want[name]))
    assert int(np.asarray(state["n_gen"])[0]) > 0


def test_placed_constants_outlive_every_state(built):
    """A server that goes idle drops its state and keeps the pool: the
    constants are not donated with a state and serve the next one."""
    pool, _, _, d = built
    t = d["rungs"][0]
    first = np.asarray(pool.chunk(_seated(pool, d, t))["tokens"])
    assert not any(c.is_deleted() for c in pool._placed.values())
    again = np.asarray(pool.chunk(_seated(pool, d, t))["tokens"])
    np.testing.assert_array_equal(first, again)
    assert pool.warmup() == 0   # a re-warm lowers, and places, nothing


def test_the_counter_advances_by_the_number_placed():
    step, make_cache, d = _toy_step()
    with DecodeServer(step, make_cache, eos_id=d["vocab"],
                      max_seq_len=d["rungs"][-1], max_slots=d["slots"],
                      slot_ladder=(d["slots"],),
                      len_ladder=tuple(d["rungs"]), steps_per_tick=2,
                      name="constants-placed") as srv:
        name = "serving_pool_constants_placed_total"
        before = monitor.counter_value(name)
        srv.warmup(configure_cache=False)
        # mix (twice, equal bytes: one copy) and head
        assert srv._pool.constants_placed == 2
        assert monitor.counter_value(name) - before == 2
        srv.warmup(configure_cache=False)
        assert monitor.counter_value(name) - before == 2
        out = srv.submit({"tokens": np.arange(4, dtype=np.int32)},
                         max_new_tokens=6).result(timeout=60)
        assert len(out[0]) >= 6 or out[0][-1] == d["vocab"]
        assert monitor.counter_value(name) - before == 2


def test_a_step_over_device_arrays_alone_places_nothing():
    rng = np.random.RandomState(0)
    embed = jnp.asarray(rng.randn(VOCAB, 8), jnp.float32)

    def step(cache, tok, pos):
        return embed[tok] @ embed.T, cache

    def make_cache(n_rows, seq_len):
        return {"k": jnp.zeros((n_rows, seq_len, 8), jnp.float32)}

    decoding.declare(make_cache, decoding.CacheSpec(
        {"k": decoding.Leaf(1)}))
    pool = KVSlotPool(step, make_cache, eos_id=VOCAB, max_slots=2,
                      max_seq_len=8, slot_ladder=[2], len_ladder=[8],
                      steps=1)
    pool.warmup()
    assert pool.constants_placed == 0
    assert pool.host_born_constants()["chunk"] == (0, 0)
    # the weight is bound as it is: the same array, not a copy
    assert any(c is embed for c in pool._exe["chunk", 2, 8].args[0])


def test_a_gap_between_requests_does_not_drop_the_pool_state(monkeypatch):
    """A server with nothing seated keeps its state through a gap
    between two arrivals (re-making it is what the next request would
    wait for: seconds at a real pool's size) and drops it once a whole
    idle wait passed with none."""
    import time

    from paddle_tpu.serving import decode as decode_mod

    monkeypatch.setattr(decode_mod, "_IDLE_WAIT_S", 3.0)
    step, make_cache, d = _toy_step()
    with DecodeServer(step, make_cache, eos_id=d["vocab"],
                      max_seq_len=d["rungs"][-1], max_slots=d["slots"],
                      slot_ladder=(d["slots"],),
                      len_ladder=tuple(d["rungs"]), steps_per_tick=2,
                      name="idle-gap") as srv:
        srv.warmup(configure_cache=False)
        ask = {"tokens": np.arange(4, dtype=np.int32)}
        first = srv.submit(ask, max_new_tokens=6).result(timeout=60)
        time.sleep(0.3)          # empty, but not for a whole wait yet
        assert srv._state is not None
        assert srv.metrics()["decode"]["kv_cache_bytes"] > 0
        again = srv.submit(ask, max_new_tokens=6).result(timeout=60)
        np.testing.assert_array_equal(first[0], again[0])
        deadline = time.time() + 20.0
        while srv._state is not None and time.time() < deadline:
            time.sleep(0.05)
        assert srv._state is None
        assert srv.metrics()["decode"]["kv_cache_bytes"] == 0
