"""Shared bench plumbing: the stacked fresh-batch feed regime.

Every model bench runs CHUNK optimizer steps per jitted call
(``Executor.run(steps=CHUNK)``), and by default feeds CHUNK *distinct*
batches per call via ``per_step_feed`` (a same-batch chunk is a
different HBM/infeed regime than a real input pipeline).
``BENCH_FRESH=0`` restores the same-batch regime for A/B
comparison.  This helper owns the env parse, leading-axis sizing, and
device staging so the four benches can't drift.
"""
import os
import sys

__all__ = [
    "fresh_enabled", "virtual_mesh_env", "stage_feeds", "prefetch_feeds",
    "flag_path", "metrics_out_path", "dump_metrics", "emit_result",
]


def fresh_enabled(default="1"):
    return os.environ.get("BENCH_FRESH", default) == "1"


def virtual_mesh_env(n=8, env=None):
    """Env-var overrides forcing an ``n``-device virtual CPU mesh:
    ``JAX_PLATFORMS=cpu`` plus ``xla_force_host_platform_device_count``
    appended to the existing XLA_FLAGS (read from ``env``, default
    ``os.environ``; an already-present device-count flag is kept as
    is).  The one definition behind every CPU-mesh bench stage — pass
    the returned dict to a subprocess env, or ``os.environ.update()``
    it BEFORE the first jax import for an in-process bench."""
    base = os.environ if env is None else env
    flags = base.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags
                 + " --xla_force_host_platform_device_count=%d" % n).strip()
    return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags}


# ---------------------------------------------------------------------------
# Metrics dump alongside the bench JSON line (paddle_tpu.monitor)
# ---------------------------------------------------------------------------
def flag_path(flag, env=None, argv=None):
    """Opt-in path argument: ``--<flag> PATH`` / ``--<flag>=PATH`` on
    the bench command line, falling back to ``$<env>``.  Returns None
    when not requested (shared by ``--metrics-out``, ``--trace-out``)."""
    argv = sys.argv[1:] if argv is None else list(argv)
    for i, arg in enumerate(argv):
        if arg == flag and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith(flag + "="):
            return arg.split("=", 1)[1]
    return (os.environ.get(env) or None) if env else None


def metrics_out_path(argv=None):
    """Opt-in registry dump target: ``--metrics-out PATH`` /
    ``--metrics-out=PATH`` on the bench command line, or
    ``$BENCH_METRICS_OUT``.  Returns None when not requested."""
    return flag_path("--metrics-out", "BENCH_METRICS_OUT", argv)


def dump_metrics(path):
    """Write the process-global monitor registry snapshot as JSON —
    every counter/gauge/histogram the run touched (executor jit cache,
    reader stalls, serving counters, predictor padding waste)."""
    import json

    from paddle_tpu import monitor

    with open(path, "w") as f:
        json.dump(monitor.snapshot(), f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def emit_result(result, argv=None):
    """Print the bench's ONE JSON line; when ``--metrics-out`` (or
    $BENCH_METRICS_OUT) is set, dump the registry snapshot next to it."""
    import json

    print(json.dumps(result), flush=True)
    path = metrics_out_path(argv)
    if path:
        dump_metrics(path)
    return result


def prefetch_feeds(stacked, fresh, chunk, device, size=2, compiled=None):
    """Device-prefetch variant of ``stage_feeds``: instead of pinning one
    staged feed in HBM forever, a background thread ``jax.device_put``s
    chunk feeds ahead of the consumer (reader.device_buffered), so the
    bench exercises the real input-pipeline regime — h2d of chunk N+1
    overlaps device compute of chunk N, and run() sees jax Arrays.

    ``compiled``: a CompiledProgram upgrades the staging to SHARDED
    prefetch — each mesh replica's batch slice lands in its own HBM
    (run the bench with ``exe.run(compiled, ...)`` to match).

    Returns (chunk_iter, close, feed1, run_kw): pull ``next(chunk_iter)``
    per ``exe.run(**run_kw)`` call and ``close()`` when done (stops the
    producer thread).
    """
    import jax

    from paddle_tpu import reader as _reader

    if compiled is not None and fresh:
        # sharded per_step_feed chunks: feed the per-step batches through
        # device_buffered(steps=chunk) so the reader owns the stacking —
        # the leading steps axis must stay REPLICATED while the batch
        # axis shards (pre-stacked arrays would shard the wrong axis)
        def stream():
            while True:  # open-ended; the consumer closes us
                for i in range(chunk):
                    yield {k: v[i] for k, v in stacked.items()}

        gen = _reader.device_buffered(
            stream, size=size, steps=chunk, compiled=compiled)()
    else:
        host = {k: (v if fresh else v[0]) for k, v in stacked.items()}

        def stream():
            while True:  # open-ended; the consumer closes us
                yield host

        gen = _reader.device_buffered(
            stream, size=size, device=device, compiled=compiled)()
    feed1 = {k: jax.device_put(v[0], device) for k, v in stacked.items()}
    run_kw = dict(return_numpy=False, steps=chunk, per_step_feed=fresh)
    return iter(gen), gen.close, feed1, run_kw


def stage_feeds(stacked, fresh, chunk, device):
    """``stacked``: dict name -> np array of shape (chunk,) + batch_shape
    (callers may build it with n_b = chunk if fresh else 1 to avoid
    allocating unused host batches).

    Returns (feed, feed1, run_kw):
      * feed  — device-staged chunked feed (stacked when fresh, else
        the single batch), for ``exe.run(**run_kw)``
      * feed1 — device-staged single batch, for single-step warmup
      * run_kw — dict(return_numpy=False, steps=chunk,
        per_step_feed=fresh)
    """
    import jax

    feed = {
        k: jax.device_put(v if fresh else v[0], device)
        for k, v in stacked.items()
    }
    feed1 = {k: jax.device_put(v[0], device) for k, v in stacked.items()}
    run_kw = dict(return_numpy=False, steps=chunk, per_step_feed=fresh)
    return feed, feed1, run_kw
