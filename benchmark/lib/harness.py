"""What every family gets from ``run.py``: the cell's data files, a
clock for the set-up split, the window's bookkeeping and the tracer.

Nothing here knows a model or a traffic mix; a family (one file under
``benchmark/families/``) does the work and returns a dict:

``correct``     bool: every check of the run held
``checks``      {name: bool}: the checks, for the log
``attempted``   operations that reached an end inside the window
``failed``      those of them that failed
``end_to_end``  {metric: value}: taken by the benchmark's own clock
``counters``    {name: number or list}: what the layer-metric readers
                get (program counters as deltas over the window, the
                submitter's samples, shapes-derived byte and FLOP counts)
"""
from __future__ import annotations

import contextlib
import gc
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

TRACE_TAIL_S = 3.0  # the profiler runs over the window's last seconds


def load_py(path: str, name: str):
    """Import one file by path: families, references and layer-metric
    readers are found by the names in the data files, never by an edit
    to an import list."""
    if not os.path.isfile(path):
        raise FileNotFoundError("%s: no such file (looked for %r)"
                                % (name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark_dyn_" + name.replace(".", "_").replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(path: str, rehearse: bool) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse", {})
    return _merge(cfg, tiny) if rehearse else cfg


def configure_jax(rehearse: bool):
    """The process's jax set-up, the same for a run and for the sweep.
    Call before anything compiles.  Returns the cache directory."""
    # The compile cache lives at a fixed path inside the checkout,
    # whatever the environment says: the program's compile_cache module
    # takes the directory it is given here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    from paddle_tpu import compile_cache

    if rehearse:
        # a CPU executable with donated state must not come from disk
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        # keep every program of the cell, however quick to build, so the
        # second run of a cell in a checkout compiles nothing; and evict
        # none: one BERT cell's programs are 200 MB, and an LRU cache
        # capped below a cell's set never hits at all
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
    return compile_cache.configure()


def say(tag: str, **kv) -> None:
    """An earlier line of the run's output (never the last)."""
    print("%s %s" % (tag, json.dumps(kv, sort_keys=True, default=str)),
          flush=True)


class Tracer:
    """jax's profiler over the last ``tail_s`` seconds of the
    window, the program's host spans over the same stretch."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.t_start = self.t_stop = None
        self.spans = []
        # a family whose dispatches are long sets this so that the trace
        # holds two whole dispatches and the gap between them
        self.tail_s = TRACE_TAIL_S

    def maybe_start(self, window_end: float) -> None:
        """Call from the measuring loop; starts once, when the window
        has ``tail_s`` left."""
        if (not self.enabled or self.t_start is not None
                or time.perf_counter() < window_end - self.tail_s):
            return
        import jax

        from paddle_tpu.monitor import spans as prog_spans

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # TraceMe spans only: small, cheap
        prog_spans.start_recording(max_spans=200000)
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if self.t_start is None or self.t_stop is not None:
            return
        import jax

        from paddle_tpu.monitor import spans as prog_spans

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.spans = prog_spans.stop_recording()

    def xplane_path(self):
        if self.dir is None:
            return None
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


class Context:
    def __init__(self, *, cell, cfg, mix, seed, seconds, trace, rehearse,
                 device, peaks, watch, t_process_start):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed, self.seconds = int(seed), float(seconds)
        self.rehearse, self.device, self.peaks = rehearse, device, peaks
        self.watch = watch
        self.t_process_start = t_process_start
        self.tracer = Tracer(bool(trace))
        self.setup_split = {}
        self._last_phase_end = t_process_start
        self.window = {}

    say = staticmethod(say)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Book the seconds since the previous phase ended to ``name``:
        the phases tile set-up with no gap."""
        try:
            yield
        finally:
            now = time.perf_counter()
            self.setup_split[name] = (self.setup_split.get(name, 0.0)
                                      + now - self._last_phase_end)
            self._last_phase_end = now

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_window(self) -> float:
        """The first instant of the measured window: everything before
        it is ``setup_s``.  Garbage made by set-up is collected and the
        survivors frozen, so no full collection lands in the window."""
        gc.collect()
        gc.freeze()
        self.window["compile_mark"] = self.watch.mark()
        t0 = time.perf_counter()
        self.setup_split["other"] = t0 - self._last_phase_end
        self.window["t0"] = t0
        self.window["setup_s"] = t0 - self.t_process_start
        return t0

    def close_window(self, t1: float) -> None:
        self.window["t1"] = t1
        self.window["compiles"] = self.watch.delta(
            self.window["compile_mark"], self.watch.mark())
        self.tracer.stop()
