"""Records the small ``.xplane.pb`` the trace-reduction tests read.

Run ON THE CHIP (``chiprun -- python3 benchmark/tests/record_fixture.py``):
a tiny jitted program is dispatched three times under ``bench/step``
annotations with a host-side pause between dispatches, so the trace
holds device ops, modules, host spans and idle gaps of known order.
The file lands in ``chiprun_out/fixture/``; the copy the tests use is
``benchmark/tests/fixture_tpu.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import time


def main():
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.stderr.write("record_fixture: needs a TPU\n")
        return 2
    out = os.path.join("chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    @jax.jit
    def step(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x, jnp.sum(x.astype(jnp.float32) ** 2)

    x = jnp.ones((512, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    jax.block_until_ready(step(x, w))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/step"):
            x, s = step(x, w)
            float(s)
        with jax.profiler.TraceAnnotation("bench/pause"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "fixture_tpu.xplane.pb"))
    print("fixture", os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
