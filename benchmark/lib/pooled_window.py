"""The measured window of a pooled serving family: the loop that
``pooled_decode_lm`` and ``pooled_hybrid_ssm_lm`` each carry in their
``run`` (PERF.md section 7 lists those copies as something to delete;
a benchmark PR can point them here), once, for the families that come
after them."""
from __future__ import annotations

import time


def measure(ctx, counters_now):
    """Open the window, sleep through ``ctx.seconds`` (starting the
    tracer when its tail begins), close the counters.  Returns ``(c0,
    c1, w0, t1)``: the counters at both ends, the window's first instant
    and the instant the counters were closed.  The caller stops its
    load and then calls ``ctx.close_window(t1)``."""
    c0 = counters_now()
    w0 = ctx.open_window()
    w1 = w0 + ctx.seconds
    while True:
        left = w1 - time.perf_counter()
        if left <= 0:
            break
        ctx.tracer.maybe_start(w1)
        time.sleep(min(left, 0.25))
    c1 = counters_now()
    t1 = time.perf_counter()
    ctx.tracer.stop()  # before the traffic does
    return c0, c1, w0, t1
