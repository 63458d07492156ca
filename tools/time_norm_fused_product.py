#!/usr/bin/env python
"""What a 64-row product costs with the norm BEHIND it, on the chip: the
product and the norm's sum of squares in one fusion (what the compiler
makes of ``rms_norm(linear(x, w))``), the product COMPLETE first
(``jax.lax.optimization_barrier`` between the two), and the product
alone, turn and turn about in one process over the same matrix.

The shapes are ``openpangu_ultra_moe_718b.shared_docs_qa_mtp_16k``'s
round: 64 rows (32 slots x K = 2) through ``attn_o`` ``bf16[16384,7680]``
under ``post_attn_norm``, the dense ``ffn_down`` ``bf16[18432,7680]``
under ``post_mlp_norm``, the module's ``eh`` ``bf16[15360,7680]`` under
its block's ``input_norm`` (the shared expert's ``bf16[2048,7680]`` is
left out: 31 MB alone in a program stay in fast memory and read nothing
like the round's).

One jitted program makes ``--passes`` passes over the matrix (each
pass's rows depend on the norm before, so none is elided), a second
makes one; a launch costs the host's clock about a millisecond whatever
the program holds, so a pass's time is the SLOPE between the two
(``tools/time_delta_update.py`` says why).  Printed a variant: ms a pass
and the matrix's bytes over it in GB/s.

    python tools/time_norm_fused_product.py

``--rehearse-cpu`` runs a tiny shape to prove the script and prints no
number a reader could take for the chip's.  ``bit_equal_to_fused`` says
whether the barriered norm's values are the fused one's (on the CPU
always: a barrier is the identity; on the chip the norm's sum of squares,
in a fusion of its own, may add in another order).  The last line of
output is one JSON object.

Chip runs, PR 64: see ``PERF.md`` §6 (PR 64).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

from time_dense_latent import timed  # noqa: E402

#: (name, rows in, rows of the matrix, its columns)
SHAPES = (("attn_o", 64, 16384, 7680), ("ffn_down", 64, 18432, 7680),
          ("eh", 64, 15360, 7680))
REHEARSAL = (("attn_o", 8, 256, 128),)
FORMS = ("fused", "barrier", "bare")


def program(form: str, passes: int):
    """``passes`` products of the rows with the matrix, each pass's rows
    moved by the pass before: its norm (``fused``: as written; ``barrier``:
    the product complete first) or, ``bare``, the product itself."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.hybrid_ssm import linear, rms_norm

    def run(x, w, g):
        z = None
        for _ in range(passes):
            # every column of the pass before moves the rows: a product
            # whose columns feed nothing is cut down to those that do
            y = linear(x if z is None else x + 1e-6 * jnp.tile(
                z, (1, -(-x.shape[1] // z.shape[1])))[:, :x.shape[1]], w)
            if form == "barrier":
                y = jax.lax.optimization_barrier(y)
            z = y if form == "bare" else rms_norm(y, g, 1e-6)
        return z

    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--passes", type=int, default=9)
    ap.add_argument("--calls", type=int, default=15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit("no TPU here (%s): a product's time is a chip "
                         "number; --rehearse-cpu proves the script"
                         % dev.platform)
    rng = np.random.RandomState(args.seed)
    rows = []
    for name, n, k, m in REHEARSAL if args.rehearse_cpu else SHAPES:
        data = (jnp.asarray(rng.randn(n, k), jnp.float32),
                jnp.asarray(0.02 * rng.randn(k, m), jnp.bfloat16),
                jnp.asarray(1.0 + 0.1 * rng.randn(m), jnp.float32))
        normed = {f: np.asarray(program(f, 2)(*data)) for f in FORMS[:2]}
        for form in FORMS:
            row = {"product": name, "form": form, "rows": n,
                   "matrix": [k, m]}
            if form == "barrier":
                row["bit_equal_to_fused"] = bool(
                    (normed["fused"] == normed["barrier"]).all())
            if not args.rehearse_cpu:
                one, many = (timed(program(form, p), data, args.calls)
                             for p in (1, args.passes))
                ms = 1e3 * (many - one) / (args.passes - 1)
                row.update(ms_a_pass=ms, gb_per_s=2e-6 * k * m / ms)
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"tool": "time_norm_fused_product",
                      "rehearsal": bool(args.rehearse_cpu),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "rows": rows}))


if __name__ == "__main__":
    main()
