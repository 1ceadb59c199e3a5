"""int8 vs bf16 measurement matrix on the MXU (VERDICT r4 #3).

Times raw s8xs8->s32 against bf16 (f32-accum) at:
  * dense matmul shapes (serving MLP / transformer projections), and
  * the ResNet-50 conv inventory's biggest shapes,
across batch sizes.  Decides whether the int8 PTQ path can ever beat bf16 on
this chip+XLA version, and at which shapes — the data behind
InferenceModel.do_quantize's defaults.

Run: python tools/int8_matrix.py [--trials 2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from conv_ceiling import _rate_two_point  # noqa: E402


def time_matmul(m, k, n, dtype, trials):
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def loop(x, w, it):
        # weight in the carry, output fed back in, so XLA cannot hoist the
        # dot out of the loop (conv_ceiling.py methodology)
        def body(i, ww):
            if dtype == "int8":
                y = jax.lax.dot(x, ww, preferred_element_type=jnp.int32)
                return ww + (y.sum() & 1).astype(jnp.int8)
            y = jax.lax.dot(x, ww, preferred_element_type=jnp.float32)
            return ww + (y.mean() * 1e-30).astype(ww.dtype)
        out = jax.lax.fori_loop(0, it, body, w)
        return out.astype(jnp.float32).sum()

    rng = np.random.default_rng(0)
    if dtype == "int8":
        x = jnp.asarray(rng.integers(-127, 127, (m, k)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8)

        def run(it, trial=0):
            # trial-perturbed weights: no two timing dispatches are
            # byte-identical
            float(loop(x, w + jnp.int8(trial % 2), it))
    else:
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.bfloat16)

        def run(it, trial=0):
            float(loop(x, w + jnp.bfloat16(trial * 1e-8), it))

    fl = 2.0 * m * k * n
    # (5n-n) window must rise above host jitter (conv_ceiling sizing rule)
    n_lo = max(8, int(25e12 / fl))
    return _rate_two_point(run, fl, trials, n_lo) / 1e12


def time_conv(batch, h, cin, cout, kk, stride, dtype, trials):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    dn = jax.lax.conv_dimension_numbers((batch, h, h, cin),
                                        (kk, kk, cin, cout),
                                        ("NHWC", "HWIO", "NHWC"))

    @jax.jit
    def loop(x, w, it):
        def body(i, ww):
            y = jax.lax.conv_general_dilated(
                x, ww, (stride, stride), "SAME", dimension_numbers=dn,
                preferred_element_type=(jnp.int32 if dtype == "int8"
                                        else jnp.float32))
            if dtype == "int8":
                return ww + (y.sum() & 1).astype(jnp.int8)
            return ww + (y.mean() * 1e-30).astype(ww.dtype)
        out = jax.lax.fori_loop(0, it, body, w)
        return out.astype(jnp.float32).sum()

    if dtype == "int8":
        x = jnp.asarray(rng.integers(-127, 127, (batch, h, h, cin)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 127, (kk, kk, cin, cout)),
                        jnp.int8)

        def run(it, trial=0):
            float(loop(x, w + jnp.int8(trial % 2), it))
    else:
        x = jnp.asarray(rng.normal(size=(batch, h, h, cin)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(kk, kk, cin, cout)), jnp.bfloat16)

        def run(it, trial=0):
            float(loop(x, w + jnp.bfloat16(trial * 1e-8), it))

    h_out = -(-h // stride)
    fl = 2.0 * batch * h_out * h_out * kk * kk * cin * cout
    n_lo = max(8, int(25e12 / fl))
    return _rate_two_point(run, fl, trials, n_lo) / 1e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=2)
    args = ap.parse_args()

    out = {"matmul": {}, "conv": {}}
    for (m, k, n) in [(256, 1024, 1024), (4096, 1024, 1024),
                      (8192, 4096, 4096)]:
        key = f"{m}x{k}x{n}"
        bf = time_matmul(m, k, n, "bf16", args.trials)
        q = time_matmul(m, k, n, "int8", args.trials)
        out["matmul"][key] = {"bf16_tflops": round(bf, 1),
                              "int8_tops": round(q, 1),
                              "speedup": round(q / bf, 3)}
    for (name, h, cin, cout, kk, s) in [
            ("stem7x7", 224, 3, 64, 7, 2),
            ("s1_3x3_64", 56, 64, 64, 3, 1),
            ("s3_3x3_256", 14, 256, 256, 3, 1),
            ("s4_1x1_2048_512", 7, 2048, 512, 1, 1)]:
        for batch in (64, 256):
            bf = time_conv(batch, h, cin, cout, kk, s, "bf16", args.trials)
            q = time_conv(batch, h, cin, cout, kk, s, "int8", args.trials)
            out["conv"][f"{name}_b{batch}"] = {
                "bf16_tflops": round(bf, 1), "int8_tops": round(q, 1),
                "speedup": round(q / bf, 3)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
