"""HBM-bandwidth floor model for the ResNet-50 training step (VERDICT r4 #1).

The round-3 verdict framed the 68 vs 122 TF/s gap as "lost inside the
framework step".  The xprof trace (MFU_ANALYSIS.md; read today with
benchmark/xplane.py, which replaced tools/xprof_lines.py) shows otherwise: the
conv fusions themselves run AT the raw conv ceiling (~25ms of the 45.6ms
step); the rest is BatchNorm statistics + backward reductions and
normalize/residual elementwise passes.  On a TPU core ops execute serially —
a bandwidth-bound fusion cannot overlap a compute-bound conv — so the step
floor is conv_MXU_time + HBM_traffic / achievable_bandwidth.

This tool makes that floor quantitative:
  1. measures achievable streaming HBM bandwidth (triad-style: 2 reads +
     1 write of a large bf16 array, and a reduce: 1 read -> scalar),
  2. computes the analytic minimum HBM traffic of BN-train + residual +
     pool passes over the ResNet-50 activation inventory,
  3. prints floor step time, floor MFU, and the measured/floor ratio.

Run: python tools/hbm_floor.py [--batch 128] [--trials 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from conv_ceiling import RESNET50_CONVS, _rate_two_point, peak_flops  # noqa: E402


def activation_inventory(batch):
    """(elements, has_bn, has_relu) per conv output in one fwd pass."""
    out = []
    for (_, h, cin, cout, k, s, cnt) in RESNET50_CONVS:
        h_out = -(-h // s)
        out.append((batch * h_out * h_out * cout, cnt))
    return out


def bn_train_hbm_bytes(batch, bpe=2):
    """Minimum HBM passes for BN training over every conv output.

    Per BN layer over activation x (E elements, bpe bytes each):
      fwd:  stats reduce (read x)            — often fused into the producing
            conv's epilogue, but the read happens either way; normalize
            (read x, write y).
      bwd:  grad reduces (read dy, read x)   — one fused pass, two operands;
            dx elementwise (read dy, read x, write dx).
    Total = 8 passes of E*bpe bytes.  The residual add chain (16 block joins)
    adds read+read+write fwd and read+write per branch bwd on the block
    output; counted separately below.
    """
    total = 0.0
    for e, cnt in activation_inventory(batch):
        total += 8 * e * bpe * cnt
    return total


def residual_pool_bytes(batch, bpe=2):
    # 16 bottleneck joins at their stage sizes (56^2x256, 28^2x512, 14^2x1024,
    # 7^2x2048), fwd: r+r+w, bwd: r+w for each of 2 branches ~= 5 passes.
    joins = [(3, 56 * 56 * 256), (4, 28 * 28 * 512),
             (6, 14 * 14 * 1024), (3, 7 * 7 * 2048)]
    t = sum(cnt * 5 * batch * e * bpe for cnt, e in joins)
    # stem maxpool fwd+bwd (112^2x64 in, 56^2x64 out): ~r + w + r + r + w
    t += batch * (112 * 112 * 64 * 3 + 56 * 56 * 64 * 2) * bpe
    return t


def measure_stream(trials):
    import jax
    import jax.numpy as jnp

    n = 256 * 1024 * 1024 // 2  # 256MB of bf16

    @jax.jit
    def triad(a, b, k, it):
        def body(i, ab):
            a, b = ab
            return (b * k + a, a)
        a, b = jax.lax.fori_loop(0, it, body, (a, b))
        return a.sum()

    a = jnp.ones((n,), jnp.bfloat16)
    b = jnp.full((n,), 2.0, jnp.bfloat16)

    def run(it, seed=0):
        float(triad(a, b, jnp.bfloat16(1.0 + seed * 1e-6), it))

    bytes_per_iter = 3 * n * 2  # 2 reads + 1 write
    bw_triad = _rate_two_point(run, bytes_per_iter, trials, 20)

    @jax.jit
    def reduce_loop(a, it):
        def body(i, s):
            return s + (a * (1.0 + s * 1e-30)).sum()
        return jax.lax.fori_loop(0, it, body, jnp.zeros((), jnp.float32))

    def run_r(it, seed=0):
        float(reduce_loop(a * (1 + seed * 1e-6), it))

    bw_reduce = _rate_two_point(run_r, n * 2, trials, 20)
    return bw_triad, bw_reduce


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--conv-ceiling-tflops", type=float, default=122.02,
                    help="tools/conv_ceiling.py aggregate for this chip")
    ap.add_argument("--measured-step-ms", type=float, default=45.6)
    args = ap.parse_args()

    import jax
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import resnet50_model_flops

    bw_triad, bw_reduce = measure_stream(args.trials)

    flops = 3.0 * resnet50_model_flops(args.batch)
    conv_ms = flops / (args.conv_ceiling_tflops * 1e12) * 1e3

    bn_bytes = bn_train_hbm_bytes(args.batch)
    rp_bytes = residual_pool_bytes(args.batch)
    # charge the elementwise traffic at the measured triad bandwidth
    mem_ms = (bn_bytes + rp_bytes) / bw_triad * 1e3

    floor_ms = conv_ms + mem_ms
    peak = peak_flops(jax.devices()[0])
    floor_mfu = flops / (floor_ms / 1e3) / peak
    meas_mfu = flops / (args.measured_step_ms / 1e3) / peak

    print(json.dumps({
        "stream_triad_gbps": round(bw_triad / 1e9, 1),
        "stream_reduce_gbps": round(bw_reduce / 1e9, 1),
        "conv_ceiling_ms": round(conv_ms, 2),
        "bn_traffic_gb": round(bn_bytes / 1e9, 2),
        "residual_pool_traffic_gb": round(rp_bytes / 1e9, 2),
        "memory_ms_at_stream_bw": round(mem_ms, 2),
        "floor_step_ms": round(floor_ms, 2),
        "floor_mfu": round(floor_mfu, 4),
        "measured_step_ms": args.measured_step_ms,
        "measured_mfu": round(meas_mfu, 4),
        "measured_vs_floor": round(floor_ms / args.measured_step_ms, 3),
    }))


if __name__ == "__main__":
    main()
