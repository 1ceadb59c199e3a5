"""Raw-XLA conv ceiling probe for the ResNet-50 MFU claim (VERDICT r2 #1).

Measures `lax.conv_general_dilated` throughput OUTSIDE the framework — one conv
per measurement, no layers, no BN, no framework graph — at every distinct conv
shape in ResNet-50 (with multiplicities), fwd-only and fwd+bwd, in bf16 NHWC.
From the per-shape measured rates it computes the *predicted ceiling MFU* for
full ResNet-50 training on this chip: if the framework's end-to-end MFU is close
to this number, the gap to the 50% north star is an XLA-conv/environment bound,
not a framework defect.

Also probes:
- a big bf16 matmul (MXU sanity ceiling),
- the space-to-depth stem alternative (4x4 s1 conv on 112x112x12 replacing the
  7x7 s2 conv on 224x224x3 — the MLPerf ResNet trick for the Cin=3 stem).

Methodology: device-side `lax.fori_loop` with the weight tensor in the carry
(perturbed each step by a value derived from the conv output, so XLA cannot
hoist or CSE the conv out of the loop) and a DYNAMIC trip count; each shape is
timed at n and 5n iterations and the rate taken from the difference (two-point
method), which cancels every per-call constant — dispatch, the scalar readback
the timing syncs on, the input perturbation — leaving the per-iteration device
time. Min-of-N trials per point. FLOPs are the standard
2*B*H'*W'*K*K*Cin*Cout for convs (fwd; bwd counted as 2x fwd = 3x total, the
conventional accounting used by MFU definitions), 2*M*N*K for matmul.

Run: python tools/conv_ceiling.py [--trials 3] [--batch 128]
Prints one JSON line; bench.py embeds the aggregate numbers in BENCH extras.
"""

from __future__ import annotations

import argparse
import json
import time

# (name, H_in, Cin, Cout, kernel, stride, count) — ResNet-50 conv inventory,
# NHWC, 224x224 input. count = how many times the shape occurs per fwd pass.
RESNET50_CONVS = [
    ("stem7x7s2",   224,    3,   64, 7, 2, 1),
    # stage 1 @56 (in 64 first block, then 256)
    ("s1_1x1_64_64",    56,  64,   64, 1, 1, 1),
    ("s1_3x3_64",       56,  64,   64, 3, 1, 3),
    ("s1_1x1_64_256",   56,  64,  256, 1, 1, 4),   # 3 expand + 1 downsample
    ("s1_1x1_256_64",   56, 256,   64, 1, 1, 2),
    # stage 2 @28 (3x3 stride-2 entry)
    ("s2_1x1_256_128",  56, 256,  128, 1, 1, 1),
    ("s2_3x3_128_s2",   56, 128,  128, 3, 2, 1),
    ("s2_1x1_256_512s2", 56, 256, 512, 1, 2, 1),   # downsample
    ("s2_1x1_128_512",  28, 128,  512, 1, 1, 4),
    ("s2_1x1_512_128",  28, 512,  128, 1, 1, 3),
    ("s2_3x3_128",      28, 128,  128, 3, 1, 3),
    # stage 3 @14
    ("s3_1x1_512_256",  28, 512,  256, 1, 1, 1),
    ("s3_3x3_256_s2",   28, 256,  256, 3, 2, 1),
    ("s3_1x1_512_1024s2", 28, 512, 1024, 1, 2, 1),
    ("s3_1x1_256_1024", 14, 256, 1024, 1, 1, 6),
    ("s3_1x1_1024_256", 14, 1024, 256, 1, 1, 5),
    ("s3_3x3_256",      14, 256,  256, 3, 1, 5),
    # stage 4 @7
    ("s4_1x1_1024_512", 14, 1024, 512, 1, 1, 1),
    ("s4_3x3_512_s2",   14, 512,  512, 3, 2, 1),
    ("s4_1x1_1024_2048s2", 14, 1024, 2048, 1, 2, 1),
    ("s4_1x1_512_2048",  7, 512, 2048, 1, 1, 3),
    ("s4_1x1_2048_512",  7, 2048, 512, 1, 1, 2),
    ("s4_3x3_512",       7, 512,  512, 3, 1, 2),
]


def conv_flops(batch, h_in, cin, cout, k, stride):
    h_out = -(-h_in // stride)  # SAME padding
    return 2.0 * batch * h_out * h_out * k * k * cin * cout


def _time(run, trials, n):
    """min-of-trials wall time of run(n[, trial]); the trial index lets
    callers perturb inputs so no two timed dispatches are byte-identical."""
    import inspect
    takes_seed = len(inspect.signature(run).parameters) > 1
    best = float("inf")
    for t in range(trials):
        t0 = time.perf_counter()
        run(n, t) if takes_seed else run(n)
        best = min(best, time.perf_counter() - t0)
    return best


def _rate_two_point(run, flops_per_iter, trials, n_lo):
    """FLOP/s from the (5n - n) time difference: immune to per-call
    constants (dispatch, readback)."""
    import inspect
    n_hi = 5 * n_lo
    # compile + warmup; out-of-band trial index so the warmup dispatch is not
    # byte-identical to timed trial 0 (dynamic trip count: one compile total)
    if len(inspect.signature(run).parameters) > 1:
        run(n_lo, trials)
    else:
        run(n_lo)
    t_lo = _time(run, trials, n_lo)
    t_hi = _time(run, trials, n_hi)
    dt = max(t_hi - t_lo, 1e-9)
    return flops_per_iter * (n_hi - n_lo) / dt


# Peak dense bf16 FLOP/s per chip by device_kind substring (public specs).
# Single source of truth — bench.py and tools/mfu_debug.py import these.
PEAK_FLOPS_TABLE = [
    ("v6", 918e12), ("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
]


def peak_flops(device) -> float:
    """Peak dense bf16 FLOP/s of ``device``.  A device_kind that is not in
    the table is an error: an MFU against an unknown peak is not a number."""
    kind = device.device_kind.lower()
    for key, peak in PEAK_FLOPS_TABLE:
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to PEAK_FLOPS_TABLE")


def probe_conv(batch, h, cin, cout, k, stride, trials, mode):
    import jax
    import jax.numpy as jnp
    from jax import lax

    dn = lax.conv_dimension_numbers((batch, h, h, cin), (k, k, cin, cout),
                                    ("NHWC", "HWIO", "NHWC"))

    def conv(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME", dimension_numbers=dn)

    @jax.jit
    def loop(x, w, n):
        if mode == "fwd":
            def body(i, w):
                y = conv(x, w)
                # output feeds back into the carried weight: not hoistable
                return w + (y.mean() * 1e-30).astype(w.dtype)
        else:  # "both": fwd + input-grad conv + weight-grad conv, like training
            def body(i, w):
                def f(w_, x_):
                    # quadratic loss: the cotangent depends on w, so the
                    # weight-grad conv is loop-variant (a linear loss has a
                    # constant cotangent and XLA hoists that conv entirely)
                    y = conv(x_, w_).astype(jnp.float32)
                    return (y * y).mean()
                gw, gx = jax.grad(f, argnums=(0, 1))(w, x)
                return w - (1e-30 * gw).astype(w.dtype) \
                         + (gx.mean() * 1e-30).astype(w.dtype)
        return lax.fori_loop(0, n, body, w).sum()

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, h, h, cin), jnp.bfloat16)
    w = jax.random.normal(key, (k, k, cin, cout), jnp.bfloat16)

    def run(n, trial=0):
        # trial-perturbed weights: no two timing dispatches are byte-identical
        float(loop(x, w + jnp.bfloat16(trial * 1e-8), n))

    # fwd = 1x; fwd+both grads = 3x fwd FLOPs (standard accounting)
    factor = {"fwd": 1.0, "both": 3.0}[mode]
    fl = conv_flops(batch, h, cin, cout, k, stride) * factor
    # scale the loop so the (5n-n) FLOP difference is big enough to rise above
    # host timing jitter regardless of shape size (~100 TFLOP difference)
    n_lo = max(8, int(25e12 / fl))
    return _rate_two_point(run, fl, trials, n_lo), fl


def probe_matmul(trials, m=8192, n=8192, kdim=8192):
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def loop(a, b, nn):
        def body(i, b):
            y = (a @ b).astype(jnp.bfloat16)
            return b + (y.mean() * 1e-30).astype(b.dtype)
        return lax.fori_loop(0, nn, body, b).sum()

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, kdim), jnp.bfloat16)
    b = jax.random.normal(key, (kdim, n), jnp.bfloat16)

    def run(nn, trial=0):
        float(loop(a, b + jnp.bfloat16(trial * 1e-8), nn))

    fl = 2.0 * m * n * kdim
    return _rate_two_point(run, fl, trials, max(8, int(25e12 / fl)))


def probe_s2d_stem(batch, trials):
    """Space-to-depth stem: 4x4 s1 conv on (112,112,12) — same math as the
    7x7 s2 stem (kernel zero-padded to 8x8 then block-reshaped), 4x the input
    channel depth for the MXU."""
    return probe_conv(batch, 112, 12, 64, 4, 1, trials, "both")


def measure(trials: int = 3, batch: int = 128, fwd_only: bool = False):
    """Run every probe on the current jax device; returns the result doc
    (what `main` prints, and what `bench.py --ceiling` embeds)."""
    import jax

    dev = jax.devices()[0]
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "device_count": len(jax.devices()), "batch": batch,
           "per_shape_tflops": {}}

    mode = "fwd" if fwd_only else "both"
    total_flops = 0.0     # fwd-pass conv FLOPs, weighted by multiplicity
    total_time = 0.0      # predicted time at measured per-shape rates
    for (name, h, cin, cout, k, s, cnt) in RESNET50_CONVS:
        rate, _ = probe_conv(batch, h, cin, cout, k, s, trials, mode)
        out["per_shape_tflops"][name] = round(rate / 1e12, 2)
        factor = 1.0 if mode == "fwd" else 3.0
        fl = conv_flops(batch, h, cin, cout, k, s) * factor * cnt
        total_flops += fl
        total_time += fl / rate

    agg = total_flops / total_time
    out["resnet50_conv_agg_tflops"] = round(agg / 1e12, 2)

    mm = probe_matmul(trials)
    out["matmul_8k_tflops"] = round(mm / 1e12, 2)

    s2d, _ = probe_s2d_stem(batch, trials)
    out["s2d_stem_tflops"] = round(s2d / 1e12, 2)
    stem = next(c for c in RESNET50_CONVS if c[0] == "stem7x7s2")
    stem_rate, _ = probe_conv(batch, stem[1], stem[2], stem[3], stem[4],
                              stem[5], trials, mode)
    out["stem7x7_tflops"] = round(stem_rate / 1e12, 2)

    # Predicted ceiling MFU for conv-dominated ResNet-50 training on this chip:
    # convs are ~95+% of ResNet FLOPs; BN/relu/pool are bandwidth-bound and
    # partially fused, so the honest ceiling is slightly below the conv
    # aggregate. Report the conv aggregate vs nameplate peak.
    peak = peak_flops(dev)
    out["conv_ceiling_mfu"] = round(agg / peak, 4)
    out["matmul_mfu"] = round(mm / peak, 4)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--fwd-only", action="store_true")
    args = ap.parse_args()
    print(json.dumps(measure(args.trials, args.batch, args.fwd_only)))


if __name__ == "__main__":
    main()
