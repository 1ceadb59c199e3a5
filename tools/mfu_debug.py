"""MFU ablation harness: localize where ResNet-50 training time goes.

Variants timed with the same two-point (n vs 5n) device-side-loop methodology
as tools/conv_ceiling.py (per-call constants cancel in the difference):

  full      — the exact bench.py step: fwd + loss + bwd + SGD-momentum update
  fwd       — model forward only
  fwdbwd    — fwd + loss + grads (no optimizer update)
  nobn      — fwdbwd with BatchNormalization replaced by a per-channel
              scale+shift (no batch statistics): isolates BN reduction cost
  b256      — full step at batch 256
  s2d       — full step with the space-to-depth stem (resnet(stem="s2d"))

Each reports achieved TFLOP/s against the XLA cost model of its own lowering,
and MFU vs nameplate peak. Run: python tools/mfu_debug.py [--variants full,fwd]
"""

from __future__ import annotations

import argparse
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import json

from conv_ceiling import _rate_two_point  # shared two-point methodology


def build_step(batch, variant):
    import jax
    import jax.numpy as jnp
    import optax

    from analytics_zoo_tpu.common import dtypes
    from analytics_zoo_tpu.models.imageclassification import resnet
    from analytics_zoo_tpu.nn import objectives
    from analytics_zoo_tpu.nn.optimizers import SGD

    dtypes.mixed_bf16()

    if variant == "nobn":
        # swap BN for a stateless scale+shift before graph construction
        from analytics_zoo_tpu.nn.layers import core

        class FakeBN(core.BatchNormalization):
            def init_state(self, input_shape):
                return {}

            def apply(self, params, state, x, *, training=False, rng=None):
                ax = self.axis if self.axis >= 0 else x.ndim + self.axis
                bshape = tuple(x.shape[i] if i == ax else 1
                               for i in range(x.ndim))
                y = x * params["gamma"].reshape(bshape).astype(x.dtype) \
                    + params["beta"].reshape(bshape).astype(x.dtype)
                return y, state

        import analytics_zoo_tpu.models.imageclassification as ic
        orig = core.BatchNormalization
        core.BatchNormalization = FakeBN
        ic.BatchNormalization = FakeBN
        try:
            model = resnet(50, num_classes=1000)
        finally:
            core.BatchNormalization = orig
            ic.BatchNormalization = orig
    elif variant == "s2d":
        model = resnet(50, num_classes=1000, stem="s2d")
    elif variant == "nopool":
        # stem max-pool -> stride-2 avg-pool (cheap backward): isolates the
        # cost of select_and_scatter in maxpool's VJP
        from analytics_zoo_tpu.nn.layers import pooling
        import analytics_zoo_tpu.models.imageclassification as ic

        class AvgAsMax(pooling.AveragePooling2D):
            pass

        orig_mp = ic.MaxPooling2D
        ic.MaxPooling2D = lambda *a, **k: AvgAsMax(*a, **k)
        try:
            model = resnet(50, num_classes=1000)
        finally:
            ic.MaxPooling2D = orig_mp
    else:
        model = resnet(50, num_classes=1000)

    params, state = model.init(jax.random.PRNGKey(0))
    opt = SGD(lr=0.1, momentum=0.9)
    opt_state = opt.init(params)
    loss_fn = objectives.get("sparse_categorical_crossentropy")

    key = jax.random.PRNGKey(1)
    imgs = jax.random.normal(key, (batch, 224, 224, 3), jnp.bfloat16)
    labels = jax.random.randint(key, (batch, 1), 0, 1000).astype(jnp.float32)

    if variant == "fwd":
        @jax.jit
        def loop(params, state, n):
            def body(i, c):
                p, s = c
                y, s2 = model.apply(p, s, imgs, training=True, rng=None)
                # feed output back into params so the fwd pass is loop-variant
                leaf = jax.tree.leaves(p)[0]
                p = jax.tree.map(lambda a: a + (y.mean() * 1e-30).astype(a.dtype), p)
                return (p, s2)
            p, s = jax.lax.fori_loop(0, n, body, (params, state))
            return jax.tree.leaves(p)[0].sum()

        def run(n):
            float(loop(params, state, n))
        single = jax.jit(lambda p, s: model.apply(p, s, imgs, training=True,
                                                  rng=None)[0].sum())
        cost = single.lower(params, state).compile().cost_analysis()
        return run, float(cost.get("flops", 0.0))

    def train_step(p, o, s):
        def loss_of(pp):
            y_pred, s2 = model.apply(pp, s, imgs, training=True, rng=None)
            return loss_fn(y_pred, labels).mean(), s2
        (l, s2), grads = jax.value_and_grad(loss_of, has_aux=True)(p)
        if variant in ("full", "b256", "s2d", "nopool"):
            updates, o = opt.update(grads, o, p)
            p = optax.apply_updates(p, updates)
        else:  # fwdbwd / nobn: fold grads into params so the loop is variant
            p = jax.tree.map(lambda a, g: a - 1e-30 * g.astype(a.dtype),
                             p, grads)
        return p, o, s2

    @jax.jit
    def loop(params, opt_state, state, n):
        def body(i, c):
            return train_step(*c)
        p, o, s = jax.lax.fori_loop(0, n, body, (params, opt_state, state))
        return jax.tree.leaves(p)[0].sum()

    def run(n):
        float(loop(params, opt_state, state, n))

    single = jax.jit(lambda p, o, s: train_step(p, o, s)[0])
    cost = single.lower(params, opt_state, state).compile().cost_analysis()
    return run, float(cost.get("flops", 0.0))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default="full,fwd,fwdbwd,nobn,b256")
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()

    import jax

    from conv_ceiling import peak_flops
    peak = peak_flops(jax.devices()[0])
    out = {}
    for v in args.variants.split(","):
        batch = 256 if v == "b256" else 128
        run, flops = build_step(batch, v)
        n_lo = max(2, int(25e12 / max(flops, 1.0)))
        rate = _rate_two_point(run, flops, args.trials, n_lo)
        out[v] = {"tflops": round(rate / 1e12, 2),
                  "mfu": round(rate / peak, 4),
                  "cost_model_flops": flops}
        print(json.dumps({v: out[v]}), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
