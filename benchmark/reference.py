"""Plain references and the comparisons that decide ``correct``.

Nothing here calls the model code under test: each forward is written out over the
parameter tree the program's ``build``/``init`` returns, in float32 with
``jax.default_matmul_precision("highest")`` (a float32 matmul otherwise rides bf16
passes on a TPU).
"""

from __future__ import annotations

import numpy as np

# Every served token's logit, in the reference's teacher-forced forward of the same
# weights, lies within this of that position's best logit.  The served path keeps
# float32 weights but its matmuls ride bf16 MXU passes, so near-ties may resolve
# differently from the reference's argmax; a token that is not a near-tie, a wrong
# cache block or a wrong position shows as a margin of order 1 (logits of the seeded
# weights spread over several units).  chip_smoke.GEN_LOGIT_TOL, same reason; one
# v5e chip measured 0.002-0.013 (PERF.md, Findings).
GEN_LOGIT_TOL = 0.05


def _layer_norm(p, x, eps=1e-5):
    import jax.numpy as jnp
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_tanh(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def lm_block(blk, x, n_head):
    """One pre-LN GPT-2 block over ``x`` (T, H), causal attention."""
    import jax
    import jax.numpy as jnp
    T, H = x.shape
    hd = H // n_head
    h = _layer_norm(blk["ln1"], x)
    qkv = h @ blk["qkv"]["W"] + blk["qkv"]["b"]
    q, k, v = (qkv[:, i * H:(i + 1) * H].reshape(T, n_head, hd)
               .transpose(1, 0, 2) for i in range(3))           # (nh, T, hd)
    att = q @ k.transpose(0, 2, 1) / np.sqrt(hd)
    att = jnp.where(jnp.tril(jnp.ones((T, T), bool)), att, -jnp.inf)
    o = (jax.nn.softmax(att, axis=-1) @ v).transpose(1, 0, 2).reshape(T, H)
    x = x + o @ blk["proj"]["W"] + blk["proj"]["b"]
    h = _layer_norm(blk["ln2"], x)
    h = _gelu_tanh(h @ blk["fc1"]["W"] + blk["fc1"]["b"])
    return x + h @ blk["fc2"]["W"] + blk["fc2"]["b"]


def lm_logit_margins(params, n_head: int, ids: np.ndarray, prompt_len: int,
                     pad_to: int) -> np.ndarray:
    """Teacher-forced forward of ``ids`` = prompt + served tokens (right-padded to
    ``pad_to``, which causal attention makes harmless).  Returns, for each served
    token, (best logit at its position) - (its own logit).  The block is one jitted
    function called once per layer, so one padded shape compiles once."""
    import jax
    import jax.numpy as jnp
    n = len(ids) - prompt_len
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lm_block, static_argnums=2)
        x = jnp.take(params["embed"], jnp.asarray(padded), axis=0) \
            + params["pos"][:pad_to]
        for blk in params["blocks"]:
            x = block(blk, x, n_head)
        rows = _layer_norm(params["ln_f"], x[prompt_len - 1:prompt_len - 1 + n])
        logits = np.asarray(rows @ params["embed"].T)           # tied head
    served = np.asarray(ids[prompt_len:], np.int64)
    return logits.max(axis=-1) - logits[np.arange(n), served]


def check_served(params, n_head: int, samples: list, pad_to: int) -> dict:
    """``samples``: ``[{"prompt": ids, "tokens": served ids}]``.  ``ok`` when every
    served token is within ``GEN_LOGIT_TOL`` of the reference's best."""
    worst = 0.0
    for s in samples:
        ids = np.concatenate([np.asarray(s["prompt"], np.int32),
                              np.asarray(s["tokens"], np.int32)])
        margins = lm_logit_margins(params, n_head, ids, len(s["prompt"]), pad_to)
        worst = max(worst, float(margins.max()))
    return {"ok": worst <= GEN_LOGIT_TOL, "max_logit_margin": worst,
            "tol": GEN_LOGIT_TOL, "checked": len(samples)}


# -- ResNet v1.5 (training-mode forward + loss) --------------------------------

RESNET_BLOCKS = {18: ("basic", (2, 2, 2, 2)), 34: ("basic", (3, 4, 6, 3)),
                 50: ("bottleneck", (3, 4, 6, 3))}

# The program's first optimizer step reports the loss of the INITIAL parameters on
# its batch, computed in bf16 activations with float32 batch-norm statistics; the
# reference computes the same loss in float32 at highest precision.  A fresh
# 1000-class ResNet-50 reads 7.6 and bf16 rounding through 50-odd layers moved it by
# 0.025 on the chip (batch 128, PERF.md Findings; the CPU toy reads the same), so
# the tolerance is four times that; a wrong stem, stride, batch-norm reduction or
# label alignment changes the statistics of every later layer.
TRAIN_LOSS_TOL = 0.1


def resnet_train_loss(params, images, labels, depth: int, name: str,
                      bn_eps: float = 1e-3):
    """He et al. ResNet (v1.5: the stride sits on a block's 3x3) with the
    space-to-depth stem, batch norm on BATCH statistics (training mode), global
    average pool, dense head, mean sparse cross-entropy.  ``params`` is the tree
    ``resnet(depth, stem="s2d").init`` returns, keyed by layer name."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    kind, blocks = RESNET_BLOCKS[depth]

    def conv_bn(x, prefix, stride, relu=True):
        x = lax.conv_general_dilated(
            x, params[prefix + "_conv"]["W"], (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
        bn = params[prefix + "_bn"]
        x = (x - mean) / jnp.sqrt(var + bn_eps) * bn["gamma"] + bn["beta"]
        return jnp.maximum(x, 0.0) if relu else x

    x = jnp.asarray(images, jnp.float32)
    B, H, W, C = x.shape
    x = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(B, H // 2, W // 2, 4 * C)                 # space to depth, 2
    x = conv_bn(x, name + "_stem", 1)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for stage, n_blocks in enumerate(blocks):
        for b in range(n_blocks):
            pre = f"{name}_s{stage}b{b}"
            stride = 2 if (b == 0 and stage > 0) else 1
            short = conv_bn(x, pre + "_down", stride, relu=False) if b == 0 \
                else x
            if kind == "bottleneck":
                h = conv_bn(x, pre + "_1", 1)
                h = conv_bn(h, pre + "_2", stride)
                h = conv_bn(h, pre + "_3", 1, relu=False)
            else:
                h = conv_bn(x, pre + "_1", stride)
                h = conv_bn(h, pre + "_2", 1, relu=False)
            x = jnp.maximum(h + short, 0.0)
    x = jnp.mean(x, axis=(1, 2))
    fc = params[name + "_fc"]
    logits = x @ fc["W"] + fc["b"]
    picked = jnp.take_along_axis(
        logits, jnp.asarray(labels, jnp.int32).reshape(-1, 1), axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def check_first_loss(params, images, labels, depth: int, name: str,
                     served_loss: float) -> dict:
    import jax
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(resnet_train_loss, static_argnums=(3, 4))(
            params, images, labels, depth, name))
    return {"ok": abs(ref - served_loss) <= TRAIN_LOSS_TOL,
            "reference_loss": ref, "first_step_loss": served_loss,
            "tol": TRAIN_LOSS_TOL}
