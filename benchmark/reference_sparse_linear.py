"""Plain reference of a decoder that mixes decayed linear attention with block-sparse
grouped-query attention (the MiniCPM-SALA family), and the comparison that decides
``correct`` for it.

Nothing here calls the model code under test.  The forward is written out over the
parameter tree the program's ``build`` returns (``runs``: an attention layer's weights
as they are, a run of consecutive linear layers stacked on a leading axis; upcast to
float32 a layer at a time, so that it fits beside the served system), in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no cache, no kernel,
no batching, one sequence, a layer and a block of positions at a time.  ``cfg`` is the
configuration file: the published ``config.json`` keys as the file cuts them,
``published`` (the source's value of each key under ``reduced``) and ``sparse_config``.

Equations (``u`` the RMS-normed input of a sublayer, eps ``rms_norm_eps``, no biases, T
tokens at positions 0..T-1, ``r = scale_depth / sqrt(published num_hidden_layers)``):

- Model.  ``x = E[ids] * scale_emb``; a layer: ``h = x + r * Mixer(RMSNorm(x))``, ``x' =
  h + r * W_down(silu(W_gate u) * (W_up u))``; ``logits = W_head(RMSNorm(x_L) /
  (hidden_size / dim_model_base))``; untied head.
- ``lightning-attn``.  ``q, k, v = u W_q, u W_k, u W_v`` (``lightning_nh`` heads of
  ``lightning_head_dim``); RMS norm with a learned gain over the head dimension on ``q``
  and ``k`` (``qk_norm``); rotary (``rope_theta``, pairs (i, i + d / 2)) on both; per head
  ``S_t = lambda_h S_(t-1) + k_t^T v_t`` (``S`` d x d), ``o_t = (q_t / sqrt(d)) S_t``,
  the recurrence run position by position; ``o <- RMSNorm_head(o)`` (``use_output_norm``);
  ``o <- o * sigmoid(u W_g)`` (``use_output_gate``); ``o W_o``.  ``lambda_h = exp(-s_h)``,
  ``s_h = 2 ** (-8 (h + 1) / heads)``, h = 0 ...: Lightning Attention-2's per-head slopes
  (arXiv:2401.04658), with no per-layer factor (ASSUMED: the source gives no decay).
- ``minicpm4`` (InfLLM-V2; arXiv:2506.07900, arXiv:2509.24663; sizes from
  ``sparse_config``, ASSUMED from MiniCPM4.1).  ``q = u W_q`` (heads x d), ``k, v = u W_k,
  u W_v`` (kv heads x d), ``heads / kv heads`` query heads a key head (head h reads key
  head ``h // group``), the same norm on ``q`` and ``k``, NO rotary.  A query at position
  t whose context t + 1 is at most ``dense_len``: causal softmax attention at ``1 /
  sqrt(d)``.  Beyond it: ``kbar_j = mean(k[stride j : stride j + kernel_size])`` for every
  complete window; ``p = softmax_j(q_t . kbar_j / sqrt(d))`` over the windows that end at
  or before t (``stride j + kernel_size - 1 <= t``), per head; summed over the group's
  heads; a block's score = the maximum over the windows that overlap it (a max-pool of 5,
  stride 4, padding 1 over the windows, at kernel 32, stride 16, block 64); the first
  ``init_blocks`` blocks and the ``window_size / block_size`` blocks that end with the
  query's own always count as best (score +inf); the ``topk`` best blocks are kept
  (equal scores: the earlier block first), the same for all heads of the group; causal
  softmax attention over the tokens of the kept blocks.  ``o <- o * sigmoid(u W_g)``
  (``attn_use_output_gate``); ``o W_o``.

Departures from the published model, each shared with the program: the published
kernels' coarse second-level log-sum-exp approximation of the compressed scores is left
out (the softmax over ``kbar`` is exact); the forced blocks count INSIDE the ``topk``; the
local window is ``window_size / block_size`` whole blocks ending with the query's own (the
last 1,985-2,048 positions at block 64), as MiniCPM4's ``local_blocks``; ``dense_len``
applies to each QUERY's own context, so that decoding past it agrees with a forward over
the whole sequence; the layers are the
file's ``mixer_types`` (a contiguous run of the published 32) while ``r`` keeps the
published depth.
"""

from __future__ import annotations

import functools
import json

import numpy as np

POSITION_BLOCK = 128
SPARSE, LINEAR = "minicpm4", "lightning-attn"

# What decides ``correct`` for served tokens (``check_served``): in the reference's
# teacher-forced forward of the same weights, how far each served token's logit lies
# under that position's best (0 where the served token IS the reference's best).  The
# served path multiplies in bfloat16, so a near-tie of logits may resolve otherwise, and
# so may a near-tie of the selection's 64th and 65th block, which moves that position's
# logits.  muP divides the final hidden state by hidden_size / dim_model_base = 16, so
# with weights of std 0.02 the logits spread over 0.080 a unit and the best two lie
# 0.019 apart on average: every margin is small in absolute terms, and the limits stand
# where the readings lie.  Two limits, each set between two readings taken at the
# published widths on one v5e chip (PERF.md, sections 4 and 6; my chip runs, PR 35):
#   mean margin: the served system reads 0.00001-0.00008 (3 % of the tokens off the
#     reference's best, by little); the reference's OWN tokens when it multiplies in
#     float8_e4m3fn, the next precision under the configuration's bfloat16, read
#     0.0147-0.0215 (half of the tokens off): the limit 0.001 is 12 x over the first,
#     15 x under the second.
#   max margin: served 0.0008-0.0041 (the larger over 2,444 tokens of a window's two
#     requests), float8 0.078-0.108 over 65 tokens a request: the limit 0.02.  A wrong
#     block, slot or state shows as margins of the logits' own spread at every later
#     token.
#   The control through ``check_served`` itself (``tests/lower_precision_control.py``:
#   the cell's own traffic, the weights SERVED through float8_e4m3fn, scored here over
#   the weights as built): mean 0.0125, max 0.113 over 2,906 tokens: not ``ok`` by both.
# (The reference in bfloat16 operands reads 0.00008 / 0.0028 where the served system read
# 0.00008 / 0.0028 on the same request: what the system shows is bfloat16's own.  The
# reference with its recurrent state kept in bfloat16 serves the float32 tokens, margin
# 0: with these weights the state's precision does not reach the best logit, so it is
# the float8 reading that the limits exclude, and the state's float32 is pinned by
# ``tests/test_sparse_linear_lm.py``, not by this comparison.)
MEAN_MARGIN_TOL = 0.001
MAX_MARGIN_TOL = 0.02


def _f32(a):
    import jax.numpy as jnp
    return jnp.asarray(a, jnp.float32)


def _rms(g, x, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _rotary(x, pos, theta):
    """Pairs (i, i + d / 2) of the last axis of ``x`` (T, ..., d) turned by
    ``pos * theta ** (-2i / d)`` (the published ``rotate_half`` form)."""
    import jax.numpy as jnp
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            lo * jnp.sin(ang) + hi * jnp.cos(ang)], axis=-1)


def _lower(round_to):
    """``r(x)``: ``x`` through the precision ``round_to`` and back (identity for
    None): how a lower-precision matmul sees its operands."""
    if round_to is None:
        return lambda x: x
    return lambda x: _f32(_f32(x).astype(round_to))


def residual_scale(cfg: dict) -> float:
    depth = (cfg.get("published") or {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"])
    return cfg.get("scale_depth", 1.0) / np.sqrt(depth)


def decays(cfg: dict) -> np.ndarray:
    """``lambda_h`` of the linear layers' heads."""
    nh = cfg["lightning_nh"]
    return np.exp(-2.0 ** (-8.0 * np.arange(1, nh + 1) / nh)).astype(np.float32)


def layers(params, cfg: dict):
    """``(kind, weights)`` of every layer in order, out of ``params["runs"]``."""
    import jax
    kinds, runs, i = cfg["mixer_types"], iter(params["runs"]), 0
    while i < len(kinds):
        run = next(runs)
        if kinds[i] == SPARSE:
            yield SPARSE, run
            i += 1
        else:
            n = run["ln1"].shape[0]
            for j in range(n):
                yield LINEAR, jax.tree.map(lambda a: a[j], run)
            i += n


def _blocked(a, n):
    return a.reshape((a.shape[0] // n, n) + a.shape[1:])


def _mlp(blk, cfg, h, r):
    import jax
    u = _rms(blk["ln2"], h, cfg["rms_norm_eps"])
    a = r(u) @ r(_f32(blk["gate"]))
    return r(jax.nn.silu(a) * (r(u) @ r(_f32(blk["up"])))) @ r(_f32(blk["down"]))


def linear_layer(blk, cfg, x, r, state_dtype=None):
    """One ``lightning-attn`` layer over ``x`` (T, H): a block of positions at a time,
    inside it the recurrence position by position."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    nh, d, eps = cfg["lightning_nh"], cfg["lightning_head_dim"], cfg["rms_norm_eps"]
    lam = jnp.asarray(decays(cfg))[:, None, None]
    scale_r = residual_scale(cfg)
    nb = min(POSITION_BLOCK, T)

    def block(S, inp):
        xb, pos = inp
        u = _rms(blk["ln1"], xb, eps)
        q = _rms(blk["q_ln"], (r(u) @ r(_f32(blk["q"]))).reshape(nb, nh, d), eps)
        k = _rms(blk["k_ln"], (r(u) @ r(_f32(blk["k"]))).reshape(nb, nh, d), eps)
        v = (r(u) @ r(_f32(blk["v"]))).reshape(nb, nh, d)
        q = _rotary(q, pos, cfg["rope_theta"]) / np.sqrt(d)
        k = _rotary(k, pos, cfg["rope_theta"])

        def step(S, qkv):
            qt, kt, vt = qkv                                     # (heads, d)
            S = lam * S + kt[:, :, None] * vt[:, None, :]
            if state_dtype is not None:
                S = _f32(S.astype(state_dtype))
            return S, (qt[:, :, None] * S).sum(axis=1)

        S, o = jax.lax.scan(step, S, (q, k, v))
        o = _rms(blk["o_ln"], o, eps).reshape(nb, nh * d) \
            * jax.nn.sigmoid(r(u) @ r(_f32(blk["g"])))
        h = xb + scale_r * (r(o) @ r(_f32(blk["o"])))
        return S, h + scale_r * _mlp(blk, cfg, h, r)

    _, out = jax.lax.scan(block, jnp.zeros((nh, d, d), jnp.float32),
                          (_blocked(x, nb), _blocked(jnp.arange(T), nb)))
    return out.reshape(T, -1)


def kept_blocks(cfg, q, kbar, t, n_blocks):
    """The blocks each key head keeps for the queries ``q`` (Q, kv heads, group, d) at
    positions ``t`` (Q,), from the compressed keys ``kbar`` (windows, kv heads, d):
    a mask (kv heads, Q, n_blocks)."""
    import jax
    import jax.numpy as jnp
    sc = cfg["sparse_config"]
    kernel, stride, block = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    d = q.shape[-1]
    qb = t // block
    b = jnp.arange(n_blocks)
    causal = b[None, :] <= qb[:, None]                                # (Q, blocks)
    ends = jnp.arange(kbar.shape[0]) * stride + kernel - 1
    seen = ends[None, :] <= t[:, None]                                # (Q, windows)
    s = jnp.einsum("qgjd,wgd->gjqw", q, kbar) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    p = jnp.where(seen, p, 0.0).sum(axis=1)                           # (g, Q, windows)
    # the windows that overlap block b: stride j < block (b + 1) and stride j +
    # kernel > block b: a max-pool over the windows
    per, width = block // stride, (block + kernel) // stride - 1
    pad = (kernel - 1) // stride
    right = per * (n_blocks - 1) + width - pad - p.shape[-1]
    score = jax.lax.reduce_window(
        jnp.pad(p, ((0, 0), (0, 0), (pad, max(right, 0)))), -jnp.inf, jax.lax.max,
        (1, 1, width), (1, 1, per), "VALID")[..., :n_blocks]
    forced = (b[None, :] < sc["init_blocks"]) \
        | (b[None, :] > qb[:, None] - sc["window_size"] // block)
    score = jnp.where(forced[None], jnp.inf, score)
    score = jnp.where(causal[None], score, -jnp.inf)
    best = jnp.argsort(-score, axis=-1, stable=True)[..., :sc["topk"]]
    chosen = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None, None],
        jnp.arange(score.shape[1])[None, :, None], best].set(True)
    dense = (t + 1 <= sc["dense_len"])[None, :, None]
    return jnp.where(dense, causal[None], chosen & causal[None])


def sparse_layer(blk, cfg, x, r):
    """One ``minicpm4`` layer over ``x`` (T, H): keys, values and compressed keys of the
    whole sequence first, then a block of queries at a time over full score rows."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    nh, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    J, eps, sc = nh // G, cfg["rms_norm_eps"], cfg["sparse_config"]
    kernel, stride, block = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    scale_r = residual_scale(cfg)
    nb = min(POSITION_BLOCK, T)

    def kv(xb):
        u = _rms(blk["ln1"], xb, eps)
        return (_rms(blk["k_ln"], (r(u) @ r(_f32(blk["k"]))).reshape(nb, G, d), eps),
                (r(u) @ r(_f32(blk["v"]))).reshape(nb, G, d))

    k, v = jax.lax.map(kv, _blocked(x, nb))
    k, v = k.reshape(T, G, d), v.reshape(T, G, d)
    n_win = max((T - kernel) // stride + 1, 0)
    kbar = k[jnp.arange(n_win)[:, None] * stride + jnp.arange(kernel)[None, :]] \
        .mean(axis=1)                                                 # (windows, G, d)
    n_blocks = -(-T // block)
    s_idx = jnp.arange(T)

    def queries(inp):
        xb, t = inp
        u = _rms(blk["ln1"], xb, eps)
        q = _rms(blk["q_ln"], (r(u) @ r(_f32(blk["q"]))).reshape(nb, nh, d), eps) \
            .reshape(nb, G, J, d)
        keep = kept_blocks(cfg, q, kbar, t, n_blocks)                 # (G, Q, blocks)
        allowed = jnp.repeat(keep, block, axis=-1)[..., :T] \
            & (s_idx[None, None, :] <= t[None, :, None])
        s = jnp.einsum("qgjd,sgd->gjqs", r(q), r(k)) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("gjqs,sgd->qgjd", r(p), r(v)).reshape(nb, nh * d) \
            * jax.nn.sigmoid(r(u) @ r(_f32(blk["g"])))
        h = xb + scale_r * (r(o) @ r(_f32(blk["o"])))
        return h + scale_r * _mlp(blk, cfg, h, r), keep.sum(-1)

    out, kept = jax.lax.map(queries, (_blocked(x, nb), _blocked(s_idx, nb)))
    return out.reshape(T, -1), kept.transpose(1, 0, 2).reshape(G, T)


@functools.lru_cache(maxsize=8)
def _jitted_layer(cfg_json: str, kind: str, round_to, state_dtype):
    import jax
    cfg = json.loads(cfg_json)
    r = _lower(round_to)
    if kind == SPARSE:
        return jax.jit(lambda blk, x: sparse_layer(blk, cfg, x, r))
    return jax.jit(lambda blk, x: (linear_layer(blk, cfg, x, r, state_dtype), None))


def logits(params, cfg: dict, ids, rows=None, round_to=None, state_dtype=None,
           probe=None) -> np.ndarray:
    """Teacher-forced float32 logits of the sequence ``ids`` (T,) at positions ``rows``
    (default: all), (len(rows), vocab).  ``round_to`` computes every matmul over operands
    rounded to that type, ``state_dtype`` keeps the linear layers' state in that type:
    the readings a lower precision gives.  ``probe`` (a list) receives, for every
    attention layer, the blocks each key head kept a query (kv heads, T).  A layer is one
    jitted function (of the sequence's length and the layer's kind)."""
    import jax
    import jax.numpy as jnp
    ids = np.asarray(ids, np.int32)
    if len(ids) > POSITION_BLOCK and len(ids) % POSITION_BLOCK:
        raise ValueError(f"pad the sequence to a multiple of {POSITION_BLOCK}")
    rows = np.arange(len(ids)) if rows is None else np.asarray(rows)
    r = _lower(round_to)
    key = json.dumps(cfg, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], jnp.asarray(ids), axis=0)) \
            * cfg.get("scale_emb", 1.0)
        for kind, blk in layers(params, cfg):
            x, kept = _jitted_layer(key, kind, round_to, state_dtype)(blk, x)
            if probe is not None and kept is not None:
                probe.append(np.asarray(kept))
        h = _rms(params["ln_f"], x[rows], cfg["rms_norm_eps"]) \
            / (cfg["hidden_size"] / cfg.get("dim_model_base", cfg["hidden_size"]))
        return np.asarray(r(h) @ r(_f32(params["head"])))


def margins(params, cfg: dict, ids, prompt_len: int, pad_to: int, **lower):
    """``ids`` = prompt + served tokens, right-padded to ``pad_to`` (every layer is
    causal, which makes the padding harmless, and one padded length is one set of
    compiled shapes).  Returns, for each served token, (best logit at its position) -
    (its own logit)."""
    n = len(ids) - prompt_len
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(ids)] = ids
    out = logits(params, cfg, padded,
                 np.arange(prompt_len - 1, prompt_len - 1 + n), **lower)
    served = np.asarray(ids[prompt_len:], np.int64)
    return out.max(axis=-1) - out[np.arange(n), served]


def check_served(params, cfg: dict, samples: list, pad_to: int) -> dict:
    """``samples``: ``[{"prompt": ids, "tokens": served ids}]``.  ``ok`` when the
    served tokens' mean margin is within ``MEAN_MARGIN_TOL`` and none exceeds
    ``MAX_MARGIN_TOL``."""
    got = [margins(params, cfg, np.concatenate(
        [np.asarray(s["prompt"], np.int32), np.asarray(s["tokens"], np.int32)]),
        len(s["prompt"]), pad_to) for s in samples]
    every = np.concatenate(got)
    mean, worst = float(every.mean()), float(every.max())
    return {"ok": mean <= MEAN_MARGIN_TOL and worst <= MAX_MARGIN_TOL,
            "mean_logit_margin": mean, "max_logit_margin": worst,
            "tokens_off_best": int((every > 0).sum()),
            "mean_tol": MEAN_MARGIN_TOL, "max_tol": MAX_MARGIN_TOL,
            "checked": len(samples), "tokens_checked": int(every.size)}
